"""`expert_ms_per_step` on the long-document cell: device time of the expert
layers' grouped matmuls (the TPU compiler's `ragged-dot` custom calls, three
an expert layer, over ALL eight sorted pairs a token though an eighth of the
experts is held) per dispatch of either step program in the traced window.
The accepted reader's pattern and arithmetic; the launches are the ones this
cell's other readers count (`readings_kda.paged_programs`: a wide launch cut
by the trace's start is left out and the program kept), so that a traced
window that begins inside a wide round does not read 0.  At width 1 (32
sorted rows at 4 lanes) the compiler lowers `ragged_dot` to plain fusions,
which are not in it, as in the document cell."""

from benchmark import readings, readings_kda, spec

NAME, UNIT, BETTER = "expert_ms_per_step.longdoc", "ms", "lower"
LAYER, MOVES, SOURCE = "Expert layer", "serve_tokens_per_s", "device_trace"

RAGGED_DOT = spec.reader("layer_metrics", "expert_ms_per_step").RAGGED_DOT


def read(run):
    if getattr(run.model, "experts", None) is None:
        return None
    programs = readings_kda.paged_programs(run).values()
    steps = sum(len(events) for events in programs)
    if not steps:
        return None
    seconds = sum(readings.op_seconds(run, RAGGED_DOT, within=events)
                  for events in programs)
    return 1e3 * seconds / steps
