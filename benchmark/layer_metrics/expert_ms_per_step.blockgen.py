"""`expert_ms_per_step` on the block-generation cell: device time of the
expert layers' grouped matmuls (the TPU compiler's `ragged-dot` custom
calls, three an expert layer, over all eight sorted pairs a token, every one
of the 128 experts held) per dispatch of either step program in the traced
window.  The reader of the long-document cell (the accepted pattern and
arithmetic, with the launches counted as `readings_kda.paged_programs`
counts them: a launch cut by the trace's start is left out and the program
kept), for a block model alone.  A narrow round of 64 lanes sorts 2,048 rows,
16 an expert, and the compiler keeps `ragged-dot` there."""

from benchmark import spec

NAME, UNIT, BETTER = "expert_ms_per_step.blockgen", "ms", "lower"
LAYER, MOVES, SOURCE = "Expert layer", "serve_tokens_per_s", "device_trace"

_read = spec.reader("layer_metrics", "expert_ms_per_step.longdoc").read


def read(run):
    if (getattr(run.model, "block_length", 1) < 2
            or run.device_trace is None):
        return None
    return _read(run)
