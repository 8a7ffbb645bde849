"""What the readers of the recurrent (KDA) layers share: which operations of
a device trace are the delta rule's, and how long they ran.

The program computes the chunked form and the width-1 update with the
compiler's own lowering (`deeplearning4j_tpu/parallel/kda.py`), so no kernel
has a name to find it by; its operations are known by what they produce.  A
TPU operation event is named by its whole HLO instruction, and the delta
rule's results are float32 arrays of shapes nothing else in a step program
has (taken from the compiled step programs' HLO and a v5e trace, PR 38),
with `L` lanes, `H` heads, state `K x V`:

- `f32[.., H, K, V]`: a lane's states `[L, H, K, V]` (the width-1 update's
  `multiply_add_fusion`, the chunk scan's carry) and the state pool itself
  (`[n * rows, H, K, V]`: the rows gathered and written where they lie);
- `f32[L, H, n, c, ..]`: the chunked form's blocks before the scan (decayed
  Gram blocks, the triangular solve's `custom-call`, pseudo-values);
- `f32[n, L, H, ..]`: the same blocks laid out for the scan over chunks, and
  the `while` that is the scan (its own time is the loop's overhead; the
  operations nested in it are counted by their own shapes).

Projections, convolutions, norms and gates around the rule (`attn:kda`
outside `kda:chunk` / `kda:step`) produce `[L, C, H, K]` or two- and
three-dimensional arrays and are not counted.  An operation's time is its
own (its interval less the operations nested in it), so the scan's body is
counted once.
"""

from __future__ import annotations

import re

from benchmark import readings, trace_reduce

RESULT = re.compile(r"^%[\w.\-]+ = (.*?) [a-z\-]+\(")


def pattern(lanes: int, heads: int, k_dim: int, v_dim: int):
    h = str(int(heads))
    return re.compile(
        rf"f32\[(?:\d+,)+{h},{int(k_dim)},{int(v_dim)}\]"
        rf"|f32\[{int(lanes)},{h},\d+,\d+(?:,\d+)*\]"
        rf"|f32\[\d+,{int(lanes)},{h}(?:,\d+)+\]")


def kda_seconds(run, steps):
    """Own seconds of the delta rule's operations inside the step-program
    events `steps`, or None where the model has no such layer."""
    linear = getattr(run.model, "linear", None)
    if linear is None or run.device_trace is None or not steps:
        return None
    found = pattern(run.counters["after"]["slots"], linear.heads,
                    linear.k_dim, linear.v_dim)
    ops = sorted(readings.ops_within(run, steps), key=lambda e: e.start)
    own = trace_reduce.self_times(ops, float("-inf"), float("inf"))
    total = 0.0
    for name, seconds in own.items():
        m = RESULT.match(name)
        if m and found.search(m.group(1)):
            total += seconds
    return total


def paged_programs(run):
    """`readings.paged_programs` ({feed width: launches}) with the width read
    off the first launch of a program that HAS the paged kernel inside, and
    the launches before that one left out.  A wide launch of this family is
    80-90 ms long and its one grouped-query layer is its first, so where the
    trace begins inside a wide launch the kernel ran before it and the
    launch's first event carries no width; `readings.paged_programs` looks at
    that first event alone and then drops the program, and
    `kda_chunk_roofline` had nothing to read in one traced run of four (my
    chip runs, PR 38)."""
    out = {}
    for launches in readings.step_programs(run):
        for i, launch in enumerate(launches):
            widths = [m.group(1)
                      for op in readings.ops_within(run, [launch])
                      if (m := readings.PAGED_KERNEL.search(op.name))]
            if widths:
                out[int(widths[0])] = launches[i:]
                break
    return out


def layers(cfg) -> int:
    return sum(kind == "kda" for kind in cfg.mixer_kinds())


def itemsize(cfg) -> int:
    """Bytes a value of the model's dtype (tails and pages; the state is
    float32 whatever it is)."""
    return 2 if cfg.dtype == "bfloat16" else 4
