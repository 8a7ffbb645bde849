"""The flash attention kernels' share of their roofline: for every call in
the traced train steps, the least time the chip could take (the larger of
its operations over the bf16 peak and its bytes over the HBM bandwidth,
`benchmark/flops.py`), summed, over the kernels' device time.

In a v5e trace (PR 23) each kernel is a `custom-call` on [batch x heads,
sequence, head size]: the dK/dV kernel is the one with two outputs; of the
others, those named after `checkpoint` (the backward of a rematerialized
block) are dQ, the rest (`jvp`, `rematted_computation`) the forward.  At
S 1024 and head size 64 the forward's two bounds are 0.044 ms of operations
and 0.041 ms of bytes: the compute bound holds, by little.
"""

import re

from benchmark import flops, readings

NAME, UNIT, BETTER = "flash_attention_roofline", "%", "higher"
LAYER, MOVES, SOURCE = ("Flash attention kernels", "train_tokens_per_s",
                        "device_trace")
KERNEL = re.compile(r"^%([\w\-]+?)[.\d]* = (\()?bf16\[(\d+),(\d+),(\d+)\]"
                    r".*? custom-call\(")


def kind(match) -> str:
    if match.group(2):
        return "dkdv"
    return "dq" if "checkpoint" in match.group(1) else "forward"


def read(run):
    steps = readings.train_steps(run)
    if not steps or run.peaks is None:
        return None
    least = spent = 0.0
    for op in readings.ops_within(run, steps):
        m = KERNEL.match(op.name)
        if not m:
            continue
        bh, seq, dh = (int(m.group(i)) for i in (3, 4, 5))
        ops_ = flops.flash_attention_flops(bh, seq, dh, kind(m))
        bytes_ = flops.flash_attention_bytes(bh, seq, dh, kind(m))
        least += max(ops_ / run.peaks["bf16_flops_per_s"],
                     bytes_ / run.peaks["hbm_bytes_per_s"])
        spent += op.dur
    return 100.0 * least / spent if spent else None
