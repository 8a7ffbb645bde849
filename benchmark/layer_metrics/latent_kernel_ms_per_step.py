"""Device time of the latent paged attention kernel's calls (one per layer,
`latent_paged_attention`) per dispatch of a step program, either width.  The
kernel is found as the paged kernel is: a `custom-call` whose first operand
is the block table and whose result is `bf16[lanes, width, heads, rank]`."""

from benchmark import readings

NAME, UNIT, BETTER = "latent_kernel_ms_per_step", "ms", "lower"
LAYER, MOVES, SOURCE = ("Latent attention kernel", "serve_tokens_per_s",
                        "device_trace")


def read(run):
    if getattr(run.model, "latent", None) is None:
        return None
    steps = [e for p in readings.paged_programs(run).values() for e in p]
    if not steps:
        return None
    seconds = readings.op_seconds(run, readings.PAGED_KERNEL, within=steps)
    return 1e3 * seconds / len(steps)
