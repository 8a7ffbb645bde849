"""One prompt alone in a serve cell's server, on the chip: how long its
prefill takes (the number a configuration's `serve.prefill_chunk` is chosen
by) and what a decode round then costs, through `generate_stream` as the
driver calls it.  Before that, where the configuration has latent attention,
the paged kernel against the gather oracle on the device, one layer's worth
at the published widths (a fault there would otherwise show only as
`correct: false` a whole run later).

    python3 benchmark/tools/lone_prefill.py <workload> <prompt tokens> \
        [<answer tokens>] [--serve slots=8,prefill_chunk=512 ...]

Each `--serve` tries the file's `serve` group with those numbers laid over
it, one server each (none: the file's own).  Not part of a run.
"""

from __future__ import annotations

import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))


def kernel_against_oracle(cfg, say):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.parallel import generation as g
    from deeplearning4j_tpu.parallel import transformer as tfm

    import dataclasses

    small = dataclasses.replace(cfg, n_layers=1, dense_layers=1,
                                vocab_size=1024, max_len=2048)
    params = tfm.init_params(small, jax.random.PRNGKey(0))
    ps, pages, lanes = 128, 40, 2
    mp = g.pages_per_seq(small, ps)
    table = np.zeros((lanes, mp), np.int32)
    table[0, :12] = np.arange(1, 13)
    table[1, :12] = np.arange(13, 25)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (lanes, 1300), 0, 1024)
    out = {}
    for kernel in (False, True):
        cache = g.init_paged_cache(small, pages, ps)
        fwd = jax.jit(lambda c, pos, nf, tok, k=kernel: g.paged_forward(
            small, params, c, jnp.asarray(table), pos, nf, tok,
            paged_kernel=k))
        pos, got = np.zeros(lanes, np.int32), []
        for width, n in ((256, 5), (1, 20)):
            for _ in range(n):
                at = int(pos[0])
                lg, cache = fwd(cache, jnp.asarray(pos),
                                jnp.full((lanes,), width, jnp.int32),
                                tokens[:, at:at + width])
                got.append(np.asarray(lg[:, -1].astype(jnp.float32)))
                pos += width
        out[kernel] = np.stack(got)
    say("latent_kernel_against_oracle",
        max_abs_difference=float(np.max(np.abs(out[True] - out[False]))),
        logits_deviation=float(np.std(out[False])))


def main(argv) -> int:
    import jax
    import numpy as np

    from benchmark import device, spec
    from benchmark.observe import say
    from deeplearning4j_tpu.ui import UiServer

    overlays = [dict((k, int(v)) for k, v in (kv.split("=") for kv in
                                               argv[i + 1].split(",")))
                for i, a in enumerate(argv) if a == "--serve"] or [{}]
    argv = argv[:argv.index("--serve")] if "--serve" in argv else argv
    cell = spec.load_cell(argv[0])
    n_prompt = int(argv[1])
    n_answer = int(argv[2]) if len(argv) > 2 else 64
    device.acquire(cell.chips, tiny=False)
    config = cell.config
    adapter = spec.adapter(config)
    cfg = adapter.program_config(config, config["dtype"], remat=False)
    if getattr(cfg, "latent", None) is not None:
        kernel_against_oracle(cfg, say)
    params = jax.block_until_ready(
        adapter.make_params(cfg, 1, config["dtype"]))
    rng = np.random.default_rng(7)
    for overlay in overlays:
        serve = {**config["serve"], **overlay}
        srv = UiServer(port=0)
        srv.serve_lm(cfg, params, **serve)
        srv.start()
        lm = srv.state.lm_server
        try:
            t = time.perf_counter()
            lm.warmup()
            warm = time.perf_counter() - t
            for trial in range(2):
                prompt = [int(x) for x in
                          rng.integers(0, cfg.vocab_size, n_prompt)]
                t0, times = time.perf_counter(), []
                for _ in lm.generate_stream(prompt, n_answer):
                    times.append(time.perf_counter() - t0)
                gaps = np.diff(times)
                say("lone_prompt", serve=serve, trial=trial,
                    prompt_tokens=n_prompt, first_token_s=times[0],
                    decode_ms_per_token={"median": 1e3 * float(
                        np.median(gaps)), "max": 1e3 * float(np.max(gaps))},
                    warmup_s=warm)
        finally:
            srv.stop()
        del lm, srv
    say("memory", **{str(d.id): d.memory_stats().get("peak_bytes_in_use")
                     for d in jax.local_devices()})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
