"""Ring-flash long-context evidence: S=4096 sharded 8 ways on the
virtual CPU mesh, fwd+bwd vs the dense oracle, with wall timings.  The
ring is causal, so each row is dealt zigzag over the 8 devices on the way
in (`zigzag_order`: device i holds chunks i and 15-i of 16) and put back
on the way out; the oracle sees the natural order.

Proves the SURVEY §5 long-context extension at a length where blocking
and ring scheduling actually engage (the 2015 reference's long-sequence
story is one LSTM scanning timesteps, `GravesLSTM.java:108`)."""

from _common import capture, ensure_cpu_mesh, write_log

ensure_cpu_mesh(8)

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from deeplearning4j_tpu.parallel import make_mesh  # noqa: E402
from deeplearning4j_tpu.parallel.mesh import shard_map  # noqa: E402
from deeplearning4j_tpu.parallel.ring_attention import (  # noqa: E402
    attention,
    ring_flash_attention,
    zigzag_order,
)


def dealt(ring, order):
    """`ring` on rows in the natural order: dealt in, put back out."""
    back = np.argsort(order)
    return lambda q, k, v: ring(q[:, order], k[:, order], v[:, order])[
        :, back]


def main() -> None:
    B, S, H, D, N = 1, 4096, 2, 32, 8
    print(f"devices: {len(jax.devices())} ({jax.default_backend()}); "
          f"B={B} S={S} H={H} D={D}, seq sharded {N} ways "
          f"(S_local={S // N})")
    rng = np.random.default_rng(11)
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
               for _ in range(3))
    mesh = make_mesh((N,), ("seq",), devices=jax.devices()[:N])
    ring = dealt(shard_map(
        lambda q, k, v: ring_flash_attention(q, k, v, "seq", causal=True),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq")), zigzag_order(N, S))

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(attention(q, k, v, causal=True) ** 2)

    jr = jax.jit(jax.value_and_grad(loss_ring, (0, 1, 2)))
    jd = jax.jit(jax.value_and_grad(loss_dense, (0, 1, 2)))
    t0 = time.perf_counter()
    lr_, gr = jax.block_until_ready(jr(q, k, v))
    print(f"ring-flash fwd+bwd compile+run: {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    ld_, gd = jax.block_until_ready(jd(q, k, v))
    print(f"dense oracle fwd+bwd compile+run: {time.perf_counter() - t0:.1f}s")
    for name, fn in (("ring-flash", jr), ("dense", jd)):
        t0 = time.perf_counter()
        for _ in range(3):
            out = fn(q, k, v)
        jax.block_until_ready(out)
        print(f"{name} steady fwd+bwd: {(time.perf_counter() - t0) / 3 * 1e3:.0f} ms")
    err_f = float(jnp.max(jnp.abs(lr_ - ld_)) / jnp.maximum(jnp.abs(ld_), 1))
    err_g = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(gr, gd))
    print(f"loss rel err: {err_f:.2e}; max grad abs err: {err_g:.2e}")
    assert err_f < 1e-5 and err_g < 5e-4, (err_f, err_g)
    print("GREEN: ring-flash @S=4096 sharded 8 ways matches the dense "
          "oracle fwd+bwd")

    # Leg 2: S=16384 — the bench_longctx length.  A global dense oracle
    # would materialize [16384, 16384] scores, so the reference here is
    # the ring schedule with the DENSE per-hop inner (exact blockwise
    # softmax-merge), which the flash inner must match.  Both engines
    # take the same dealt rows, so nothing is dealt or put back here:
    # the random rows ARE the dealt ones.
    from deeplearning4j_tpu.parallel.ring_attention import ring_attention

    S2 = 16384
    q2, k2, v2 = (jnp.asarray(
        rng.standard_normal((1, S2, 2, 32)), jnp.float32) for _ in range(3))

    def make(fn):
        return jax.jit(shard_map(
            lambda q, k, v: fn(q, k, v, "seq", causal=True), mesh=mesh,
            in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq")))

    rf, rd = make(ring_flash_attention), make(ring_attention)
    t0 = time.perf_counter()
    out_f = jax.block_until_ready(rf(q2, k2, v2))
    tf = time.perf_counter() - t0
    t0 = time.perf_counter()
    out_d = jax.block_until_ready(rd(q2, k2, v2))
    td = time.perf_counter() - t0
    err = float(jnp.max(jnp.abs(out_f - out_d)))
    print(f"S=16384 fwd: ring-flash {tf:.1f}s vs ring-dense {td:.1f}s "
          f"(incl. compile); max abs err {err:.2e}")
    assert err < 5e-5, err
    print("GREEN: ring-flash @S=16384 (bench length) matches the exact "
          "ring schedule")


if __name__ == "__main__":
    with capture() as buf:
        main()
    write_log("longctx", buf.getvalue())
