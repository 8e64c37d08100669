"""Kernel microbenches (CPU interpret mode — correctness-level timing only;
the BlockSpec/VMEM reasoning that matters for TPU is in each kernel's
docstring and the §Perf log).  Prints name,us_per_call,derived CSV rows."""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp


def _time(fn, *args, iters=3) -> float:
    fn(*args)  # compile/warm
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def bench_rows() -> list[tuple[str, float, str]]:
    rng = np.random.default_rng(0)
    rows = []

    # flash attention vs dense reference (small shape; interpret mode)
    from repro.kernels.flash_attention.kernel import flash_attention_hmajor
    from repro.kernels.flash_attention.ref import attention_ref
    b, h, kvh, s, d = 1, 4, 2, 512, 64
    q = jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, kvh, s, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, kvh, s, d)).astype(np.float32))
    t_ref = _time(lambda: attention_ref(q, k, v, causal=True))
    t_ker = _time(lambda: flash_attention_hmajor(q, k, v, causal=True,
                                                 block_q=128, block_k=128))
    err = float(jnp.abs(
        flash_attention_hmajor(q, k, v, causal=True, block_q=128,
                               block_k=128)[0]
        - attention_ref(q, k, v, causal=True)).max())
    rows.append(("flash_attention_interp", t_ker, f"ref_us={t_ref:.0f};max_err={err:.1e}"))

    # rglru kernel vs sequential scan ref
    from repro.kernels.rglru_scan.kernel import rglru_scan_pallas
    from repro.kernels.rglru_scan.ref import rglru_scan_ref
    la = -jnp.abs(jnp.asarray(rng.normal(size=(2, 512, 256)).astype(np.float32))) * 0.1
    bb = jnp.asarray(rng.normal(size=(2, 512, 256)).astype(np.float32))
    h0 = jnp.zeros((2, 256), jnp.float32)
    t_ref = _time(lambda: rglru_scan_ref(la, bb, h0))
    t_ker = _time(lambda: rglru_scan_pallas(la, bb, h0, block_t=128,
                                            block_w=256))
    err = float(jnp.abs(rglru_scan_pallas(la, bb, h0, block_t=128, block_w=256)
                        - rglru_scan_ref(la, bb, h0)).max())
    rows.append(("rglru_scan_interp", t_ker, f"ref_us={t_ref:.0f};max_err={err:.1e}"))

    # mlstm chunk kernel vs chunkwise ref
    from repro.kernels.mlstm_chunk.kernel import mlstm_chunk_pallas
    from repro.kernels.mlstm_chunk.ref import mlstm_ref
    b, h, s, dh = 1, 4, 512, 64
    q = jnp.asarray(rng.normal(size=(b, h, s, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, h, s, dh)).astype(np.float32)) / 8
    v = jnp.asarray(rng.normal(size=(b, h, s, dh)).astype(np.float32))
    li = jnp.asarray(rng.normal(size=(b, h, s)).astype(np.float32))
    lf = jnp.log(jax.nn.sigmoid(jnp.asarray(
        rng.normal(size=(b, h, s)).astype(np.float32))))
    t_ref = _time(lambda: mlstm_ref(q, k, v, li, lf))
    t_ker = _time(lambda: mlstm_chunk_pallas(q, k, v, li, lf, chunk=128))
    err = float(jnp.abs(mlstm_chunk_pallas(q, k, v, li, lf, chunk=128)
                        - mlstm_ref(q, k, v, li, lf)).max())
    rows.append(("mlstm_chunk_interp", t_ker, f"ref_us={t_ref:.0f};max_err={err:.1e}"))
    return rows


def main():
    for name, us, derived in bench_rows():
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
