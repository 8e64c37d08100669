"""Smoke test of the PHSFL main path on a TPU.

    python chip_smoke.py              # one chip: kernels, edge round, decode
    python chip_smoke.py --chips 4    # only: the edge psum on a (4, 1) mesh
                                      # against the single-device host round

With ``--chips 4`` the script runs one masked edge round of four clients
(xLSTM-350M's widths at 4 layers, float32) through ``make_phsfl_round`` on
a (4, 1) mesh and through ``make_host_round`` on one chip, and compares the
aggregated parameters and the loss.  Without it, on one chip, in order:

(a) the JAX version and the devices; anything but a TPU exits nonzero;
(b) the four Pallas kernels, compiled (not interpreted), against their
    ``ref.py`` oracles, both under ``default_matmul_precision("highest")``
    (which also makes the kernels' own dots contract in fp32);
(c) ``repro.launch.train.main`` on xLSTM-350M at its published widths: three
    masked edge rounds of two clients drawn from a 100k-client population
    (the cohort scheduler core runs on the host CPU), then the Eq. 18 head
    fine-tune; every round loss and the personalization gain must be finite;
(d) ``repro.launch.serve.main`` at the same widths: personalized decode must
    return a (batch, steps) block of token ids.

The times and the peak memory printed on the way are a record of this run,
not a benchmark.  The last line of stdout is one JSON object naming the
device.  Any failure raises and exits nonzero; no phase is skipped.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# kernel phase widths: flash attention at 16 query / 8 kv heads of 128 over
# 4k tokens, the quantizer over 1M elements, the mLSTM at xLSTM-350M's
# 4 heads of 512 over the smoke's 1024 tokens, the RG-LRU at
# RecurrentGemma-2B's 2560 lanes over 4k tokens
FLASH = (1, 16, 8, 4096, 128)          # b, h, kvh, s, d
QUANT_ROWS = 8192
MLSTM = (1, 4, 1024, 512)              # b, h, s, dh
RGLRU = (1, 4096, 2560)                # b, s, w

TRAIN_ARGS = ["--arch", "xlstm-350m", "--published-widths", "--clients", "2",
              "--local-steps", "2", "--micro", "1", "--seq", "1024",
              "--rounds", "3", "--channel", "rayleigh",
              "--population", "100000", "--cohort-size", "2"]
SERVE_BATCH, SERVE_STEPS = 4, 16
SERVE_ARGS = ["--arch", "xlstm-350m", "--published-widths",
              "--batch", str(SERVE_BATCH), "--prompt-len", "16",
              "--steps", str(SERVE_STEPS)]

# four-chip comparison: xLSTM-350M's widths cut to 4 layers (2 mLSTM +
# 2 sLSTM) in float32, 4 clients x 2 local steps of 128 tokens.  float32
# because in bf16 most SGD updates are smaller than one bf16 step of the
# weight they move, so the two programs' rounding, not their math, decides
# the result (mesh vs host update distance 0.18 in bf16 against 9e-6 in
# float32, 2 layers on the CPU); 4 layers so that the host mirror's four
# float32 replicas fit one chip.  Both rounds run at "highest" matmul
# precision, so f32 dots are not cut to bf16 passes.
MESH_CLIENTS, MESH_LAYERS, MESH_SEQ = 4, 4, 128
MESH_UPDATE_TOL = 1e-3
MESH_LOSS_TOL = 1e-4


def log(**kv):
    print("[smoke] " + json.dumps(kv), flush=True)


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache loads
    included, tracing and lowering not)."""

    def __init__(self, jax):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def check_close(name, got, want, *, atol, rtol, why):
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise AssertionError(f"{name}: shape {got.shape} != {want.shape}")
    err = float(np.max(np.abs(got - want)))
    log(kernel=name, max_abs_err=err, atol=atol, rtol=rtol, why=why)
    np.testing.assert_allclose(got, want, atol=atol, rtol=rtol,
                               err_msg=f"{name} vs its ref ({why})")


def compiled_kernel(jax, name, fn, *args):
    """Compile ``fn`` and require a Mosaic kernel in the program."""
    t = time.time()
    compiled = jax.jit(fn).lower(*args).compile()
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{name}: no tpu_custom_call in the program; "
                             f"the kernel was not compiled for the chip")
    log(kernel=name, compile_s=time.time() - t)
    return compiled(*args)


def phase_kernels(jax):
    with jax.default_matmul_precision("highest"):
        _kernels(jax)


def _kernels(jax):
    import jax.numpy as jnp
    from repro.kernels import interpret_mode
    from repro.kernels.flash_attention.kernel import flash_attention_hmajor
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.mlstm_chunk.kernel import mlstm_chunk_pallas
    from repro.kernels.mlstm_chunk.ref import mlstm_ref
    from repro.kernels.quantize.kernel import quantize_dequantize_pallas
    from repro.kernels.quantize.ops import tensor_scale
    from repro.kernels.quantize.ref import quantize_dequantize_ref
    from repro.kernels.rglru_scan.kernel import rglru_scan_pallas
    from repro.kernels.rglru_scan.ref import rglru_scan_ref

    if interpret_mode():
        raise AssertionError("interpret_mode() is True on a TPU")
    ks = iter(jax.random.split(jax.random.PRNGKey(0), 16))
    normal = lambda shape, dt=jnp.float32: jax.random.normal(
        next(ks), shape, jnp.float32).astype(dt)

    b, h, kvh, s, d = FLASH
    q, k, v = (normal((b, n, s, d), jnp.bfloat16) for n in (h, kvh, kvh))
    out = compiled_kernel(jax, "flash_attention", flash_attention_hmajor,
                          q, k, v)
    ref = attention_ref(q, k, v, causal=True)
    check_close("flash_attention", out, ref, atol=2e-2, rtol=2e-2,
                why="bf16 output: one bf16 rounding (2^-8) of values of "
                    "order 1, plus the kernel's online-softmax order")

    x = normal((QUANT_ROWS, 128))
    u = jax.random.uniform(next(ks), x.shape, jnp.float32)
    scale = tensor_scale(x, 127)
    out = compiled_kernel(
        jax, "quantize",
        lambda x_, u_, s_: quantize_dequantize_pallas(x_, u_, s_, qmax=127),
        x, u, scale)
    ref = quantize_dequantize_ref(x, u, scale[0, 0], 127)
    step = float(scale[0, 0])
    check_close("quantize", out, ref, atol=step * 1.001, rtol=0.0,
                why="floor() at a grid boundary may land one step apart "
                    "if the kernel's reciprocal differs from XLA's by an "
                    "ulp")

    b, h, s, dh = MLSTM
    q, v = normal((b, h, s, dh)), normal((b, h, s, dh))
    k = normal((b, h, s, dh)) / math.sqrt(dh)
    li = normal((b, h, s))
    lf = jax.nn.log_sigmoid(normal((b, h, s)))
    out = compiled_kernel(jax, "mlstm_chunk", mlstm_chunk_pallas,
                          q, k, v, li, lf)
    ref = mlstm_ref(q, k, v, li, lf)
    check_close("mlstm_chunk", out, ref, atol=2e-3, rtol=2e-3,
                why="f32 throughout; the kernel's masked-sum cumsum and "
                    "the ref's cumsum associate differently, and outputs "
                    "divide by a normalizer that can be small (1.4e-4 "
                    "max on the CPU at these widths)")

    b, s, w = RGLRU
    log_a = -0.1 * jnp.abs(normal((b, s, w)))
    bb = normal((b, s, w))
    h0 = normal((b, w))
    out = compiled_kernel(jax, "rglru_scan", rglru_scan_pallas, log_a, bb, h0)
    ref = rglru_scan_ref(log_a, bb, h0)
    check_close("rglru_scan", out, ref, atol=1e-4, rtol=1e-4,
                why="f32 elementwise recurrence; exp() of the kernel and "
                    "of XLA may differ by an ulp per step")


def phase_train(jax, clock):
    from repro.launch import train
    c0 = clock.seconds
    t = time.time()
    out = train.main(TRAIN_ARGS)
    wall = time.time() - t
    losses = out["round_loss"]
    if len(losses) != 3 or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"round losses not all finite: {losses}")
    if not math.isfinite(out["personalization_gain"]):
        raise AssertionError(
            f"personalization gain {out['personalization_gain']}")
    log(phase="train", round_loss=losses, round_s=out["round_s"],
        personalization_gain=out["personalization_gain"],
        backend_compile_s=clock.seconds - c0, wall_s=wall,
        peak_bytes_in_use=jax.devices()[0].memory_stats()[
            "peak_bytes_in_use"])


def phase_serve(jax, clock):
    import numpy as np
    from repro.launch import serve
    c0 = clock.seconds
    t = time.time()
    out = serve.main(SERVE_ARGS)
    gen = np.asarray(out["generated"])
    if gen.shape != (SERVE_BATCH, SERVE_STEPS):
        raise AssertionError(f"generated ids shape {gen.shape}")
    log(phase="serve", generated_shape=list(gen.shape),
        tok_per_s=out["tok_per_s"], backend_compile_s=clock.seconds - c0,
        wall_s=time.time() - t,
        peak_bytes_in_use=jax.devices()[0].memory_stats()[
            "peak_bytes_in_use"])


def phase_mesh_round(jax):
    with jax.default_matmul_precision("highest"):
        _mesh_round(jax)


def _mesh_round(jax):
    """One masked edge round of 4 clients: the psum over the 'data' axis of
    a (4, 1) mesh against make_host_round on one device, same inputs."""
    import dataclasses

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import HierarchyConfig, TrainConfig
    from repro.configs.registry import get_arch
    from repro.core import (build_optimizer, init_stacked_params,
                            make_host_round, make_phsfl_round)
    from repro.launch.train import _client_round_batch
    from repro.models import build_model

    C, K = MESH_CLIENTS, 2
    cfg = dataclasses.replace(get_arch("xlstm-350m"), num_layers=MESH_LAYERS,
                              dtype="float32")
    model = build_model(cfg)
    hcfg = HierarchyConfig(num_edge_servers=1, clients_per_es=C, kappa0=K,
                           kappa1=1, global_rounds=1)
    tcfg = TrainConfig(learning_rate=0.05, freeze_head=True,
                       local_steps_in_step=K, remat=False)
    params = init_stacked_params(model, jax.random.PRNGKey(0), C)
    opt, _ = build_optimizer(model, tcfg)
    state1 = opt.init(jax.tree.map(lambda x: x[0], params))
    opt_state = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (C,) + x.shape), state1)
    batch = _client_round_batch(cfg, C, K, 1, MESH_SEQ, seed=0)
    au = jnp.full((C,), 1.0 / C, jnp.float32)
    ab = jnp.ones((C,), jnp.float32)
    mask = jnp.asarray([1.0] * (C - 1) + [0.0], jnp.float32)  # one drops out
    inputs = (params, opt_state, batch, au, ab, mask)

    mesh = jax.make_mesh((C, 1), ("data", "model"))
    mesh_round = make_phsfl_round(model, hcfg, tcfg, mesh, global_sync=False,
                                  participation=True,
                                  cut=cfg.n_client_layers)
    with jax.set_mesh(mesh):
        sharded = jax.device_put(inputs, NamedSharding(mesh, P("data")))
        t = time.time()
        p_m, _, m_m = jax.jit(mesh_round.fn)(*sharded)
        loss_mesh = float(m_m["loss"])
        mesh_s = time.time() - t
        p_mesh = jax.tree.map(np.asarray, p_m)
        del sharded, p_m
    host_round = make_host_round(model, hcfg, tcfg, num_clients=C,
                                 global_sync=False, participation=True,
                                 cut=cfg.n_client_layers)
    t = time.time()
    p_h, _, m_h = jax.jit(host_round.fn)(*inputs)
    loss_host = float(m_h["loss"])
    host_s = time.time() - t
    p_host = jax.tree.map(np.asarray, p_h)
    del p_h

    # Same math, two programs: the mesh runs each client's steps on its own
    # chip, the host round vmaps them on one.  Compare what the round
    # changed: the distance between the two results over the size of the
    # host round's update, both norms over all leaves.  A wrong aggregation
    # weight moves it by that weight's error (a dropped client counted in:
    # ~0.3).
    p0 = jax.tree.map(lambda x: np.asarray(x[0], np.float32), params)
    apart, per_leaf = [], []
    diff2 = upd2 = 0.0
    for (path, a), b, a0 in zip(jax.tree_util.tree_leaves_with_path(p_mesh),
                                jax.tree.leaves(p_host), jax.tree.leaves(p0)):
        name = jax.tree_util.keystr(path)
        a, b = a.astype(np.float32), b.astype(np.float32)
        apart += [f"{side} {name}" for side, x in (("mesh", a), ("host", b))
                  if not (x == x[:1]).all()]
        d = np.linalg.norm((a[0] - b[0]).astype(np.float64))
        u = np.linalg.norm((b[0] - a0).astype(np.float64))
        diff2, upd2 = diff2 + d * d, upd2 + u * u
        per_leaf.append((float(d / max(u, 1e-30)), name))
    update_rel = math.sqrt(diff2 / upd2)
    loss_rel = abs(loss_mesh - loss_host) / abs(loss_host)
    log(phase="mesh_round", clients=C, seq=MESH_SEQ, loss_mesh=loss_mesh,
        loss_host=loss_host, loss_rel_diff=loss_rel,
        update_rel_diff=update_rel, update_norm=math.sqrt(upd2),
        worst_leaves=sorted(per_leaf, reverse=True)[:4],
        mesh_s=mesh_s, host_s=host_s)
    if apart:
        raise AssertionError(f"aggregation left clients apart: {apart[:4]}")
    if not update_rel <= MESH_UPDATE_TOL:
        raise AssertionError(f"mesh vs host update: relative distance "
                             f"{update_rel} > {MESH_UPDATE_TOL}")
    if not loss_rel <= MESH_LOSS_TOL:
        raise AssertionError(f"loss mesh {loss_mesh} vs host {loss_host}: "
                             f"relative difference {loss_rel} > "
                             f"{MESH_LOSS_TOL}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh-vs-host edge round")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke.py: no repro package under {SRC}")
    sys.path.insert(0, str(SRC))
    # the cohort scheduler core runs on JAX's CPU backend beside the chip
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"

    import jax
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    log(jax=jax.__version__, platform=dev.platform, kind=dev.device_kind,
        count=len(devices), compile_cache=cache_dir,
        jax_platforms=os.environ.get("JAX_PLATFORMS"))
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke.py: JAX found no TPU (platform "
                 f"{dev.platform!r})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke.py: --chips {args.chips} but JAX sees "
                 f"{len(devices)} device(s)")

    clock = CompileClock(jax)
    if args.chips == 4:
        phase_mesh_round(jax)
    else:
        phase_kernels(jax)
        phase_train(jax, clock)
        phase_serve(jax, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
