"""The pieces that put the main path on a chip, checked without one.

- the persistent compile cache lands where it should;
- kernels pick interpret mode off the TPU;
- ``chip_smoke.py`` refuses to report success without a TPU;
- the float64 cohort scheduler core runs on the host CPU device.
"""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np

import jax

from repro.configs.base import WirelessConfig
from repro.configs.phsfl_cnn import CONFIG as CNN_CFG
from repro.core.comm import comm_for_cnn
from repro.kernels import interpret_mode
from repro.launch import compile_cache
from repro.wireless import scheduler_core
from repro.wireless.population import Population, make_cohort_scheduler

REPO = Path(__file__).resolve().parents[1]


def test_compile_cache_defaults_to_the_repo(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.use_compile_cache()
        assert got == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_leaves_an_outside_setting_alone(monkeypatch,
                                                       tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert compile_cache.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_interpret_mode_follows_the_backend():
    assert jax.default_backend() == "cpu"
    assert interpret_mode() is True
    assert interpret_mode(False) is False       # an explicit mode wins


def test_chip_smoke_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         cwd=REPO, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


def test_cohort_core_runs_on_the_cpu_device(monkeypatch):
    platforms = []

    def recording(stage):
        def run(*args, **kw):
            outs = stage(*args, **kw)
            platforms.extend(d.platform for o in outs for d in o.devices())
            return outs
        return run

    for name in ("cohort_stage_a", "cohort_stage_b"):
        monkeypatch.setattr(scheduler_core, name,
                            recording(getattr(scheduler_core, name)))
    wc = WirelessConfig(model="rayleigh", mean_uplink_mbps=8.0,
                        deadline_s=1.5, seed=3)
    comm = comm_for_cnn(CNN_CFG, dataset_size=400, batch_size=16)
    sched = make_cohort_scheduler(wc, 64, comm, 2,
                                  population=Population(64, seed=3),
                                  cohort_size=8)
    rep = sched.step(0)
    assert np.asarray(rep.mask).shape == (64,)
    assert platforms and set(platforms) == {"cpu"}
    with scheduler_core.cpu_x64():
        assert jax.config.jax_enable_x64
        assert jax.config.jax_default_device == jax.devices("cpu")[0]
