"""The PHSFL edge round, driven as ``launch/train.py`` drives it.

Each round makes the calls ``train.py`` makes: put the round's batch on
the device, step the wireless scheduler and slice its report to the
cohort, build the participation mask, run the jitted
``make_host_round`` round (C clients vmapped on one chip: kappa0 local
SGD steps with the head frozen, then the masked edge aggregation in
float32), and wait for its loss.  The harness's host spans wrap the four
calls: ``batch_put``, ``sched_step``, ``round_dispatch``, ``loss_wait``.

Set-up builds the population, the scheduler, the round and the weights,
then drives the first ``check_rounds`` rounds through that same call and
feed: they compile every program the window uses, and they are the
rounds the reference follows.  The window then runs rounds for
``--seconds``.  Once it has closed and the program's state is freed, the
plain reference re-runs the first rounds from the same weights, batches
and masks, and the two are compared (``reference.compare``).

With ``--trace`` the window is ``trace_rounds`` rounds under the
profiler instead, and the run reports the per-layer metrics.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import shutil
import sys
import tempfile
import time
from collections import defaultdict

import numpy as np

import reference as R
import trace_reduce
import traffic_gen
import weights as W
from harness import Outcome, Run

META_KEYS = {"name", "source", "family"}


def model_config(config: dict):
    """The program's ModelConfig: the registry entry ``config["arch"]``
    with every size the file gives."""
    from repro.configs.registry import get_arch

    base = get_arch(config["arch"])
    fields = {f.name for f in dataclasses.fields(base)} - META_KEYS
    changes = {}
    for k, v in config.items():
        if k not in fields:
            continue
        if isinstance(v, dict):
            v = dataclasses.replace(getattr(base, k), **v)
        elif isinstance(v, list):
            v = tuple(v)
        changes[k] = v
    return dataclasses.replace(base, **changes)


class Spans:
    """Host seconds per span name; in a traced run each span is also a
    ``TraceAnnotation`` on the profiler's clock."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.total = defaultdict(float)

    @contextlib.contextmanager
    def __call__(self, name):
        import jax

        t = time.perf_counter()
        with (jax.profiler.TraceAnnotation(name) if self.annotate
              else contextlib.nullcontext()):
            yield
        self.total[name] += time.perf_counter() - t


class Program:
    """The system under test, built once per process: the model, the
    jitted round, the weights' initializer."""

    def __init__(self, cell, bench, wrap_round=None):
        import jax
        import jax.numpy as jnp

        from repro.configs.base import HierarchyConfig, TrainConfig
        from repro.core import build_optimizer, make_host_round
        from repro.models import build_model

        if cell.chips != 1:
            raise NotImplementedError("edge_round drives one chip")
        self.cell, self.bench = cell, bench
        self.config, t = cell.config, cell.traffic
        self.C, self.k0 = t["clients"], t["local_steps"]
        self.lr = t["lr"]
        self.mcfg = model_config(self.config)
        model = build_model(self.mcfg)
        self.shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        self.init = W.initializer(self.shapes)
        hcfg = HierarchyConfig(num_edge_servers=1, clients_per_es=self.C,
                               kappa0=self.k0, kappa1=1, global_rounds=1)
        tcfg = TrainConfig(learning_rate=self.lr, optimizer="sgd",
                           freeze_head=True, local_steps_in_step=self.k0,
                           remat=False, agg_dtype=t["agg_dtype"])
        opt, _ = build_optimizer(model, tcfg)
        self.opt_shapes = jax.eval_shape(opt.init, self.shapes)
        round_ = make_host_round(model, hcfg, tcfg, num_clients=self.C,
                                 global_sync=False, participation=True,
                                 cut=self.mcfg.n_client_layers)
        fn = jax.jit(round_.fn)
        self.fn = wrap_round(fn, self.C) if wrap_round else fn
        self.au = jnp.full((self.C,), 1.0 / self.C, jnp.float32)
        self.ab = jnp.ones((self.C,), jnp.float32)
        self.frozen = {self.mcfg.head_name}
        self.frozen_mask = R.frozen_mask(self.shapes, self.frozen)

    def tokens_per_round(self) -> int:
        t = self.cell.traffic
        return self.C * self.k0 * t["micro"] * t["seq"]

    def scheduler(self, seed: int):
        """The cohort scheduler over the cell's population, as train.py
        builds it for ``--population N --cohort-size C``."""
        from repro.configs.base import WirelessConfig
        from repro.core.comm import comm_for_lm
        from repro.wireless import make_scheduler
        from repro.wireless.population import CohortScheduler, Population

        t = self.cell.traffic
        inf = float("inf")
        rate = t["mean_rate_mbps"]
        wcfg = WirelessConfig(
            model=t["channel"], mean_uplink_mbps=rate,
            mean_downlink_mbps=4 * rate,
            deadline_s=inf if t["deadline_s"] is None else t["deadline_s"],
            energy_budget_j=(inf if t["energy_budget_j"] is None
                             else t["energy_budget_j"]),
            seed=seed)
        comm = comm_for_lm(self.mcfg, seq_len=t["seq"],
                           dataset_size=t["dataset_size"],
                           batch_size=t["micro"], batches_per_epoch=1)
        pop = Population(t["population"], seed=seed)
        return make_scheduler(wcfg, pop.N, comm, self.k0,
                              es_assign=pop.es_assign, cls=CohortScheduler,
                              population=pop, cohort_size=self.C,
                              sampling=t["sampling"])


class Session:
    """One seed's run of the program: its weights, traffic and scheduler,
    and the round loop."""

    def __init__(self, prog: Program, seed: int, spans: Spans):
        import jax
        import jax.numpy as jnp

        self.prog, self.seed, self.spans = prog, seed, spans
        self.key = W.seed_key(seed, 0)
        self.pool = traffic_gen.make_pool(seed, prog.cell.traffic,
                                          prog.config)
        self.sched = prog.scheduler(seed)
        self.params = prog.init(self.key, prog.C)
        self.opt_state = jax.tree.map(
            lambda s: jnp.zeros((prog.C,) + s.shape, s.dtype),
            prog.opt_shapes)
        self.masks = []

    def round(self, rd: int) -> float:
        import jax
        import jax.numpy as jnp

        from repro.wireless.population import cohort_report

        p, sp = self.prog, self.spans
        with sp("batch_put"):
            batch = jax.device_put(self.pool[rd % len(self.pool)])
        with sp("sched_step"):
            rep = cohort_report(self.sched.step(rd), self.sched.last_cohort)
            mask = jnp.asarray(rep.mask, jnp.float32)
        with sp("round_dispatch"):
            self.params, self.opt_state, metrics = p.fn(
                self.params, self.opt_state, batch, p.au, p.ab, mask)
        with sp("loss_wait"):
            loss = float(metrics["loss"])
        self.masks.append(np.asarray(rep.mask, np.float64))
        return loss

    def first_rounds(self, n: int) -> dict:
        """Rounds 0..n-1, and the readings they are judged on."""
        p0 = self.params
        losses = []
        for rd in range(n):
            losses.append(self.round(rd))
            if rd == 0:
                grad1 = R.first_client_diff_stats(p0, self.params)[0] / \
                    self.prog.lr
                del p0
        p0 = self.prog.init(self.key, self.prog.C)
        delta = R.first_client_diff_stats(self.params, p0)
        del p0
        return R.readings(losses, grad1, delta, self.prog.frozen_mask)

    def free(self):
        self.params = self.opt_state = None


def reference_readings(prog: Program, sess: Session, n: int, *,
                       cast=R.exact, batch_fault=None) -> dict:
    """The plain reference over the session's first n rounds, from the
    same weights, batches and masks.  ``cast`` sets the precision of its
    matrix products (``R.fp8`` for the control); ``batch_fault`` breaks
    the batches (a fault put in the program's place)."""
    ref = prog.bench.module("refs", prog.cell.config_name)
    step = R.make_step(ref.loss, prog.config, prog.lr, prog.frozen, cast)
    batches = sess.pool[:n]
    if batch_fault is not None:
        batches = [batch_fault(b, prog.C) for b in batches]
    alpha = [1.0 / prog.C] * prog.C
    return R.follow(step, prog.init(sess.key), batches, sess.masks[:n],
                    alpha, prog.lr, prog.frozen, n)


class CompileCount:
    """Backend compiles (persistent-cache loads included) since start."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def _peak_bytes(chips: int):
    import jax

    stats = [d.memory_stats() for d in jax.devices()[:chips]]
    peaks = [s["peak_bytes_in_use"] for s in stats
             if s and "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


def run(r: Run) -> Outcome:
    import jax

    cell = r.cell
    t = cell.traffic
    n_check = t["check_rounds"]
    spans = Spans(annotate=r.trace)
    compiles = CompileCount()
    prog = Program(cell, r.bench, r.wrap_round)
    sess = Session(prog, r.seed, spans)
    prog_read = sess.first_rounds(n_check)
    spans.total.clear()

    rd, attempted, failed = n_check, 0, 0
    summary = None
    compiled_in_setup = compiles.n
    setup_s = time.time() - r.t_start
    if r.trace:
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            jax.profiler.start_trace(tmp)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                for _ in range(t["trace_rounds"]):
                    failed += not math.isfinite(sess.round(rd))
                    rd += 1
                    attempted += 1
            window_s = time.perf_counter() - t0
            jax.profiler.stop_trace()
            ops, host, (lo, hi) = trace_reduce.read_profile(tmp, cell.chips)
            summary = trace_reduce.summarize(ops, host, lo, hi)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    else:
        t0 = time.perf_counter()
        while True:
            failed += not math.isfinite(sess.round(rd))
            rd += 1
            attempted += 1
            window_s = time.perf_counter() - t0
            if window_s >= r.seconds:
                break
    print(f"[bench] setup_s={setup_s:.3f} compiles: {compiled_in_setup} in "
          f"set-up, {compiles.n - compiled_in_setup} in the window",
          file=sys.stderr)
    peak = _peak_bytes(cell.chips)
    sess.free()

    ref_read = reference_readings(prog, sess, n_check)
    numbers = R.compare(prog_read, ref_read)
    print(f"[bench] readings: {numbers}", file=sys.stderr)
    checks = {k: (numbers[k], float(lim)) for k, lim in cell.limits.items()}

    out = Outcome(
        end_to_end={"train_tokens_per_s":
                    attempted * prog.tokens_per_round() / window_s,
                    "setup_s": setup_s},
        attempted=attempted, failed=failed, checks=checks,
        memory_peak_bytes=peak, rounds_traced=attempted if r.trace else 0,
        trace=summary, span_s=dict(spans.total), chips=cell.chips)
    if summary is not None:
        dev = jax.devices()[0]
        out.peaks = r.bench.peaks(dev.device_kind)
        out.flops_per_round = r.bench.module(
            "flops", cell.config_name).round_flops(cell.config, t)
    return out
