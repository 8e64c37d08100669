"""Fused jax decision core for population-scale cohort scheduling.

This module re-expresses the fault-free per-round decision path of
:class:`repro.wireless.scheduler.ParticipationScheduler` — channel rate
construction, the :class:`~repro.wireless.cutter.CutController` (cut,
codec) grid argmin, device compute times, the serial/pipelined timeline
aggregates, per-ES contention (equal and water-filled proportional), the
withdrawal + reshare pass, and the deadline/energy gates with the
moved-bits ledger — as jit-compiled jax ops over the whole client axis,
so one round's scheduling for 10**5..10**6 registered clients is two
fused XLA computations (plus a tiny host step between them for the
selection gate).  The numpy scheduler stays the reference ORACLE; this
core's contract is bit-identity to it, pinned by the U=8 property test
(``tests/test_population.py``).

Bit-identity strategy
---------------------
* Everything runs in float64 on the host CPU device: callers wrap
  invocations in :func:`cpu_x64` (``jax.enable_x64`` plus
  ``jax.default_device`` on ``jax.devices("cpu")[0]``), and all array
  inputs arrive as host ``np.float64``/``bool``/``int`` arrays.  No
  explicit jax dtype literals appear here — weak python scalars promote
  to the f64 inputs, exactly like numpy.  The CPU pin is part of the
  contract: the bit-identity below is a property of CPU XLA, and a TPU
  only emulates f64, so the core never runs on an accelerator even when
  one is JAX's default backend.
* Elementwise f64 arithmetic, ``argmin`` (first-minimum tie-break),
  ``nan_to_num`` defaults, and ``segment_sum`` vs
  ``np.bincount(weights=...)`` are bitwise-identical to numpy on CPU XLA
  (empirically verified for this pinned jax build, including under jit).
* Reductions whose float association ORDER numpy fixes are replicated
  explicitly: the pipelined per-chunk overlap sum uses
  :func:`_rowsum_np_order` (numpy's pairwise summation for a trailing
  axis), and the water-filling loop is a ``lax.while_loop`` with the
  oracle's exact per-iteration expressions.
* Entropy stays HOST-side: fading draws, thinning draws, and fault plans
  come from the same numpy ``Generator`` streams the oracle uses, and are
  fed in as arrays — the core is a pure function of them.
* Control flow the oracle makes data-dependent (the conditional reshare
  second pass) is computed unconditionally in-trace and selected with
  ``where`` on the traced predicate; control flow that is irreproducible
  in-trace (``np.argsort``'s quicksort tie order for top-k) stays on the
  host between the two stages, operating on bit-identical inputs.

Fault-plan rounds (erasures/crashes) have data-dependent attempt-column
shapes and are delegated by :class:`repro.wireless.population.
CohortScheduler` to the numpy oracle path; ES-outage-only rounds stay on
this core (the outage masks are host inputs).
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp


@contextlib.contextmanager
def cpu_x64():
    """The context every core invocation must run in: float64 on the host
    CPU device.  Raises if JAX exposes no CPU backend (e.g. a restrictive
    ``JAX_PLATFORMS``) rather than letting the core land on an
    accelerator."""
    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            "the cohort scheduler core runs in float64 on JAX's CPU backend, "
            "which is not available here; include 'cpu' in JAX_PLATFORMS "
            "(e.g. JAX_PLATFORMS=tpu,cpu)") from e
    with jax.enable_x64(True), jax.default_device(cpu):
        yield


# Pipelined chunk sums replicate numpy's pairwise summation, whose simple
# closed forms cover n <= 128 columns; beyond that numpy recurses and the
# replication (and any sane chunk count) ends.
MAX_CHUNKS = 128


@dataclass(frozen=True)
class CoreSpec:
    """Static (trace-time) configuration of one cohort scheduling round.

    Frozen so it can be a jit ``static_argnames`` argument; every field
    mirrors the oracle knob it is named after.  ``contend`` is the
    oracle's contention-bypass predicate evaluated statically (ideal
    channel or infinite ES capacity never contends)."""

    model: str               # "ideal" | "static" | "rayleigh" | "trace"
    up_mean_bps: float
    down_mean_bps: float
    latency_s: float
    has_down_trace: bool     # trace model with a measured downlink trace
    contend: bool
    contention: str          # "equal" | "proportional"
    es_cap_bps: float
    num_es: int
    reshare: bool
    has_cutter: bool
    adaptive: bool           # cutter present and policy != "fixed"
    policy: str              # "fixed" | "greedy" | "deadline"
    fixed_cut: int
    num_cells: int
    cutter_deadline_s: float
    cutter_tx_power_w: float
    cutter_compute_power_w: float
    cutter_pipeline: bool
    cutter_ea: float         # expected HARQ attempts priced by the cutter
    cutter_hb: float         # HARQ backoff seconds priced by the cutter
    deadline_s: float
    tx_power_w: float
    compute_power_w: float
    pipeline: bool
    chunks: int


def _rowsum_np_order(cols):
    """Sum n (U,) columns in numpy's np.sum(axis=1) association order.

    numpy reduces a C-contiguous trailing axis with pairwise summation:
    a zero-seeded sequential loop for n < 8, and the 8-accumulator
    unrolled block (with a sequential remainder) for 8 <= n <= 128.
    Replicating the exact order keeps the pipelined timeline aggregates
    bitwise-identical to the oracle's ``.sum(axis=1)``.
    """
    n = len(cols)
    assert 1 <= n <= MAX_CHUNKS
    if n < 8:
        res = 0.0 + cols[0]
        for k in range(1, n):
            res = res + cols[k]
        return res
    r = list(cols[:8])
    i = 8
    while i + 8 <= n:
        for j in range(8):
            r[j] = r[j] + cols[i + j]
        i += 8
    res = ((r[0] + r[1]) + (r[2] + r[3])) + ((r[4] + r[5]) + (r[6] + r[7]))
    for k in range(i, n):
        res = res + cols[k]
    return res


# ---------------------------------------------------------------- rates --
def _rates(spec: CoreSpec, fade, down_row, scale):
    """ChannelModel.sample()'s rate expressions over host-drawn entropy.

    ``fade`` is the per-round fading array drawn host-side from the
    channel's own numpy stream (ones for static, Exp(1) for rayleigh, the
    resized trace row scaled by ``1e6 / up_mean`` for trace), so the rate
    VALUES equal the oracle's bit-for-bit."""
    if spec.model == "ideal":
        inf = jnp.full(scale.shape, jnp.inf)
        return inf, inf, jnp.zeros(scale.shape)
    up = jnp.maximum(spec.up_mean_bps * scale * fade, 1.0)
    down = jnp.maximum(spec.down_mean_bps * scale * fade, 1.0)
    if spec.has_down_trace:
        down = jnp.maximum(down_row * 1e6 * scale, 1.0)
    return up, down, jnp.full(scale.shape, spec.latency_s)


# ------------------------------------------------------------ cut decide --
def _estimates(spec: CoreSpec, tables, up, down, latency, spf):
    """CutController._estimates over the (cells, U) grid, verbatim."""
    t_up = tables["up_bits"][:, None] / up[None, :]
    t_down = tables["down_bits"][:, None] / down[None, :]
    t_up = jnp.nan_to_num(t_up, nan=0.0)
    t_down = jnp.nan_to_num(t_down, nan=0.0)
    ea, hb = spec.cutter_ea, spec.cutter_hb
    t_up_air = t_up
    harq = ea != 1.0 or hb != 0.0
    if harq:
        gap = (ea - 1.0) * hb
        t_up_air = ea * t_up
        t_up = t_up_air + gap
        t_down = ea * t_down + gap
    t_comp = tables["flops"][:, None] * spf[None, :]
    if spec.cutter_pipeline:
        u = jnp.nan_to_num(tables["up_stream"][:, None] / up[None, :],
                           nan=0.0)
        t_tail = jnp.nan_to_num(tables["up_tail"][:, None] / up[None, :],
                                nan=0.0)
        if harq:
            u = ea * u + gap
            t_tail = ea * t_tail + gap
        c = t_comp / spec.chunks
        up_finish = c + u + (spec.chunks - 1) * jnp.maximum(c, u) + t_tail
        times = 2 * latency[None, :] + up_finish + t_down
    else:
        times = 2 * latency[None, :] + t_up + t_down
        times = times + t_comp
    energy = spec.cutter_tx_power_w * t_up_air
    energy = energy + spec.cutter_compute_power_w * t_comp
    return times, energy


def _decide(spec: CoreSpec, tables, up, down, latency, energy_left, spf):
    """CutController.decide() over the cohort (fixed/greedy/deadline)."""
    if not spec.has_cutter or spec.policy == "fixed" or spec.num_cells == 1:
        return jnp.full(up.shape, spec.fixed_cut, dtype=int)
    times, energy = _estimates(spec, tables, up, down, latency, spf)
    affordable = energy <= energy_left[None, :]
    t_aff = jnp.where(affordable, times, jnp.inf)
    fastest_aff = jnp.argmin(t_aff, axis=0)
    cheapest = jnp.argmin(energy, axis=0)
    none_affordable = ~affordable.any(axis=0)
    if spec.policy == "greedy":
        return jnp.where(none_affordable, cheapest, fastest_aff)
    feasible = affordable & (times <= spec.cutter_deadline_s)
    idx = jnp.arange(spec.num_cells)[:, None]
    deepest = jnp.where(feasible, idx, -1).max(axis=0)
    out = jnp.where(deepest >= 0, deepest, fastest_aff)
    return jnp.where(none_affordable, cheapest, out)


def _bits_comp(spec: CoreSpec, tables, fixed, cuts, spf):
    """Per-client bit arrays + compute times of a cut-index vector."""
    if spec.has_cutter:
        b_up = tables["up_bits"][cuts]
        b_down = tables["down_bits"][cuts]
        b_stream = tables["up_stream"][cuts]
        b_tail = tables["up_tail"][cuts]
        comp_s = tables["flops"][cuts] * spf
    else:
        b_up = fixed["up_bits"]
        b_down = fixed["down_bits"]
        b_stream = fixed["up_stream"]
        b_tail = fixed["up_tail"]
        comp_s = fixed["flops"] * spf
    return b_up, b_down, b_stream, b_tail, comp_s


# --------------------------------------------------------------- timeline --
def _timeline_agg(spec: CoreSpec, up, down, latency, b_up, b_down,
                  b_stream, b_tail, comp_s):
    """The serial/pipelined RoundTimeline AGGREGATES (times, charged
    compute/tx seconds, downlink window, can_tx) in the oracle builders'
    exact expression order (repro.wireless.timeline._serial/_pipelined)."""
    deadline = spec.deadline_s
    if not spec.pipeline:
        t_up_clock = b_up / up
        t_down = b_down / down
        t_up = jnp.where(jnp.isfinite(t_up_clock), t_up_clock, 0.0)
        t_down_f = jnp.where(jnp.isfinite(t_down), t_down, 0.0)
        times = 2 * latency + t_up_clock + t_down + comp_s
        c_s = jnp.minimum(comp_s, deadline)
        window = jnp.maximum(deadline - comp_s, 0.0)
        tx_s = jnp.minimum(t_up, window)
        down_start = comp_s + t_up
        down_win = jnp.clip(deadline - down_start, 0.0, t_down_f)
        can_tx = window > 0
        return times, c_s, tx_s, down_win, can_tx
    n = spec.chunks
    u = b_stream / up
    t_tail = b_tail / up
    t_down = b_down / down
    u = jnp.where(jnp.isfinite(u), u, 0.0)
    t_tail = jnp.where(jnp.isfinite(t_tail), t_tail, 0.0)
    t_down = jnp.where(jnp.isfinite(t_down), t_down, 0.0)
    c = comp_s / n
    # per-chunk streaming columns, summed in numpy's association order
    ov_cols = []
    for i in range(n):
        tx_start_i = jnp.maximum((i + 1) * c, c + i * u)
        ov_cols.append(jnp.clip(deadline - tx_start_i, 0.0, u))
    tail_start = jnp.maximum(n * c, c + (n - 1) * u) + u
    up_finish = tail_start + t_tail
    times = 2 * latency + up_finish + t_down
    c_s = jnp.minimum(comp_s, deadline)
    tx_s = (_rowsum_np_order(ov_cols)
            + jnp.clip(deadline - tail_start, 0.0, t_tail))
    down_win = jnp.clip(deadline - up_finish, 0.0, t_down)
    can_tx = c < deadline
    return times, c_s, tx_s, down_win, can_tx


# -------------------------------------------------------------- contention --
def _waterfill(cap, w, limits, groups, active, num_groups):
    """channel.waterfill_shares as a while_loop, expression-for-expression."""
    def body(carry):
        capped, _, _ = carry
        w_unc = jnp.where(active & ~capped, w, 0.0)
        totals = jax.ops.segment_sum(w_unc, groups,
                                     num_segments=num_groups)
        used = jax.ops.segment_sum(
            jnp.where(active & capped, limits, 0.0), groups,
            num_segments=num_groups)
        remaining = jnp.maximum(cap - used, 0.0)
        share = remaining[groups] * w / jnp.maximum(totals[groups], 1.0)
        newly = active & ~capped & (limits <= share)
        return capped | newly, share, newly.any()

    def cond(carry):
        return carry[2]

    init = (jnp.zeros(w.shape, bool), jnp.full(w.shape, cap),
            jnp.asarray(True))
    capped, share, _ = jax.lax.while_loop(cond, body, init)
    return jnp.where(active & capped, limits, share)


def _contended_up(spec: CoreSpec, up, active, es):
    """ChannelModel.contended_uplink for a statically-contended spec."""
    cap = spec.es_cap_bps
    if spec.contention == "proportional":
        share = _waterfill(cap, up, up, es, active, spec.num_es)
    else:
        counts = jax.ops.segment_sum(jnp.where(active, 1.0, 0.0), es,
                                     num_segments=spec.num_es)
        share = cap / jnp.maximum(counts[es], 1.0)
    return jnp.where(active, jnp.minimum(up, share), up)


# ------------------------------------------------------------------ stages --
@partial(jax.jit, static_argnames=("spec",))
def cohort_stage_a(spec: CoreSpec, tables, fixed, fade, down_row, scale,
                   spf, energy_left, client_down):
    """Private-rate decision pass: rates, cut decide, timeline, gate 1.

    Returns (up, down, latency, cuts, comp_s, times0, charge0, gate1) —
    ``times0`` feeds the host's top-k argsort (whose quicksort tie order
    must be numpy's), ``gate1`` is the energy+window (+outage) gate."""
    up, down, latency = _rates(spec, fade, down_row, scale)
    cuts = _decide(spec, tables, up, down, latency, energy_left, spf)
    b_up, b_down, b_stream, b_tail, comp_s = _bits_comp(
        spec, tables, fixed, cuts, spf)
    times0, c_s, tx_s, _, can_tx = _timeline_agg(
        spec, up, down, latency, b_up, b_down, b_stream, b_tail, comp_s)
    charge0 = spec.tx_power_w * tx_s + spec.compute_power_w * c_s
    gate1 = (energy_left >= charge0) & can_tx & ~client_down
    return up, down, latency, cuts, comp_s, times0, charge0, gate1


@partial(jax.jit, static_argnames=("spec",))
def cohort_stage_b(spec: CoreSpec, tables, fixed, scheduled_in, up, down,
                   latency, cuts_in, energy_left, spf, es_assign):
    """Contention + final gates + ledger over a chosen scheduled set.

    Mirrors ParticipationScheduler._contend (adaptive re-decide at the
    contended rates, withdrawal, the conditional reshare second pass —
    computed unconditionally and selected on the traced predicate) and
    the oracle's post-contention body: the deadline gate, the energy
    deduction, and the fault-free moved-bits ledger.  Pure: the top-k
    backfill calls it a second time on the refilled set with the same
    private inputs."""
    if spec.contend:
        eff1 = _contended_up(spec, up, scheduled_in, es_assign)
        if spec.adaptive:
            cuts2 = _decide(spec, tables, eff1, down, latency, energy_left,
                            spf)
            cuts = jnp.where(scheduled_in, cuts2, cuts_in)
        else:
            cuts = cuts_in
        b_up, b_down, b_stream, b_tail, comp_s = _bits_comp(
            spec, tables, fixed, cuts, spf)
        _, c_s1, tx_s1, _, can1 = _timeline_agg(
            spec, eff1, down, latency, b_up, b_down, b_stream, b_tail,
            comp_s)
        charge1 = spec.tx_power_w * tx_s1 + spec.compute_power_w * c_s1
        ok = (energy_left >= charge1) & can1
        withdrawn = scheduled_in & ~ok
        sched = scheduled_in & ok
        if spec.reshare:
            do2 = withdrawn.any() & sched.any()
            eff2 = _contended_up(spec, up, sched, es_assign)
            eff = jnp.where(do2, eff2, eff1)
        else:
            eff = eff1
    else:
        eff = up
        cuts = cuts_in
        b_up, b_down, b_stream, b_tail, comp_s = _bits_comp(
            spec, tables, fixed, cuts, spf)
        withdrawn = jnp.zeros(up.shape, bool)
        sched = scheduled_in
    times, c_s, tx_s, down_win, _ = _timeline_agg(
        spec, eff, down, latency, b_up, b_down, b_stream, b_tail, comp_s)
    charge = spec.tx_power_w * tx_s + spec.compute_power_w * c_s
    alive = sched & (times <= spec.deadline_s)
    energy_after = jnp.where(sched, energy_left - charge, energy_left)
    # fault-free moved-bits ledger (oracle: full traffic when alive, else
    # rate x charged airtime / downlink window; the nan of inf*0 never
    # survives the where)
    moved_up = jnp.where(alive, b_up,
                         jnp.where(tx_s > 0, eff * tx_s, 0.0))
    moved_down = jnp.where(alive, b_down,
                           jnp.where(down_win > 0, down * down_win, 0.0))
    compute_j = jnp.where(sched, spec.compute_power_w * c_s, 0.0)
    return (eff, cuts, comp_s, times, sched, withdrawn, alive,
            energy_after, moved_up, moved_down, compute_j, tx_s, charge)


# ----------------------------------------------------------- spec builders --
def build_spec(cfg, *, cutter=None, bits=None, es_assign,
               num_clients) -> CoreSpec:
    """Derive the static CoreSpec of a scheduler configuration.

    ``cutter``/``bits`` follow the ParticipationScheduler constructor
    (exactly one).  Raises for shapes the vectorized path cannot
    reproduce bit-identically (pipelined chunk counts beyond numpy's
    non-recursive pairwise-summation range)."""
    del num_clients  # shape comes from the arrays; kept for call clarity
    cap = cfg.es_uplink_mbps * 1e6
    contend = cfg.model != "ideal" and bool(np.isfinite(cap))
    es = np.asarray(es_assign, int)
    num_es = int(es.max()) + 1 if es.size else 1
    if cutter is not None:
        chunks = max(int(cutter.chunks), 1)
        spec_kw = dict(
            has_cutter=True, adaptive=cutter.policy != "fixed",
            policy=cutter.policy, fixed_cut=int(cutter.fixed_cut),
            num_cells=cutter.num_cuts,
            cutter_deadline_s=float(cutter.deadline_s),
            cutter_tx_power_w=float(cutter.tx_power_w),
            cutter_compute_power_w=float(cutter.compute_power_w),
            cutter_pipeline=bool(cutter.pipeline),
            cutter_ea=float(cutter.expected_attempts),
            cutter_hb=float(cutter.harq_backoff_s))
    else:
        chunks = max(int(bits.chunks), 1)
        spec_kw = dict(
            has_cutter=False, adaptive=False, policy="fixed", fixed_cut=0,
            num_cells=1, cutter_deadline_s=float("inf"),
            cutter_tx_power_w=0.0, cutter_compute_power_w=0.0,
            cutter_pipeline=False, cutter_ea=1.0, cutter_hb=0.0)
    if cfg.pipeline and chunks > MAX_CHUNKS:
        raise ValueError(
            f"pipelined chunk count {chunks} exceeds {MAX_CHUNKS}: numpy "
            f"sums that many columns with recursive pairwise blocks, which "
            f"the vectorized path does not replicate")
    return CoreSpec(
        model=cfg.model, up_mean_bps=cfg.mean_uplink_mbps * 1e6,
        down_mean_bps=cfg.mean_downlink_mbps * 1e6,
        latency_s=float(cfg.latency_s),
        has_down_trace=bool(cfg.model == "trace" and cfg.trace_down),
        contend=contend, contention=cfg.contention, es_cap_bps=float(cap),
        num_es=num_es, reshare=bool(cfg.reshare_uplink),
        deadline_s=float(cfg.deadline_s), tx_power_w=float(cfg.tx_power_w),
        compute_power_w=float(cfg.compute_power_w),
        pipeline=bool(cfg.pipeline), chunks=chunks, **spec_kw)


def cell_tables(cutter) -> dict:
    """The cutter's per-cell arrays as the core's gather tables."""
    return {"up_bits": np.asarray(cutter.up_bits, np.float64),
            "down_bits": np.asarray(cutter.down_bits, np.float64),
            "up_stream": np.asarray(cutter.up_stream, np.float64),
            "up_tail": np.asarray(cutter.up_tail, np.float64),
            "flops": np.asarray(cutter.flops, np.float64)}


def fixed_tables(bits, flops: float, num_clients: int) -> dict:
    """Fixed-bits mode: per-client (U,) bit arrays + the scalar workload.

    Mirrors the oracle's broadcasting of scalar RoundBits and the
    pipelined builder's ``up_stream is None`` degeneration (the whole
    uplink as one stream payload, no tail)."""
    def bc(x):
        return np.ascontiguousarray(
            np.broadcast_to(np.asarray(x, np.float64), (num_clients,)))
    stream = bits.up_stream if bits.up_stream is not None else bits.uplink
    tail = bits.up_tail if bits.up_stream is not None else 0.0
    return {"up_bits": bc(bits.uplink), "down_bits": bc(bits.downlink),
            "up_stream": bc(stream), "up_tail": bc(tail),
            "flops": np.asarray(flops, np.float64)}


_DUMMY_TABLES = {"up_bits": np.zeros(1), "down_bits": np.zeros(1),
                 "up_stream": np.zeros(1), "up_tail": np.zeros(1),
                 "flops": np.zeros(1)}


def dummy_tables() -> dict:
    """Placeholder for whichever of tables/fixed a spec does not use (jit
    still traces both pytree slots)."""
    return dict(_DUMMY_TABLES)
