"""Observability for the PHSFL stack: traces, metrics, manifests.

The paper's claims are about time, bits, and energy; this package makes
every one of them inspectable without perturbing a single number:

- **Trace export** (``telemetry.trace``): each wireless round's
  :class:`~repro.wireless.timeline.RoundTimeline` — compute chunks, uplink
  payloads with their HARQ retransmission attempts, downlink, crashes —
  becomes Chrome/Perfetto trace events, one track per client and per edge
  server, streamed to disk by :class:`TraceWriter`.  Open the file at
  https://ui.perfetto.dev or chrome://tracing.
- **Metrics** (``telemetry.metrics``): a stdlib-only typed registry of
  counters/gauges/histograms.  The scheduler registers participation,
  withdrawals/backfills, goodput-vs-retransmit bits, stale-bank
  depth/age, and per-phase energy; FedSim registers round wall time, eval
  accuracy, and live-vs-stale aggregation mass.  Flushed as JSONL plus a
  run-end summary table.
- **Spans and scopes** (``telemetry.spans``): the program's named layers.
  Device scopes (``jax.named_scope``: ``embed``, ``mlstm``, ``slstm``,
  ``attention``, ``mlp``, ``lm_loss``, ``optimizer``, ``edge_agg``,
  ``global_agg``) label every operation of the jitted round, backward
  included, so a device profile splits by layer.  Host spans
  (``sched.cohort``, ``sched.channel``, ``sched.price``, ``sched.report``
  inside ``CohortScheduler.step``) are off by default; with a sink
  installed each is a ``jax.profiler.TraceAnnotation`` on the profiler's
  clock and a ``span.<name>`` histogram of host seconds.
- **Manifest** (``telemetry.manifest``): config hash, seeds, jax/device
  info, git SHA — who made this artifact.

:class:`Telemetry` bundles the three behind one handle.  The OFF state is
the default everywhere (``telemetry=None`` parameters, enforced by the
``telemetry-off-default`` reprolint rule) and is bit-inert: no file I/O,
no RNG, no arithmetic — the golden-report regressions run against it.
See the package README for file formats and knobs.
"""

from __future__ import annotations

import os

from repro.telemetry.manifest import (collect_manifest, config_hash,
                                      write_manifest)
from repro.telemetry.metrics import (Counter, Gauge, Histogram,
                                     MetricsRegistry)
from repro.telemetry.sinks import MetricLogger, json_safe
from repro.telemetry.spans import (SCOPES, SPAN_NAMES, get_span_sink, scope,
                                   set_span_sink, span)
from repro.telemetry.trace import (TraceWriter, round_span_s,
                                   timeline_to_trace_events)

__all__ = [
    "Telemetry",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "TraceWriter", "timeline_to_trace_events", "round_span_s",
    "collect_manifest", "config_hash", "write_manifest",
    "MetricLogger", "json_safe",
    "span", "scope", "set_span_sink", "get_span_sink", "SCOPES",
    "SPAN_NAMES",
]


class Telemetry:
    """One handle over the run's trace writer, metrics registry, manifest.

    ``Telemetry(out_dir)`` is the ON state: ``<out_dir>/trace.json``
    (streamed Chrome trace), ``<out_dir>/metrics.jsonl`` (one registry
    snapshot every ``metrics_every`` flushes), ``<out_dir>/manifest.json``
    (via :meth:`write_manifest`), ``<out_dir>/summary.txt`` (at
    :meth:`close`).  ``spans=True`` additionally installs the metrics
    registry as the span sink (:func:`set_span_sink`) until :meth:`close`.

    ``Telemetry.disabled()`` is the OFF state every entry point defaults
    to: ``enabled`` is False and :meth:`record_round` / :meth:`flush` /
    :meth:`close` return immediately — instrumented code stays bit-inert.
    """

    def __init__(self, out_dir: str | None = None, *, trace: bool = True,
                 metrics_every: int = 1, spans: bool = False,
                 _enabled: bool = True):
        self.enabled = bool(_enabled)
        self.out_dir = out_dir
        self.metrics = MetricsRegistry()
        self.metrics_every = max(int(metrics_every), 1)
        self.trace = None
        self._metrics_fh = None
        self._flushes = 0
        self._owns_span_sink = False
        self._closed = False
        if not self.enabled:
            return
        if out_dir is not None:
            os.makedirs(out_dir, exist_ok=True)
            if trace:
                self.trace = TraceWriter(os.path.join(out_dir,
                                                      "trace.json"))
            self._metrics_fh = open(os.path.join(out_dir, "metrics.jsonl"),
                                    "w")
        if spans:
            set_span_sink(self.metrics)
            self._owns_span_sink = True

    _DISABLED = None

    @classmethod
    def disabled(cls) -> "Telemetry":
        """The shared OFF instance (the default of every entry point)."""
        if cls._DISABLED is None:
            cls._DISABLED = cls(_enabled=False)
        return cls._DISABLED

    # ------------------------------------------------------------ rounds --
    def record_round(self, report, timeline, *, es_assign=None,
                     deadline_s: float = float("inf"),
                     withdrawn: int = 0, backfilled: int = 0,
                     tx_j: float = 0.0, bank_depth: int = 0,
                     bank_age_max: int = 0) -> None:
        """One scheduler round: trace events + the scheduler's instruments
        (called by ``ParticipationScheduler.step`` when telemetry is on)."""
        if not self.enabled:
            return
        m = self.metrics
        rep = report
        m.counter("sched.rounds").inc()
        m.counter("sched.participants").inc(rep.num_participants)
        if rep.scheduled is not None:
            m.counter("sched.scheduled").inc(int(rep.scheduled.sum()))
        m.counter("sched.withdrawn").inc(int(withdrawn))
        m.counter("sched.backfilled").inc(int(backfilled))
        m.counter("sched.bits_moved").inc(float(rep.bits_tx))
        m.counter("sched.goodput_bits").inc(
            max(float(rep.bits_tx) - float(rep.retx_bits), 0.0))
        m.counter("sched.retx_bits").inc(float(rep.retx_bits))
        m.counter("energy.retx_j").inc(float(rep.retx_j))
        m.counter("energy.tx_j").inc(float(tx_j))
        if rep.compute_j is not None:
            m.counter("energy.compute_j").inc(float(rep.compute_j.sum()))
        m.gauge("sched.participation").set(
            rep.num_participants / max(len(rep.mask), 1))
        m.histogram("sched.round_time_s").observe(float(rep.round_time_s))
        if rep.stale_banked is not None:
            m.counter("stale.banked").inc(int(rep.stale_banked.sum()))
            m.counter("stale.delivered").inc(
                int((rep.stale_delivered > 0).sum()))
            m.counter("stale.dropped").inc(int(rep.stale_dropped.sum()))
            m.gauge("stale.bank_depth").set(int(bank_depth))
            m.gauge("stale.bank_age_max").set(int(bank_age_max))
        if rep.crashed is not None:
            m.counter("faults.crashed").inc(int(rep.crashed.sum()))
            m.counter("faults.failed").inc(int(rep.failed.sum()))
        if rep.es_down is not None:
            m.counter("faults.es_down_rounds").inc(int(rep.es_down.sum()))
        if self.trace is not None:
            self.trace.add_round(report, timeline, es_assign=es_assign,
                                 deadline_s=deadline_s)
        self.flush(step=int(rep.round_idx))

    # ------------------------------------------------------------- sinks --
    def flush(self, step: int | None = None, force: bool = False) -> None:
        """Append one metrics.jsonl snapshot every ``metrics_every`` calls
        (every call with ``force``)."""
        if not self.enabled or self._metrics_fh is None:
            return
        self._flushes += 1
        if force or (self._flushes - 1) % self.metrics_every == 0:
            self.metrics.flush_jsonl(self._metrics_fh, step=step)
            self._metrics_fh.flush()

    def write_manifest(self, *, config=None, seeds=None,
                       extra=None) -> dict | None:
        """Collect and (when an out_dir exists) write manifest.json."""
        if not self.enabled:
            return None
        man = collect_manifest(config=config, seeds=seeds, extra=extra)
        if self.out_dir is not None:
            write_manifest(os.path.join(self.out_dir, "manifest.json"), man)
        return man

    def summary(self) -> str:
        return self.metrics.summary_table()

    def close(self) -> str | None:
        """Final flush, summary.txt, trace finalization.  Idempotent;
        returns the summary table (None when disabled)."""
        if not self.enabled:
            return None
        if self._closed:
            return self.summary()
        self._closed = True
        if self._owns_span_sink and get_span_sink() is self.metrics:
            set_span_sink(None)
        table = self.summary()
        if self._metrics_fh is not None:
            self.metrics.flush_jsonl(self._metrics_fh, step=None)
            self._metrics_fh.close()
            self._metrics_fh = None
        if self.out_dir is not None:
            with open(os.path.join(self.out_dir, "summary.txt"), "w") as fh:
                fh.write(table + "\n")
        if self.trace is not None:
            self.trace.close()
        return table
