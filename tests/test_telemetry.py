"""Telemetry subsystem: trace export, metrics registry, sinks, manifests.

The load-bearing assertions:

- a hand-computed 2-client pipelined fault round (one HARQ retransmission,
  one crash) exports EXACTLY the expected trace events — segment names,
  track ids, microsecond timestamps;
- trace export is a pure function (repeated export is identical) and the
  streamed trace of a REAL fault-injected pipelined scheduler run
  reproduces every RoundTimeline segment number exactly;
- ``Telemetry.disabled()`` (the default everywhere) is bit-inert: the
  golden FedSim history captured at the pre-telemetry HEAD still matches;
- ``MetricLogger`` preserves JSON-native value types (the old
  ``float-or-str`` coercion regression);
- ``tools.bench_report`` unifies the drifted BENCH row schemas and FAILS
  on malformed records.
"""

import io
import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import FaultConfig, WirelessConfig
from repro.telemetry import (Counter, Gauge, Histogram, MetricLogger,
                             MetricsRegistry, Telemetry, TraceWriter,
                             collect_manifest, config_hash, json_safe,
                             round_span_s, timeline_to_trace_events)
from repro.wireless import make_scheduler
from repro.wireless.channel import LinkState, RoundBits
from repro.wireless.faults import FaultPlan
from repro.wireless.timeline import build_timeline

from tools import bench_report


# ------------------------------------------------------------------ metrics
class TestMetrics:
    def test_counter_monotone(self):
        c = Counter("n")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_last_write(self):
        g = Gauge("g")
        g.set(4)
        g.set(-1.5)
        assert g.value == -1.5

    def test_histogram_stats_and_buckets(self):
        h = Histogram("h", buckets=(1.0, 10.0))
        for v in (0.5, 2.0, 3.0, 20.0):
            h.observe(v)
        assert h.count == 4 and h.sum == 25.5
        assert h.min == 0.5 and h.max == 20.0 and h.mean == 25.5 / 4
        assert h.bucket_counts == [1, 2, 1]          # <=1, <=10, overflow

    def test_registry_get_or_create_and_kind_conflict(self):
        reg = MetricsRegistry()
        assert reg.counter("a") is reg.counter("a")
        with pytest.raises(TypeError):
            reg.gauge("a")

    def test_flush_jsonl_roundtrip(self):
        reg = MetricsRegistry()
        reg.counter("bits").inc(7)
        reg.gauge("acc").set(0.5)
        buf = io.StringIO()
        rec = reg.flush_jsonl(buf, step=3)
        parsed = json.loads(buf.getvalue())
        assert parsed == json.loads(json.dumps(rec))
        assert parsed["step"] == 3
        assert parsed["metrics"]["bits"]["value"] == 7
        assert parsed["metrics"]["acc"]["kind"] == "gauge"

    def test_summary_table_lists_all(self):
        reg = MetricsRegistry()
        reg.counter("z.last").inc()
        reg.histogram("a.first").observe(1.0)
        table = reg.summary_table()
        assert table.index("a.first") < table.index("z.last")


# ---------------------------------------------------------------- sinks
class TestMetricLogger:
    def test_json_native_types_preserved(self):
        # regression: the old logger coerced non-floats through str(),
        # stringifying ints, bools, and lists in the JSONL output
        out = io.StringIO()
        log = MetricLogger("t", stream=out)
        rec = log.log(step=2, n=3, ok=True, xs=[1, 2], name="adam",
                      arr=np.arange(2), scalar=np.float32(0.5))
        line = out.getvalue()
        parsed = json.loads(line.split("] ", 1)[1])
        assert parsed["n"] == 3 and isinstance(parsed["n"], int)
        assert parsed["ok"] is True
        assert parsed["xs"] == [1, 2]
        assert parsed["name"] == "adam"
        assert parsed["arr"] == [0, 1]
        assert parsed["scalar"] == 0.5
        assert rec["step"] == 2

    def test_json_safe_fallback(self):
        assert json_safe(object).startswith("<class")
        assert json_safe({"k": (1, np.int64(2))}) == {"k": [1, 2]}

    def test_telemetry_mirror(self):
        tel = Telemetry()                     # enabled, no out_dir: memory
        log = MetricLogger("t", stream=io.StringIO(), telemetry=tel)
        log.log(step=1, loss=2.5, name="x")
        snap = tel.metrics.snapshot()
        assert snap["log.t.loss"]["value"] == 2.5
        assert "log.t.name" not in snap      # non-numeric: not mirrored

    def test_shim_import(self):
        from repro.utils.logging import MetricLogger as Shim
        assert Shim is MetricLogger


# ---------------------------------------------------- hand-computed trace
def _two_client_fault_round():
    """2 clients, pipelined (2 chunks + tail), client 0 retransmits payload
    1 once, client 1 crashes at t=3.5 — every number below is hand-derived.

    Rates: up 100 bps, down 200 bps.  comp_s=2.0 (c=1.0/chunk), payloads
    100 bits (u=1.0 s), tail 50 bits (0.5 s), downlink 100 bits (0.5 s),
    backoff 0.25 s, deadline 10 s.
    """
    U = 2
    link = LinkState(uplink_bps=np.full(U, 100.0),
                     downlink_bps=np.full(U, 200.0),
                     latency_s=np.zeros(U))
    bits = RoundBits(uplink=250.0, downlink=100.0, up_stream=100.0,
                     up_tail=50.0, chunks=2)
    plan = FaultPlan(
        up_attempts=np.array([[1, 2, 1], [1, 1, 1]]),
        up_ok=np.ones((2, 3), bool),
        down_attempts=np.array([1, 1]),
        down_ok=np.array([True, True]),
        crash_frac=np.array([np.inf, 0.35]),     # client 1 dies at 3.5 s
        backoff_s=0.25)
    return build_timeline(link, bits, np.full(U, 2.0), 10.0, U, plan=plan,
                          pipeline=True)


class TestTraceExport:
    def test_hand_computed_round(self):
        tl = _two_client_fault_round()
        evs = timeline_to_trace_events(tl, round_idx=7, t0_s=100.0)

        def seg(u, name):
            match = [e for e in evs if e["tid"] == u and e["name"] == name]
            assert len(match) == 1, (u, name, [e["name"] for e in evs])
            return match[0]

        us = 1e6
        # client 0: 2 compute chunks, 3 payloads + 1 retransmission
        assert seg(0, "compute[0]")["ts"] == 100.0 * us
        assert seg(0, "compute[1]")["ts"] == 101.0 * us
        assert seg(0, "compute[1]")["dur"] == 1.0 * us
        p0 = seg(0, "uplink[p0]")
        assert p0["ts"] == 101.0 * us and p0["dur"] == 1.0 * us
        assert p0["args"]["bits"] == 100.0 and p0["args"]["retx"] is False
        assert seg(0, "uplink[p1]")["ts"] == 102.0 * us
        retx = seg(0, "uplink[p1.a1]")      # backoff 0.25 after p1 ends at 3
        assert retx["ts"] == 103.25 * us and retx["dur"] == 1.0 * us
        assert retx["args"] == {"round": 7, "bits": 100.0, "payload": 1,
                                "attempt": 1, "retx": True}
        tail = seg(0, "uplink[p2]")
        assert tail["ts"] == 104.25 * us and tail["dur"] == 0.5 * us
        d0 = seg(0, "downlink")
        assert d0["ts"] == 104.75 * us and d0["dur"] == 0.5 * us
        assert d0["ph"] == "X" and d0["pid"] == 1

        # client 1: no retransmissions (its p1 placeholder column is
        # skipped), crash instant at its cap
        assert seg(1, "uplink[p0]")["ts"] == 101.0 * us
        assert seg(1, "uplink[p1]")["ts"] == 102.0 * us
        assert seg(1, "uplink[p2]")["ts"] == 103.0 * us
        assert seg(1, "downlink")["ts"] == 103.5 * us
        crash = seg(1, "crash")
        assert crash["ph"] == "i" and crash["ts"] == 103.5 * us
        assert not any(e["tid"] == 1 and ".a1]" in e["name"] for e in evs)

        # exactly the hand-enumerated event set, nothing else
        assert len([e for e in evs if e["tid"] == 0]) == 7
        assert len([e for e in evs if e["tid"] == 1]) == 7

    def test_export_is_deterministic(self):
        tl = _two_client_fault_round()
        a = timeline_to_trace_events(tl, 0)
        b = timeline_to_trace_events(tl, 0)
        assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)

    def test_clients_mask_hides_tracks(self):
        tl = _two_client_fault_round()
        evs = timeline_to_trace_events(tl, 0, clients=[True, False])
        assert {e["tid"] for e in evs} == {0}

    def test_fault_free_timeline_has_no_fault_fields(self):
        U = 2
        link = LinkState(np.full(U, 100.0), np.full(U, 200.0), np.zeros(U))
        tl = build_timeline(link, RoundBits(uplink=100.0, downlink=50.0),
                            np.zeros(U), np.inf, U)
        assert tl.tx_payload is None and tl.crashed is None
        evs = timeline_to_trace_events(tl, 0)
        names = {e["name"] for e in evs}
        assert names == {"compute", "uplink", "downlink"}


# ------------------------------------------- trace vs scheduler timeline
def _fault_scheduler(U=4):
    from repro.configs.phsfl_cnn import CONFIG as CNN_CFG
    from repro.core.comm import comm_for_cnn
    cfg = WirelessConfig(model="static", mean_uplink_mbps=20.0,
                         mean_downlink_mbps=80.0, deadline_s=3.0,
                         pipeline=True, staleness_lambda=0.5,
                         faults=FaultConfig(erasure_prob=0.4, max_retries=2,
                                            backoff_s=0.1, crash_hazard=0.2),
                         seed=0)
    comm = comm_for_cnn(CNN_CFG, dataset_size=400, batch_size=16,
                        batches_per_epoch=2)
    return make_scheduler(cfg, U, comm, 2, es_assign=np.arange(U) // 2)


class TestTraceVsTimeline:
    def test_streamed_trace_reproduces_timeline_exactly(self, tmp_path):
        """Every segment number in the streamed trace of a REAL
        fault-injected pipelined run equals the scheduler's RoundTimeline
        (exact float equality — export and JSON never round)."""
        sched = _fault_scheduler()
        w = TraceWriter(tmp_path / "trace.json")
        rounds = []
        for r in range(4):
            t0 = w.clock_s
            rep = sched.step(r)
            w.add_round(rep, sched.last_timeline, es_assign=sched.es_assign,
                        deadline_s=sched.cfg.deadline_s)
            rounds.append((t0, rep, sched.last_timeline))
        w.close()
        evs = json.load(open(tmp_path / "trace.json"))
        assert any(".a1]" in e["name"] for e in evs), "no retx in scenario"
        for t0, rep, tl in rounds:
            r = int(rep.round_idx)
            mine = [e for e in evs if e.get("ph") == "X" and e["pid"] == 1
                    and e["args"]["round"] == r]
            for u in np.flatnonzero(rep.scheduled):
                # uplink starts: trace == timeline, exact float equality
                # in microsecond space (the exporter's own formula)
                got = sorted(e["ts"] for e in mine
                             if e["tid"] == u and "uplink" in e["name"])
                want = sorted(
                    (t0 + float(s)) * 1e6
                    for s, b in zip(tl.tx_start[u], tl.tx_bits[u])
                    if b > 0 and math.isfinite(s))
                assert got == want, (r, u)
                gd = sorted(e["dur"] for e in mine
                            if e["tid"] == u and "uplink" in e["name"])
                wd = sorted(
                    float(e - s) * 1e6 for s, e, b in
                    zip(tl.tx_start[u], tl.tx_end[u], tl.tx_bits[u])
                    if b > 0 and math.isfinite(e))
                assert gd == wd, (r, u)
                down = [e for e in mine if e["tid"] == u
                        and e["name"] == "downlink"]
                if math.isfinite(tl.down_end[u]):
                    assert down[0]["ts"] == (t0 + float(
                        tl.down_start[u])) * 1e6
            # crashes appear as instants at the cap
            if rep.crashed is not None:
                for u in np.flatnonzero(rep.crashed):
                    cr = [e for e in evs if e.get("ph") == "i"
                          and e.get("tid") == u and e["name"] == "crash"
                          and e["args"]["round"] == r]
                    assert cr and cr[0]["ts"] == (t0 + float(
                        tl.cap_s[u])) * 1e6

    def test_round_span_covers_segments(self):
        sched = _fault_scheduler()
        rep = sched.step(0)
        span = round_span_s(rep, sched.last_timeline)
        assert span >= rep.round_time_s
        assert math.isfinite(span)

    def test_writer_tracks_and_markers(self, tmp_path):
        sched = _fault_scheduler()
        w = TraceWriter(tmp_path / "t.json")
        for r in range(2):
            rep = sched.step(r)
            w.add_round(rep, sched.last_timeline, es_assign=sched.es_assign,
                        deadline_s=3.0)
        w.close()
        evs = json.load(open(tmp_path / "t.json"))
        meta = {(e["pid"], e.get("tid"), e["args"]["name"]) for e in evs
                if e["ph"] == "M"}
        assert (0, None, "round markers") in meta
        assert (2, 0, "ES 0") in meta and (2, 1, "ES 1") in meta
        marks = [e for e in evs if e["ph"] == "i" and e["pid"] == 0]
        assert {m["name"] for m in marks} >= {"round 0", "round 1",
                                              "deadline"}
        es_spans = [e for e in evs if e["pid"] == 2 and e["ph"] == "X"]
        assert len(es_spans) == 4                   # 2 ES x 2 rounds

    def test_streamed_file_valid_without_close(self, tmp_path):
        # crash-safety: the JSON Array Format's trailing ] is optional
        sched = _fault_scheduler()
        w = TraceWriter(tmp_path / "t.json")
        rep = sched.step(0)
        w.add_round(rep, sched.last_timeline)
        w._fh.flush()
        text = open(tmp_path / "t.json").read()
        evs = json.loads(text + "]")                # viewer-equivalent fixup
        assert len(evs) > 0


# ----------------------------------------------------- scheduler metrics
class TestSchedulerTelemetry:
    def test_record_round_instruments(self, tmp_path):
        tel = Telemetry(str(tmp_path))
        sched = _fault_scheduler()
        sched.telemetry = tel
        for r in range(3):
            sched.step(r)
        snap = tel.metrics.snapshot()
        assert snap["sched.rounds"]["value"] == 3
        assert snap["sched.round_time_s"]["count"] == 3
        assert (snap["sched.goodput_bits"]["value"]
                + snap["sched.retx_bits"]["value"]
                == pytest.approx(snap["sched.bits_moved"]["value"]))
        assert "stale.bank_depth" in snap
        tel.close()
        lines = [json.loads(l) for l in
                 open(tmp_path / "metrics.jsonl")]
        assert len(lines) == 4                       # 3 rounds + final
        assert lines[0]["step"] == 0

    def test_disabled_is_inert_no_op(self):
        tel = Telemetry.disabled()
        assert tel is Telemetry.disabled()           # shared singleton
        assert not tel.enabled
        assert tel.record_round(None, None) is None  # never touches args
        assert tel.close() is None
        assert tel.write_manifest(config={"x": 1}) is None

    def test_scheduler_results_identical_with_telemetry(self, tmp_path):
        a, b = _fault_scheduler(), _fault_scheduler()
        b.telemetry = Telemetry(str(tmp_path))
        for r in range(3):
            ra, rb = a.step(r), b.step(r)
            assert ra.round_time_s == rb.round_time_s
            assert ra.bits_tx == rb.bits_tx
            np.testing.assert_array_equal(ra.mask, rb.mask)
            np.testing.assert_array_equal(ra.energy_left_j, rb.energy_left_j)


# ------------------------------------------------------------- manifest
class TestManifest:
    def test_config_hash_stable_and_distinct(self):
        w1 = WirelessConfig(model="static")
        w2 = WirelessConfig(model="static")
        w3 = WirelessConfig(model="rayleigh")
        assert config_hash(w1) == config_hash(w2)
        assert config_hash(w1) != config_hash(w3)
        assert config_hash(None) is None

    def test_collect_manifest_fields(self):
        man = collect_manifest(config={"a": 1}, seeds={"seed": 7},
                               extra={"note": "x"})
        assert man["seeds"] == {"seed": 7}
        assert man["note"] == "x"
        assert man["python"] and man["platform"]
        json.dumps(man, default=repr)                # JSON-serializable


# ---------------------------------------------------------- bench report
class TestBenchReport:
    def test_normalizes_drifted_schemas(self, tmp_path):
        (tmp_path / "BENCH_a.json").write_text(json.dumps([
            {"mode": "serial", "total_bits": 10.0, "final_acc": 0.5},
            {"policy": "harq", "erasure_prob": 0.3, "bits": 20.0,
             "failed": [0, 1, 1, 0], "crashed": 2},
        ]))
        (tmp_path / "BENCH_b.json").write_text(json.dumps([
            {"name": "lm", "bits_tx": 30.0, "stale_delivered": [1, 0]},
        ]))
        rows = bench_report.load_all(str(tmp_path))
        assert [r["source"] for r in rows] == ["a", "a", "b"]
        assert rows[0]["label"] == "serial"
        assert rows[1]["label"] == "harq @ p=0.3"
        assert [r["total_bits"] for r in rows] == [10.0, 20.0, 30.0]
        assert rows[1]["failed"] == 2 and rows[1]["crashed"] == 2
        assert rows[2]["stale_delivered"] == 1
        md = bench_report.to_markdown(rows)
        assert md.splitlines()[0].startswith("| source | label |")
        buf = io.StringIO()
        bench_report.write_csv(rows, buf)
        assert len(buf.getvalue().splitlines()) == 4

    def test_malformed_records_fail(self, tmp_path):
        (tmp_path / "BENCH_bad.json").write_text('{"not": "a list"}')
        with pytest.raises(bench_report.MalformedRecord):
            bench_report.load_all(str(tmp_path))
        (tmp_path / "BENCH_bad.json").write_text(
            json.dumps([{"mode": "x", "final_acc": "high"}]))
        with pytest.raises(bench_report.MalformedRecord):
            bench_report.load_all(str(tmp_path))
        (tmp_path / "BENCH_bad.json").write_text("not json")
        with pytest.raises(bench_report.MalformedRecord):
            bench_report.load_all(str(tmp_path))

    def test_cli_on_real_repo_files(self, tmp_path, capsys):
        assert bench_report.main(["--dir", ".", "--csv",
                                  str(tmp_path / "r.csv")]) == 0
        out = capsys.readouterr().out
        assert out.startswith("| source | label |")
        assert (tmp_path / "r.csv").exists()

    def test_cli_empty_dir_fails(self, tmp_path):
        assert bench_report.main(["--dir", str(tmp_path)]) == 1


# --------------------------------------------- FedSim golden bit-identity
@pytest.mark.slow
class TestFedSimGolden:
    def test_disabled_telemetry_bit_identical_to_pre_telemetry_head(self):
        """The telemetry-off default reproduces the golden FedSim history
        captured at the pre-telemetry HEAD, bit for bit — and running the
        SAME simulation with telemetry ON changes nothing either."""
        from repro.configs.phsfl_cnn import CONFIG
        from repro.configs.sweeps import (sweep_hierarchy, sweep_train,
                                          sweep_wireless)
        from repro.core.fedsim import FedSim
        from repro.data.synthetic import make_federated_image_data

        golden = json.load(open("tests/golden_fedsim_history.json"))
        data = make_federated_image_data(8, alpha=0.3, train_per_class=40,
                                         test_per_class=20, seed=0)
        h, t = sweep_hierarchy(2), sweep_train()
        w = sweep_wireless("static", deadline_s=3.0, pipeline=True,
                           staleness_lambda=0.5,
                           faults=FaultConfig(erasure_prob=0.3,
                                              max_retries=2,
                                              crash_hazard=0.2), seed=0)
        sim = FedSim(CONFIG, data, h, t, batches_per_epoch=2, seed=0,
                     wireless=w)                     # telemetry DEFAULT off
        res = sim.run(rounds=2, log_every=1)
        assert res.history == golden["history"]
        assert res.network == golden["network"]
        assert res.total_sim_time_s == golden["total_sim_time_s"]
        psum = float(sum(np.asarray(x, np.float64).sum()
                         for x in jax.tree.leaves(res.global_params)))
        assert psum == golden["global_params_sum"]
