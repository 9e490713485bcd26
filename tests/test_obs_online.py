"""Online-observability tests (DESIGN.md §Observability, "Online tier").

Unit coverage for the rolling-window instruments (bucket expiry, backward
stamps, window queries), the SLO burn-rate monitor (multi-window AND rule,
cooldown, min-requests guard), the flight recorder (bounded rings, rate
limiting, schema-valid dumps), and the ``attach_measured`` edge cases.
Integration coverage: engine and sim feed identical windowed metric names
and identical burn-rate alert semantics (parity); a controller wired with
``burn_alerts=`` re-solves on an injected slowdown BEFORE the next
interval tick; the dispatch profiler lands the host/device split on
sampled TickRecords; tracer drop counters stay zero on normal runs.
"""
import json
import math
import os

import numpy as np
import pytest

from conftest import MAX_NEW, PROMPT_LEN, VOCAB, tiny_engine

from repro.obs import (Alert, BurnRateRule, CollectingSink, DecisionAudit,
                       FlightRecorder, FlightTrigger, MetricWindows,
                       NULL_WINDOWS, Observability, SLOMonitor,
                       slo_class_key)
from repro.obs.export import (assert_zero, summarize_file,
                              validate_metrics_file, validate_trace_file,
                              write_metrics_jsonl)
from repro.obs.slo import bad_metric, good_metric
from repro.obs.windows import WindowedCounter, WindowedHistogram


# ------------------------------------------------------------- windows
def test_windowed_counter_totals_and_expiry():
    c = WindowedCounter("x", window_s=10.0, n_buckets=10)  # 1 s buckets
    c.inc(0.5)
    c.inc(1.5, 2)
    c.inc(2.5)
    assert c.total(2.5) == 4.0
    assert c.total(2.5, window_s=1.0) == 1.0      # newest bucket only
    assert c.total(2.5, window_s=2.0) == 3.0
    # advancing 10 s expires everything; rate follows
    assert c.total(12.6) == 0.0
    assert c.rate(12.6) == 0.0


def test_windowed_counter_backward_stamp_clamps_and_negative_raises():
    c = WindowedCounter("x", window_s=10.0, n_buckets=10)
    c.inc(5.0)
    c.inc(1.0)          # behind the newest bucket: clamps into it
    assert c.total(5.0, window_s=1.0) == 2.0
    with pytest.raises(ValueError):
        c.inc(6.0, -1)


def test_windowed_counter_large_clock_jump_resets_ring():
    c = WindowedCounter("x", window_s=10.0, n_buckets=10)
    for t in range(10):
        c.inc(float(t))
    assert c.total(9.0) == 10.0
    c.inc(1e6)          # jump far past the ring: only the new bucket lives
    assert c.total(1e6) == 1.0


def test_windowed_histogram_stats_and_expiry():
    h = WindowedHistogram("lat", window_s=10.0, n_buckets=10)
    for i, v in enumerate([5.0, 7.0, 10.0, 12.0]):
        h.observe(float(i), v)
    assert h.count(3.0) == 4
    assert h.mean(3.0) == pytest.approx(8.5)
    assert h.percentile(3.0, 50) == pytest.approx(8.5)
    assert h.count(3.0, window_s=1.0) == 1        # newest bucket
    assert h.count(30.0) == 0
    assert math.isnan(h.mean(30.0))
    assert math.isnan(h.percentile(30.0, 99))


def test_windowed_histogram_sample_cap_keeps_exact_count():
    h = WindowedHistogram("lat", window_s=10.0, n_buckets=10, cap=4)
    for _ in range(20):
        h.observe(0.5, 1.0)
    assert h.count(0.5) == 20                      # count/sum stay exact
    assert h.mean(0.5) == pytest.approx(1.0)


def test_metric_windows_map_and_null():
    w = MetricWindows(window_s=10.0, n_buckets=10)
    assert w.on
    w.inc("a", 1.0, 2)
    w.observe("b", 1.0, 3.0)
    assert w.names() == ["a", "b"]
    assert w.counter("a").total(1.0) == 2.0
    assert w.rate("a", 1.0, window_s=10.0) == pytest.approx(0.2)
    assert w.rate("b", 1.0) == 0.0                 # histogram: no rate
    assert not NULL_WINDOWS.on
    NULL_WINDOWS.inc("a", 0.0)                     # no-op, no state
    assert NULL_WINDOWS.names() == []


def test_window_snapshot_rows_validate(tmp_path):
    w = MetricWindows(window_s=10.0, n_buckets=10)
    w.inc("req", 1.0, 3)
    w.observe("lat", 1.0, 9.0)
    rows = w.snapshot(1.0)
    assert {r["kind"] for r in rows} == {"window_counter",
                                         "window_histogram"}
    p = tmp_path / "m.jsonl"
    with open(p, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    assert validate_metrics_file(str(p)) == 2


# ----------------------------------------------------------------- slo
def test_slo_class_key_formats():
    assert slo_class_key(750.0) == "750"
    assert slo_class_key(1500.5) == "1500.5"
    assert slo_class_key(0.0) == "none"
    assert slo_class_key(-1.0) == "none"
    assert good_metric("750") == "slo.class.750.good"
    assert bad_metric("none") == "slo.class.none.bad"


def _fed_windows(goods, bads, cls="750"):
    """Windows with (t, n) good/bad feeds for one class."""
    w = MetricWindows(window_s=60.0, n_buckets=60)
    for t, n in goods:
        w.inc(good_metric(cls), t, n)
    for t, n in bads:
        w.inc(bad_metric(cls), t, n)
    return w


def test_burn_rate_monitor_fires_on_both_windows():
    w = _fed_windows(goods=[], bads=[(t, 2) for t in range(0, 31)])
    sink = CollectingSink()
    mon = SLOMonitor(w, budget=0.05,
                     rules=(BurnRateRule(fast_s=5.0, slow_s=30.0),),
                     sinks=(sink,), min_requests=5)
    fired = mon.check(30.0)
    assert len(fired) == 1
    a = fired[0]
    assert a.slo_class == "750" and a.kind == "burn_rate"
    assert a.burn_fast == pytest.approx(20.0)      # all-bad / 0.05 budget
    assert a.burn_slow == pytest.approx(20.0)
    assert sink.pending() == 1
    assert sink.pop_pending() == [a] and sink.pending() == 0
    assert sink.alerts == [a]                      # history survives pop


def test_burn_rate_needs_slow_window_too():
    # bad only in the last 3 s: the 5 s fast window burns, the 30 s slow
    # window is still mostly good -> no alert (one-bucket blip filter)
    w = _fed_windows(goods=[(t, 10) for t in range(0, 27)],
                     bads=[(t, 2) for t in (27, 28, 29)])
    mon = SLOMonitor(w, budget=0.05,
                     rules=(BurnRateRule(fast_s=3.0, slow_s=30.0),))
    assert mon.check(29.5) == []


def test_burn_rate_min_requests_silences_noise():
    w = _fed_windows(goods=[], bads=[(0.5, 2)])    # 2 < min_requests
    mon = SLOMonitor(w, budget=0.05,
                     rules=(BurnRateRule(fast_s=5.0, slow_s=30.0),),
                     min_requests=5)
    assert mon.burn_rate("750", 1.0, 5.0) is None
    assert mon.check(1.0) == []


def test_burn_rate_cooldown_rearms():
    w = _fed_windows(goods=[], bads=[(float(t), 2) for t in range(0, 60)])
    mon = SLOMonitor(w, budget=0.05,
                     rules=(BurnRateRule(fast_s=5.0, slow_s=30.0),),
                     cooldown_s=10.0)
    assert len(mon.check(30.0)) == 1
    assert mon.check(35.0) == []                   # inside cooldown
    assert len(mon.check(41.0)) == 1               # re-armed
    assert len(mon.alerts) == 2


def test_monitor_disabled_windows_noop():
    mon = SLOMonitor(NULL_WINDOWS)
    assert mon.check(0.0) == []


# ----------------------------------------------------- controller reaction
def _mini_controller(burn_alerts=None, reactive=False):
    from repro.core.adapter import ControllerConfig, InfAdapterController
    from repro.core.forecaster import MovingMaxForecaster
    from repro.core.profiles import paper_resnet_profiles
    cfg = ControllerConfig(interval_s=30.0, budget=8, slo_ms=750.0,
                           reactive=reactive)
    profiles = paper_resnet_profiles()
    ctrl = InfAdapterController(profiles, MovingMaxForecaster(window=10),
                                cfg, burn_alerts=burn_alerts)
    return ctrl, profiles


def test_maybe_react_resolves_on_burn_alert_without_reactive():
    from repro.sim.cluster import SimCluster
    sink = CollectingSink()
    ctrl, profiles = _mini_controller(burn_alerts=sink, reactive=False)
    sim = SimCluster(profiles)
    ctrl.monitor.record(0.0, 5)
    ctrl.step(0.0, sim)
    assert ctrl.maybe_react(3.0, sim) is None      # no alert pending
    sink.emit(Alert(t=3.0, slo_class="750", rule="fast5s/slow30s",
                    burn_fast=20.0, burn_slow=20.0, budget=0.05))
    d = ctrl.maybe_react(3.0, sim)
    assert d is not None and d.t == 3.0
    assert ctrl.audit.entries[-1].reason == "burn_rate"
    assert sink.pending() == 0                     # alert consumed
    # next interval step reverts to the normal reason
    ctrl.step(30.0, sim)
    assert ctrl.audit.entries[-1].reason == "interval"


def test_maybe_react_without_sink_keeps_legacy_gate():
    from repro.sim.cluster import SimCluster
    ctrl, profiles = _mini_controller(burn_alerts=None, reactive=False)
    sim = SimCluster(profiles)
    ctrl.monitor.record(0.0, 5)
    ctrl.step(0.0, sim)
    assert ctrl.maybe_react(3.0, sim) is None      # not reactive, no sink


def test_sim_burn_alert_resolves_before_next_interval():
    """End-to-end on the virtual clock: a replica slowdown makes requests
    miss their SLO, the monitor trips mid-interval, and the controller
    re-solves (reason burn_rate) BEFORE the next 30 s interval tick."""
    from repro.cluster import make_nodes
    from repro.cluster.faults import FaultSchedule, replica_slowdown
    from repro.sim.cluster import SimCluster
    from repro.sim.runner import run_experiment

    sink = CollectingSink()
    ctrl, profiles = _mini_controller(burn_alerts=sink, reactive=False)
    obs = Observability(windows=True)
    sim = SimCluster(profiles, nodes=make_nodes(2, 8), replica_size=1,
                     obs=obs)
    mon = SLOMonitor(obs.windows, budget=0.05,
                     rules=(BurnRateRule(fast_s=5.0, slow_s=15.0),),
                     sinks=(sink,), cooldown_s=60.0, min_requests=3)
    rate = np.full(60, 8.0)
    faults = FaultSchedule([])      # slowdown applied after warm-up below
    result = None

    # inject the slowdown on every replica shortly after t=10
    class SlowAt(FaultSchedule):
        def __init__(self):
            super().__init__([])
            self.done = False

        def next_t(self):
            return 10.0 if not self.done else float("inf")

        def apply_due(self, t, cluster):
            if self.done or t < 10.0:
                return []
            self.done = True
            evs = []
            for rid in list(cluster.fabric.replicas):
                e = replica_slowdown(10.0, rid, 50.0)
                cluster.inject_fault(10.0, e)
                evs.append(e)
            return evs

    result = run_experiment("burn", ctrl, profiles, rate, slo_ms=750.0,
                            interval_s=30.0, seed=0, cluster=sim,
                            warm_start={list(profiles)[0]: 1},
                            faults=SlowAt(), slo_monitor=mon)
    assert result is not None
    assert len(mon.alerts) >= 1
    burn = [e for e in ctrl.audit.entries if e.reason == "burn_rate"]
    assert burn, "no burn_rate re-solve recorded"
    assert 10.0 < burn[0].t < 30.0      # reacted before the interval tick


# ------------------------------------------------------ engine/sim parity
def _run_windowed_engine(slo_ms, **kw):
    from repro.serving.api import Request
    clk = [0.0]
    obs = Observability(trace=True, windows=True)
    eng = tiny_engine(clock=lambda: clk[0], obs=obs, queue_cap=64, **kw)
    name = next(iter(eng.variant_defs))
    eng.apply_allocation(0.0, {name: 1})
    rng = np.random.default_rng(1)
    for i in range(6):
        eng.submit(Request(rid=i, tokens=rng.integers(0, VOCAB, PROMPT_LEN),
                           max_new=MAX_NEW, arrival=clk[0], slo_ms=slo_ms),
                   None)
        eng.step(clk[0])
        clk[0] += 0.01
    for _ in range(500):
        if not (eng.backlog(clk[0]) or eng.in_flight()):
            break
        eng.step(clk[0])
        clk[0] += 0.01
    assert len(eng.done) == 6
    return eng, clk[0]


def _run_windowed_sim(slo_ms):
    from repro.core.profiles import paper_resnet_profiles
    from repro.serving.api import Request
    from repro.sim.cluster import SimCluster
    profiles = paper_resnet_profiles()
    obs = Observability(windows=True)
    sim = SimCluster(profiles, obs=obs)
    name = next(iter(profiles))
    sim.apply_allocation(-100.0, {name: 2})
    for i in range(20):
        sim.submit(Request(rid=i, tokens=np.zeros(0, np.int64), max_new=1,
                           arrival=float(i) * 0.05, slo_ms=slo_ms), name)
    sim.drain(2.0)
    return sim, 2.0


WINDOW_CORE = {"requests.submitted", "requests.completed",
               "request.latency_ms"}


def test_engine_and_sim_emit_same_windowed_names():
    slo = 750.0
    eng, t_e = _run_windowed_engine(slo)
    sim, t_s = _run_windowed_sim(slo)
    cls = slo_class_key(slo)
    for w, t in ((eng.windows, t_e), (sim.windows, t_s)):
        names = set(w.names())
        assert WINDOW_CORE <= names
        # every completion lands in exactly one per-class counter
        good = w.counter(good_metric(cls)).total(t)
        bad = w.counter(bad_metric(cls)).total(t)
        assert good + bad == w.counter("requests.completed").total(t) > 0
    # same vocabulary modulo the engine's extra goodput window
    e_names = {n for n in eng.windows.names()
               if n in WINDOW_CORE or n.startswith("slo.class.")}
    s_names = {n for n in sim.windows.names()
               if n in WINDOW_CORE or n.startswith("slo.class.")}
    assert e_names == s_names


def test_engine_and_sim_burn_alert_parity():
    """An impossible SLO turns every completion bad on BOTH backends; the
    same monitor configuration fires the same alert on each."""
    slo = 1e-6
    eng, t_e = _run_windowed_engine(slo)
    sim, t_s = _run_windowed_sim(slo)
    for w, t in ((eng.windows, t_e), (sim.windows, t_s)):
        mon = SLOMonitor(w, budget=0.05,
                         rules=(BurnRateRule(fast_s=5.0, slow_s=30.0),),
                         min_requests=3)
        fired = mon.check(t)
        assert len(fired) == 1
        assert fired[0].slo_class == slo_class_key(slo)
        assert fired[0].burn_fast == pytest.approx(20.0)


# -------------------------------------------------------- flight recorder
def test_flight_recorder_dump_roundtrip(tmp_path):
    eng, t = _run_windowed_engine(750.0)
    fr = FlightRecorder(out_dir=str(tmp_path), min_interval_s=0.0)
    for evs in eng.tracer.events.values():
        for e in evs:
            fr.push_event(e)
    for rec in eng.tracer.ticks:
        fr.push_tick(rec)
    fr.snap_metrics(t, eng.metrics)
    path = fr.trigger("unit_test", t, extra={"note": "roundtrip"})
    assert path is not None and os.path.basename(path) == \
        "FLIGHT_unit_test.json"
    n = validate_trace_file(path)
    assert n > 0
    with open(path) as f:
        obj = json.load(f)
    assert obj["otherData"]["flight_reason"] == "unit_test"
    assert obj["otherData"]["note"] == "roundtrip"
    # counter deltas render as Chrome "C" events on pid 3
    assert any(e.get("ph") == "C" and e.get("pid") == 3
               for e in obj["traceEvents"])


def test_flight_recorder_rings_are_bounded():
    fr = FlightRecorder(max_spans=4, max_ticks=2, max_metric_snaps=2)
    from repro.obs.trace import SpanEvent, TickRecord
    for i in range(10):
        fr.push_event(SpanEvent(rid=i, name="queued", t=float(i)))
    assert len(fr.spans) == 4
    assert fr.spans[0].rid == 6                    # oldest evicted
    for i in range(5):
        fr.push_tick(TickRecord(t=float(i), backend="b", kind="decode"))
    assert len(fr.ticks) == 2 and fr.ticks[0].t == 3.0


def test_flight_recorder_rate_limit_and_max_dumps(tmp_path):
    fr = FlightRecorder(out_dir=str(tmp_path), min_interval_s=5.0,
                        max_dumps=3)
    assert fr.trigger("a", 0.0) is not None
    assert fr.trigger("a", 2.0) is None            # inside min_interval
    assert fr.trigger("b", 2.0) is not None        # per-reason limit
    p3 = fr.trigger("a", 7.0)
    assert p3 is not None and p3.endswith("FLIGHT_a_2.json")
    assert fr.trigger("c", 100.0) is None          # max_dumps exhausted
    assert len(fr.dumps) == 3


def test_flight_trigger_sanitizes_reason(tmp_path):
    fr = FlightRecorder(out_dir=str(tmp_path), min_interval_s=0.0)
    p = fr.trigger("burn rate: 750/ms!", 0.0)
    assert os.path.basename(p) == "FLIGHT_burn_rate_750_ms.json"


def test_fault_injection_triggers_flight_dump(tmp_path):
    from repro.cluster import make_nodes
    from repro.cluster.faults import replica_slowdown
    from repro.core.profiles import paper_resnet_profiles
    from repro.sim.cluster import SimCluster
    fr = FlightRecorder(out_dir=str(tmp_path), min_interval_s=0.0)
    obs = Observability(windows=True, flight=fr)
    assert obs.tracer.on                           # flight implies trace
    profiles = paper_resnet_profiles()
    sim = SimCluster(profiles, nodes=make_nodes(1, 4), replica_size=1,
                     obs=obs)
    sim.apply_allocation(-100.0, {list(profiles)[0]: 1})
    rid = next(iter(sim.fabric.replicas))
    sim.inject_fault(1.0, replica_slowdown(1.0, rid, 4.0))
    assert len(fr.dumps) == 1
    assert "fault_replica_slowdown" in fr.dumps[0]
    assert validate_trace_file(fr.dumps[0]) > 0


def test_alert_sink_flight_trigger(tmp_path):
    fr = FlightRecorder(out_dir=str(tmp_path), min_interval_s=0.0)
    FlightTrigger(fr).emit(Alert(t=1.0, slo_class="750",
                                 rule="fast5s/slow30s", burn_fast=4.0,
                                 burn_slow=3.0, budget=0.05))
    assert len(fr.dumps) == 1
    assert os.path.basename(fr.dumps[0]) == "FLIGHT_burn_rate_750.json"
    with open(fr.dumps[0]) as f:
        assert json.load(f)["otherData"]["burn_fast"] == 4.0


# ----------------------------------------------------------- drop counters
def test_tracer_drop_counters_zero_on_normal_run(tmp_path):
    eng, _ = _run_windowed_engine(750.0)
    assert eng.metrics.value("obs.spans_dropped") == 0.0
    assert eng.metrics.value("obs.ticks_dropped") == 0.0
    p = tmp_path / "m.jsonl"
    write_metrics_jsonl(str(p), eng.metrics)
    assert_zero(str(p), "obs.spans_dropped")       # the CI smoke assertion
    assert_zero(str(p), "obs.ticks_dropped")


def test_tracer_drop_counter_increments_past_cap():
    obs = Observability(trace=True, max_events=2)
    tr = obs.tracer
    for i in range(5):
        tr.event(0, "queued", float(i))
    assert obs.metrics.value("obs.spans_dropped") == 3.0


def test_dropped_spans_still_reach_flight_ring(tmp_path):
    """The recorder keeps the recent past even after the tracer's own
    buffer filled — its feed runs before the cap check."""
    fr = FlightRecorder(out_dir=str(tmp_path))
    obs = Observability(trace=True, max_events=2, flight=fr)
    for i in range(6):
        obs.tracer.event(0, "queued", float(i))
    assert obs.metrics.value("obs.spans_dropped") == 4.0
    assert len(fr.spans) == 6


# ------------------------------------------------------- attach_measured
def _audit_with(times):
    a = DecisionAudit()
    for t in times:
        a.record(t, "C", {"lam": 1.0},
                 {"units": {"m": 1}, "predicted": {"p99_ms": 100.0,
                                                   "goodput": 0.9}})
    return a


def test_attach_measured_zero_decisions():
    a = DecisionAudit()
    assert a.attach_measured([1.0], [50.0], [True]) == 0


def test_attach_measured_zero_requests():
    a = _audit_with([0.0])
    assert a.attach_measured([], [], []) == 0
    assert a.entries[0].measured is None


def test_attach_measured_single_decision_takes_all_and_warmup():
    a = _audit_with([10.0])
    n = a.attach_measured([1.0, 11.0, 20.0], [50.0, 60.0, 70.0],
                          [True, True, False])
    assert n == 1
    m = a.entries[0].measured
    assert m["n_requests"] == 3                    # warm-up credited too
    assert m["goodput"] == pytest.approx(2 / 3)


def test_attach_measured_out_of_order_decisions_sorted():
    # recorded out of t-order: bucketing sorts by t (documented), so the
    # t=0 entry takes [0, 10) and the t=10 entry takes [10, inf)
    a = _audit_with([10.0, 0.0])
    n = a.attach_measured([1.0, 12.0], [50.0, 60.0], [True, False])
    assert n == 2
    by_t = {e.t: e.measured for e in a.entries}
    assert by_t[0.0]["n_requests"] == 1
    assert by_t[0.0]["p50_ms"] == pytest.approx(50.0)
    assert by_t[10.0]["n_requests"] == 1
    assert by_t[10.0]["p50_ms"] == pytest.approx(60.0)


def test_attach_measured_empty_window_marked_not_counted():
    a = _audit_with([0.0, 10.0])
    n = a.attach_measured([1.0], [50.0], [True])
    assert n == 1
    assert a.entries[1].measured == {"n_requests": 0}


# ------------------------------------------------------------- summarize
def test_export_summarize_metrics_and_audit(tmp_path):
    eng, _ = _run_windowed_engine(750.0)
    mp = tmp_path / "m.jsonl"
    write_metrics_jsonl(str(mp), eng.metrics)
    out = summarize_file(str(mp))
    assert "requests.completed" in out and "p99" in out
    a = _audit_with([0.0, 30.0])
    a.attach_measured([1.0, 31.0], [50.0, 60.0], [True, True])
    ap = tmp_path / "a.jsonl"
    a.to_jsonl(str(ap))
    out = summarize_file(str(ap))
    assert "interval" in out and "m:1" in out
    with pytest.raises(ValueError):
        summarize_file(str(tmp_path / "missing.jsonl")) \
            if (tmp_path / "missing.jsonl").exists() else \
            (_ for _ in ()).throw(ValueError("missing"))


def test_export_cli_assert_zero(tmp_path, capsys):
    from repro.obs.export import main
    eng, _ = _run_windowed_engine(750.0)
    mp = tmp_path / "m.jsonl"
    write_metrics_jsonl(str(mp), eng.metrics)
    assert main(["--validate-metrics", str(mp),
                 "--assert-zero", "obs.spans_dropped",
                 "--assert-zero", "obs.ticks_dropped",
                 "--summarize", str(mp)]) == 0
    assert main(["--validate-metrics", str(mp),
                 "--assert-zero", "requests.completed"]) == 1
    assert main(["--assert-zero", "obs.spans_dropped"]) == 1
