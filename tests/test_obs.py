"""Unified telemetry subsystem (repro/obs/): metrics, tracer, exporters.

Engine-integration tests run the real serving paths (host cold tier —
single device, CPU-tractable smoke shapes); both engines on one
telemetry over a remote cold tier run tests/_obs_checks.py in a
subprocess with a forced 2-device CPU backend, and the multi-rank
runtime ``fetch_rows`` timestamp check of the bare cache lives in
tests/_tiering_checks.py."""
import dataclasses
import json
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.cache import CacheConfig
from repro.configs import dlrm as dlrm_cfg
from repro.core import comm
from repro.models import dlrm as dlrm_mod
from repro.obs import (
    LANES,
    Histogram,
    MetricsRegistry,
    SweepReport,
    Telemetry,
    Tracer,
    validate_chrome_trace,
)
from repro.pipeline import PipelineTrace
from repro.serving.engine import CTRRequest, make_dlrm_engine


# ---------------------------------------------------------------------------
# Histograms + registry
# ---------------------------------------------------------------------------

def test_histogram_quantiles_and_bounds():
    h = Histogram("lat", buckets_per_decade=20)
    vals = [1e-4 * (1.1 ** i) for i in range(100)]    # 100 us .. ~1.25 s
    for v in vals:
        h.observe(v)
    assert h.count == 100
    assert h.min == pytest.approx(vals[0]) and h.max == pytest.approx(
        vals[-1])
    assert h.mean == pytest.approx(sum(vals) / len(vals))
    exact = np.quantile(vals, [0.5, 0.95, 0.99])
    # log-bucketed: each quantile within ~one bucket's relative width
    for got, want in zip((h.p50, h.p95, h.p99), exact):
        assert abs(got - want) / want < 0.15
    # quantiles never leave the observed range
    assert h.min <= h.quantile(0.0) <= h.quantile(1.0) <= h.max


def test_histogram_rejects_bad_values_and_empty_readout():
    h = Histogram("x")
    assert h.quantile(0.5) == 0.0 and h.mean == 0.0
    for bad in (-1.0, float("nan"), float("inf")):
        with pytest.raises(ValueError):
            h.observe(bad)
    with pytest.raises(ValueError):
        h.quantile(1.5)
    h.observe(0.0)                       # zero is a legal latency
    assert h.count == 1 and h.p50 == 0.0


def test_metrics_registry_snapshot_schema():
    m = MetricsRegistry()
    m.counter("bytes", unit="B").inc(128)
    m.gauge("depth").set(3)
    m.histogram("lat", unit="s").observe(0.01)
    m.register_producer("cache", lambda: {"hits": 7})
    snap = m.snapshot()
    assert snap["schema_version"] == MetricsRegistry.SCHEMA_VERSION == 2
    assert set(snap) == {"schema_version", "counters", "gauges",
                         "histograms", "windowed", "rolling", "ewma",
                         "producers"}
    assert snap["counters"]["bytes"] == {"unit": "B", "value": 128}
    assert snap["producers"]["cache"] == {"hits": 7}
    assert snap["histograms"]["lat"]["count"] == 1
    # v2 pins the histogram payload: sum/min/max make mean + extremes
    # recoverable from a snapshot alone
    assert set(snap["histograms"]["lat"]) == {
        "unit", "count", "sum", "min", "max", "mean", "p50", "p95", "p99"}
    assert snap["histograms"]["lat"]["sum"] == pytest.approx(0.01)
    assert snap["histograms"]["lat"]["min"] == pytest.approx(0.01)
    assert snap["histograms"]["lat"]["max"] == pytest.approx(0.01)
    json.dumps(snap)                     # snapshot must be JSON-clean
    # get-or-create is idempotent; a unit mismatch is a bug, not a merge
    assert m.counter("bytes", unit="B").value == 128
    with pytest.raises(ValueError, match="unit"):
        m.counter("bytes", unit="1")
    with pytest.raises(ValueError, match="cannot decrease"):
        m.counter("bytes", unit="B").inc(-1)
    # duplicate producers raise unless explicitly replaced
    with pytest.raises(ValueError, match="already registered"):
        m.register_producer("cache", dict)
    m.register_producer("cache", lambda: {"hits": 9}, replace=True)
    assert m.snapshot()["producers"]["cache"] == {"hits": 9}


# ---------------------------------------------------------------------------
# Tracer: golden Chrome schema, lanes, comm events
# ---------------------------------------------------------------------------

def test_tracer_golden_chrome_schema(tmp_path):
    tr = Tracer()
    t = tr.now()
    for lane in LANES:
        tr.add_span(f"{lane}.work", t, t + 1e-3, lane=lane, cat=lane,
                    args={"k": 1})
    path = tr.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        obj = json.load(f)               # must load with plain json.load
    n = validate_chrome_trace(obj)
    assert n == len(LANES) * 2           # one metadata + one X per lane
    for e in obj["traceEvents"]:
        assert e["ph"] in ("X", "M")
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["ts"] >= 0 and e["dur"] >= 0
    meta = [e for e in obj["traceEvents"] if e["ph"] == "M"]
    assert {e["args"]["name"] for e in meta} == set(LANES)
    xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert {e["tid"] for e in xs} == set(LANES.values())
    assert all(e["dur"] == pytest.approx(1e3, rel=1e-6) for e in xs)
    assert obj["displayTimeUnit"] == "ms"


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace([])
    bad = {"traceEvents": [{"ph": "X", "ts": 0, "dur": 0, "pid": 0,
                            "tid": 0}]}          # no name
    with pytest.raises(ValueError, match="name"):
        validate_chrome_trace(bad)
    bad = {"traceEvents": [{"ph": "B", "ts": 0, "dur": 0, "pid": 0,
                            "tid": 0, "name": "x"}]}
    with pytest.raises(ValueError, match="phase"):
        validate_chrome_trace(bad)
    bad = {"traceEvents": [{"ph": "X", "ts": -1, "dur": 0, "pid": 0,
                            "tid": 0, "name": "x"}]}
    with pytest.raises(ValueError, match="ts"):
        validate_chrome_trace(bad)


def test_tracer_lane_validation_and_disable():
    tr = Tracer()
    with pytest.raises(ValueError, match="lane"):
        tr.add_span("x", 0.0, 1.0, lane="nope")
    off = Tracer(enabled=False)
    off.add_span("x", 0.0, 1.0)
    with off.span("y"):
        pass
    assert off.event_count == 0


def test_collective_event_positional_backcompat():
    # pre-obs call sites construct with four positional fields; the
    # wall-clock stamps default to 0.0/0.0 (= untimed)
    ev = comm.CollectiveEvent("all_gather", 1024, 4, "bulk")
    assert (ev.t0, ev.t1) == (0.0, 0.0)
    tr = Tracer()
    assert not tr.add_collective_event(ev)       # untimed: skipped
    timed = comm.CollectiveEvent("fetch_rows", 1024, 4, "bulk", 1.0, 1.5)
    assert tr.add_collective_event(timed)
    (s,) = tr.spans(lane="comm")
    assert s.name == "fetch_rows" and s.seconds == pytest.approx(0.5)
    assert s.args == {"bytes": 1024, "axis_size": 4, "backend": "bulk"}


def test_comm_sink_reaches_background_threads():
    """comm.instrument() is thread-local; the obs sink is process-wide,
    so runtime events recorded on the pipeline's background prefetch
    thread land on the main tracer's timeline."""
    tr = Tracer()
    tr.install_comm_sink()
    try:
        th = threading.Thread(target=lambda: comm.record_runtime(
            "fetch_rows", 4096, 4, "bulk", 1.0, 1.25))
        th.start()
        th.join()
    finally:
        tr.remove_comm_sink()
    (s,) = tr.spans(lane="comm", name="fetch_rows")
    assert s.seconds == pytest.approx(0.25)
    # removed: later events no longer land
    comm.record_runtime("fetch_rows", 1, 2, "bulk", 0.0, 1.0)
    assert tr.event_count == 1


def test_comm_sink_and_instrument_log_coexist():
    tr = Tracer()
    tr.install_comm_sink()
    try:
        with comm.instrument() as ev:
            comm.record_runtime("fetch_rows", 64, 2, "bulk", 2.0, 2.5)
        assert len(ev) == 1 and ev[0].bytes_in == 64
    finally:
        tr.remove_comm_sink()
    assert len(tr.spans(lane="comm")) == 1


def test_install_comm_sink_restores_previous():
    seen = []
    prev = comm.set_event_sink(seen.append)
    tr = Tracer()
    tr.install_comm_sink()
    tr.install_comm_sink()               # idempotent
    tr.remove_comm_sink()
    comm.record_runtime("fetch_rows", 1, 2, "bulk", 0.0, 1.0)
    assert len(seen) == 1                # the previous sink is back
    comm.set_event_sink(prev)


# ---------------------------------------------------------------------------
# PipelineTrace: overlap under out-of-order records + tracer mirroring
# ---------------------------------------------------------------------------

def test_overlap_s_out_of_order_and_interleaved():
    """overlap_s must be record-order independent: the scheduler logs a
    batch's admit/fetch spans AFTER the forward they overlapped with
    (spans are recorded on the main thread at join), and interleaved
    batches produce non-monotone span lists."""
    tr = PipelineTrace()
    # forwards: [10, 20] and [30, 40]; prefetch spans recorded later,
    # out of chronological order, each straddling forward boundaries
    tr.record("fetch", 2, 38.0, 44.0)      # 2s inside forward #2
    tr.record("forward", 1, 10.0, 20.0)
    tr.record("admit", 1, 5.0, 12.0)       # 2s inside forward #1
    tr.record("forward", 2, 30.0, 40.0)
    tr.record("fetch", 1, 19.0, 31.0)      # 1s in #1 + 1s in #2
    tr.record("scatter", 1, 15.0, 18.0)    # scatter never counts
    assert tr.overlap_s() == pytest.approx(2.0 + 2.0 + 2.0)
    pre = tr.total("admit") + tr.total("fetch")
    assert tr.overlap_fraction() == pytest.approx(6.0 / pre)


def test_pipeline_trace_mirrors_to_tracer():
    tracer = Tracer()
    tr = PipelineTrace(tracer=tracer, label="eng-a")
    with tr.stage("fetch", 7, "repro.cache.fetch") as s_fetch:
        pass
    with pytest.raises(ValueError, match="unknown stage"):
        tr.record("nope", 0, 0.0, 1.0)
    with pytest.raises(ValueError, match="unknown stage"):
        tr.stage("nope", 0, "repro.nope")
    # the span call landed the lane span and kept its own stamps
    (s,) = tracer.spans(lane="pipeline")
    assert s.name == "pipeline.fetch"
    assert s.args == {"engine": "eng-a", "batch": 7}
    (st,) = tr.spans
    assert (s.t0, s.t1) == (st.start, st.end) == (s_fetch.t0, s_fetch.t1)
    # a worker thread's span keeps nothing; its owner records the stamps
    with tr.stage("admit", 8, "repro.cache.admit", keep=False) as s_admit:
        pass
    assert len(tr.spans) == 1 and len(tracer.spans(lane="pipeline")) == 2
    tr.record("admit", 8, s_admit.t0, s_admit.t1)
    # a body that raises lands its lane span but keeps no stage stamps
    with pytest.raises(RuntimeError):
        with tr.stage("swap", 8, "repro.pipeline.swap"):
            raise RuntimeError("boom")
    assert [x.stage for x in tr.spans] == ["fetch", "admit"]
    # an interval built from other stamps lands through derived()
    tr.derived("forward", 7, 2.0, 3.0)
    fwd = tracer.spans(lane="pipeline", name="pipeline.forward")
    assert [(f.t0, f.t1) for f in fwd] == [(2.0, 3.0)]
    assert len(tracer.spans(lane="pipeline")) == len(tr.spans) + 1 == 4


# ---------------------------------------------------------------------------
# Engine integration: request latency, cache-lane spans
# ---------------------------------------------------------------------------

def _smoke_cfg(depth=1):
    return dataclasses.replace(
        dlrm_cfg.smoke(), kernel_mode="reference",
        cache=CacheConfig(rows=32, pipeline_depth=depth))


def _zipf_requests(cfg, n, rng):
    T, L, F = (cfg.num_sparse_features, cfg.pooling,
               cfg.num_dense_features)
    R = cfg.rows_per_table
    return [CTRRequest(
        rid=rid, dense=rng.standard_normal(F).astype(np.float32),
        indices=np.minimum(rng.zipf(1.2, size=(T, L)) - 1,
                           R - 1).astype(np.int32),
        lengths=np.full(T, L, np.int32)) for rid in range(n)]


def test_serial_engine_records_latency_and_cache_spans():
    cfg = _smoke_cfg()
    params = dlrm_mod.init_params(jax.random.key(0), cfg)
    tel = Telemetry()
    eng = make_dlrm_engine(params, cfg, batch_size=4, telemetry=tel)
    rng = np.random.default_rng(1)
    n = 10
    for r in _zipf_requests(cfg, n, rng):
        eng.submit(r)
    eng.run_to_completion()
    h = tel.request_latency(eng.obs_name)
    assert h.count == n and 0 <= h.p50 <= h.p99 <= h.max
    assert not eng._enqueue_t             # every stamp consumed
    assert len(tel.tracer.spans(lane="request")) == n
    # engine lane: one prefetch + one forward span per flush
    fw = tel.tracer.spans(lane="engine", name="dlrm.forward")
    pf = tel.tracer.spans(lane="engine", name="dlrm.prefetch")
    assert len(fw) == len(pf) == 3        # ceil(10 / 4) flushes
    # cache lane: admit spans plus seq-tagged fetch/scatter pairs
    fetches = tel.tracer.spans(lane="cache", name="cache.fetch")
    assert fetches and all(s.args["tier"] == "host" for s in fetches)
    scatters = tel.tracer.spans(lane="cache", name="cache.scatter")
    assert {s.args["seq"] for s in fetches} == \
        {s.args["seq"] for s in scatters}
    # the producer surfaces live CacheStats in the snapshot
    snap = tel.metrics.snapshot()
    prod = snap["producers"]["dlrm.cache"]
    assert prod["schema_version"] == 4 and prod["lookups"] > 0


def test_request_latency_under_pipelined_requeue_on_failure():
    """A pipeline failure requeues every unscored request; their latency
    stamps must survive so the retry measures from the ORIGINAL submit,
    and rids scored before the failure are recorded exactly once."""
    cfg = _smoke_cfg(depth=2)
    params = dlrm_mod.init_params(jax.random.key(7), cfg)
    tel = Telemetry()
    piped = make_dlrm_engine(params, cfg, batch_size=4, telemetry=tel)
    rng = np.random.default_rng(8)
    n = 12
    for r in _zipf_requests(cfg, n, rng):
        piped.submit(r)
    t_submit = time.perf_counter()
    cold = piped.cache.buffers[0].cold
    real_fetch, calls = cold.fetch, {"n": 0}

    def flaky(t, r, out=None):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("transient cold-tier failure")
        return real_fetch(t, r, out=out)

    cold.fetch = flaky
    try:
        with pytest.raises(RuntimeError, match="transient"):
            piped.run_to_completion()
    finally:
        cold.fetch = real_fetch
    assert len(piped.queue) == n
    got = piped.run_to_completion()
    assert sorted(got) == list(range(n))
    h = tel.request_latency(piped.obs_name)
    assert h.count == n                   # once per request, no doubles
    assert not piped._enqueue_t
    # retried requests measured from the original submit: the recorded
    # spans all start at/before the failure point
    spans = tel.tracer.spans(lane="request")
    assert len(spans) == n
    assert all(s.t0 <= t_submit for s in spans)
    assert h.min >= 0.0


def test_pipelined_engine_mirrors_stage_spans():
    cfg = _smoke_cfg(depth=2)
    params = dlrm_mod.init_params(jax.random.key(3), cfg)
    tel = Telemetry()
    piped = make_dlrm_engine(params, cfg, batch_size=4, telemetry=tel)
    rng = np.random.default_rng(4)
    for r in _zipf_requests(cfg, 8, rng):
        piped.submit(r)
    piped.run_to_completion()
    lane = tel.tracer.spans(lane="pipeline")
    assert {s.name for s in lane} >= {"pipeline.admit", "pipeline.fetch",
                                      "pipeline.scatter",
                                      "pipeline.forward"}
    assert all(s.args["engine"] == "dlrm_pipelined" for s in lane)
    # mirrored 1:1 with the scheduler's own StageSpan list
    assert len(lane) == len(piped.trace.spans)
    assert tel.request_latency("dlrm_pipelined").count == 8
    # both buffers' bags share the timeline
    assert all(b.tracer is tel.tracer for b in piped.cache.buffers)


def test_telemetry_disabled_records_nothing():
    cfg = _smoke_cfg()
    params = dlrm_mod.init_params(jax.random.key(0), cfg)
    tel = Telemetry(enabled=False)
    eng = make_dlrm_engine(params, cfg, batch_size=4, telemetry=tel)
    rng = np.random.default_rng(2)
    for r in _zipf_requests(cfg, 4, rng):
        eng.submit(r)
    eng.run_to_completion()
    assert tel.tracer.event_count == 0
    # histograms still count (cheap, and the quantiles stay available)
    assert tel.request_latency("dlrm").count == 4


# ---------------------------------------------------------------------------
# Both engines, one telemetry, remote cold tier (subprocess, 2 devices)
# ---------------------------------------------------------------------------

def test_both_engines_share_one_merged_trace(tmp_path):
    trace_path, seen_path = tmp_path / "trace.json", tmp_path / "seen.json"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tests", "_obs_checks.py"),
         str(trace_path), str(seen_path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    trace = json.loads(trace_path.read_text())
    seen = json.loads(seen_path.read_text())
    assert validate_chrome_trace(trace) > 0
    engines = {e["args"]["engine"] for e in trace["traceEvents"]
               if e.get("args", {}).get("engine")}
    assert {"dlrm", "dlrm_pipelined"} <= engines, engines
    # each engine's requests land in its own histogram of the one
    # Telemetry, and the remote tier's collectives on its comm lane
    assert seen["latency_counts"] == {"dlrm": 12, "dlrm_pipelined": 12}
    assert seen["timed_fetch_rows"] > 0


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

def test_sweep_report_validates_columns():
    rep = SweepReport("sweep", "x", "y")
    rep.add(sweep="s", x=1, y=2.5)
    rep.comment("context line")
    rep.add(sweep="s", x=3, y="0.125")
    assert len(rep) == 2
    assert rep.csv() == "sweep,x,y\ns,1,2.5\n# context line\ns,3,0.125\n"
    with pytest.raises(ValueError, match="missing"):
        rep.add(sweep="s", x=1)
    with pytest.raises(ValueError, match="unexpected"):
        rep.add(sweep="s", x=1, y=2, z=3)
    with pytest.raises(ValueError, match="duplicate"):
        SweepReport("a", "a")
    with pytest.raises(ValueError, match="at least one"):
        SweepReport()
