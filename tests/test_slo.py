"""SLO monitoring and drift detection.

Unit tests drive :class:`SLOMonitor` / :class:`DriftDetector` over
hand-fed windowed instruments (breach-event schema, evidence gating,
transition-only drift firing); engine-integration tests attach both to a
real serving run and check the slo tracer lane; the flash-crowd tests
serve a priced plan under stationary and rotated traffic and hold the
detector to both.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.cache import CacheConfig
from repro.configs import dlrm as dlrm_cfg
from repro.core.perf_model import H100_DGX
from repro.core.sharding_plan import TableSpec, plan
from repro.data.synthetic import dlrm_drift_batches
from repro.models import dlrm as dlrm_mod
from repro.obs import (
    LANES,
    DriftDetector,
    SLOMonitor,
    SLOPolicy,
    Telemetry,
    expected_hit_rates,
)
from repro.obs.slo import SLO_EVENT_SCHEMA_VERSION, SLOEvent
from repro.serving.engine import CTRRequest, make_dlrm_engine


# ---------------------------------------------------------------------------
# SLOMonitor: breach events, schema, evidence gating
# ---------------------------------------------------------------------------

def _feed_window(tel, engine="dlrm", *, latencies=(), hits=0, lookups=0,
                 depth=None):
    m = tel.metrics
    w = tel.window
    for v in latencies:
        m.windowed_histogram(f"{engine}.request_latency_s", unit="s",
                             window=w).observe(v)
    if lookups:
        m.rolling_counter(f"{engine}.window.hits", window=w).inc(hits)
        m.rolling_counter(f"{engine}.window.lookups", window=w).inc(lookups)
    if depth is not None:
        m.windowed_histogram(f"{engine}.queue_depth", unit="1", window=w,
                             lo=0.5, hi=1e7,
                             buckets_per_decade=5).observe(depth)


def test_monitor_emits_structured_breach_events():
    tel = Telemetry(window=4)
    pol = SLOPolicy(name="tight", p99_budget_s=1e-3, hit_rate_floor=0.9,
                    queue_depth_cap=10)
    mon = SLOMonitor(tel, pol)
    _feed_window(tel, latencies=[5e-3, 6e-3], hits=5, lookups=10, depth=64)
    tel.batch_tick("dlrm")
    assert mon.windows_evaluated == 1
    assert mon.breaches == 3
    assert mon.summary()["breaches_by_rule"] == \
        {"p99": 1, "hit_rate": 1, "queue_depth": 1}
    ev = mon.events[0]
    d = ev.to_dict()
    assert d["schema_version"] == SLO_EVENT_SCHEMA_VERSION
    assert set(d) == {"schema_version", "kind", "rule", "tick", "engine",
                      "measured", "threshold"}
    assert d["kind"] == "breach" and d["tick"] == 1
    # every breach mirrored onto the dedicated slo tracer lane
    spans = tel.tracer.spans(lane="slo")
    assert {s.name for s in spans} == \
        {"slo.p99", "slo.hit_rate", "slo.queue_depth"}
    assert all(s.args["schema_version"] == SLO_EVENT_SCHEMA_VERSION
               for s in spans)
    assert "slo" in LANES


def test_monitor_quiet_when_inside_budget():
    tel = Telemetry(window=4)
    mon = SLOMonitor(tel, SLOPolicy(p99_budget_s=1.0, hit_rate_floor=0.2,
                                    queue_depth_cap=100))
    for _ in range(3):
        _feed_window(tel, latencies=[1e-3], hits=9, lookups=10, depth=2)
        tel.batch_tick("dlrm")
    assert mon.windows_evaluated == 3 and mon.breaches == 0
    assert mon.worst_p99_s == pytest.approx(1e-3)
    assert not tel.tracer.spans(lane="slo")


def test_monitor_evidence_gating_skips_thin_windows():
    tel = Telemetry(window=4)
    pol = SLOPolicy(p99_budget_s=1e-6, hit_rate_floor=0.99,
                    min_window_count=5, min_window_lookups=100)
    mon = SLOMonitor(tel, pol)
    # 2 observations < min_window_count, 10 lookups < min_window_lookups:
    # both rules would breach on the values, but the evidence floor skips
    _feed_window(tel, latencies=[1.0, 1.0], hits=0, lookups=10)
    tel.batch_tick("dlrm")
    assert mon.windows_evaluated == 1 and mon.breaches == 0


def test_monitor_stride_and_engine_scoping():
    tel = Telemetry(window=4)
    mon = SLOMonitor(tel, SLOPolicy(p99_budget_s=1e-6), stride=2)
    other = SLOMonitor(tel, SLOPolicy(p99_budget_s=1e-6), engine="other")
    for _ in range(4):
        _feed_window(tel, latencies=[1.0])
        tel.batch_tick("dlrm")
    assert mon.windows_evaluated == 2     # ticks 2 and 4 only
    assert other.windows_evaluated == 0   # different engine, never fires
    with pytest.raises(ValueError):
        SLOMonitor(tel, SLOPolicy(), stride=0)


# ---------------------------------------------------------------------------
# DriftDetector: transition firing, re-arm, plan wiring
# ---------------------------------------------------------------------------

def _feed_hit_rate(tel, rates, engine="dlrm"):
    rates = np.asarray(rates, np.float64)
    tel.metrics.ewma(f"{engine}.hit_rate_t").update(rates,
                                                    mask=rates >= 0)


def test_drift_fires_on_transition_only_and_rearms():
    tel = Telemetry(window=4)
    det = DriftDetector(tel, [0.9, 0.9], threshold=0.2, min_updates=2)
    alpha = tel.metrics.ewma("dlrm.hit_rate_t").alpha
    assert alpha == 0.25
    # converge near the expectation first (also satisfies min_updates)
    for _ in range(3):
        _feed_hit_rate(tel, [0.9, 0.9])
        tel.batch_tick("dlrm")
    assert det.events == [] and det.first_detection_tick is None
    # table 0 craters; EWMA needs a couple of updates to cross 0.2 dev
    ticks_to_fire = 0
    while not det.events:
        _feed_hit_rate(tel, [0.0, 0.9])
        ticks_to_fire += 1
        assert ticks_to_fire < 10, "detector never fired"
        tel.batch_tick("dlrm")
    assert det.first_detection_tick == 3 + ticks_to_fire
    ev = det.events[0]
    assert ev.kind == "drift" and ev.rule == "hit_rate_drift"
    assert ev.table == 0 and ev.expected == pytest.approx(0.9)
    assert ev.to_dict()["expected"] == pytest.approx(0.9)
    # persistently drifted: NO further events for the same table
    for _ in range(3):
        _feed_hit_rate(tel, [0.0, 0.9])
        tel.batch_tick("dlrm")
    assert len(det.events) == 1
    assert tel.tracer.spans(lane="slo", name="slo.hit_rate_drift")
    # recovery re-arms: drifting again fires a SECOND event
    while 0 in det.drifted:
        _feed_hit_rate(tel, [0.9, 0.9])
        tel.batch_tick("dlrm")
    for _ in range(10):
        _feed_hit_rate(tel, [0.0, 0.9])
        tel.batch_tick("dlrm")
        if len(det.events) == 2:
            break
    assert len(det.events) == 2
    assert det.summary()["tables_drifted"] == [0, 0]


def test_drift_requires_min_updates_of_evidence():
    tel = Telemetry(window=4)
    det = DriftDetector(tel, [0.9], threshold=0.1, min_updates=3)
    for k in range(1, 5):
        _feed_hit_rate(tel, [0.0])
        tel.batch_tick("dlrm")
        if k < 3:
            assert not det.events, f"fired with only {k} updates"
    assert det.events and det.first_detection_tick == 3


def test_drift_shape_mismatch_raises():
    tel = Telemetry(window=4)
    DriftDetector(tel, [0.9, 0.9, 0.9])
    _feed_hit_rate(tel, [0.5, 0.5])       # 2 tables measured, 3 expected
    with pytest.raises(ValueError, match="shape"):
        tel.batch_tick("dlrm")


def test_expected_hit_rates_from_plan():
    specs = [TableSpec(f"t{i}", rows=2048, dim=16, pooling=8)
             for i in range(6)]
    p = plan(specs, num_shards=2, batch_per_shard=8,
             hbm_budget_bytes=48_000, hw=H100_DGX, zipf_a=0.9)
    exp = expected_hit_rates(p, len(specs))
    assert exp.shape == (6,)
    for pl in p.placements:
        if pl.strategy == "cached" and pl.cache_rows > 0:
            assert exp[pl.index] == pytest.approx(pl.est_hit_rate)
            assert 0.0 < exp[pl.index] < 1.0


# ---------------------------------------------------------------------------
# Engine integration: live windowed instruments feed monitor + detector
# ---------------------------------------------------------------------------

def _smoke_cfg(depth=1):
    return dataclasses.replace(
        dlrm_cfg.smoke(), kernel_mode="reference",
        cache=CacheConfig(rows=32, pipeline_depth=depth))


def _zipf_requests(cfg, n, rng, rid0=0):
    T, L, F = (cfg.num_sparse_features, cfg.pooling,
               cfg.num_dense_features)
    R = cfg.rows_per_table
    return [CTRRequest(
        rid=rid, dense=rng.standard_normal(F).astype(np.float32),
        indices=np.minimum(rng.zipf(1.2, size=(T, L)) - 1,
                           R - 1).astype(np.int32),
        lengths=np.full(T, L, np.int32))
        for rid in range(rid0, rid0 + n)]


@pytest.mark.parametrize("depth", [1, 2])
def test_engine_feeds_monitor_and_detector(depth):
    cfg = _smoke_cfg(depth)
    params = dlrm_mod.init_params(jax.random.key(0), cfg)
    tel = Telemetry(window=4)
    eng = make_dlrm_engine(params, cfg, batch_size=4, telemetry=tel)
    # impossible latency budget -> every evaluated window breaches, and
    # a huge drift threshold -> the detector sees updates but stays quiet
    mon = SLOMonitor(tel, SLOPolicy(p99_budget_s=1e-12),
                     engine=eng.obs_name)
    det = DriftDetector(tel, np.ones(cfg.num_sparse_features),
                        engine=eng.obs_name, threshold=2.0)
    rng = np.random.default_rng(1)
    for r in _zipf_requests(cfg, 12, rng):
        eng.submit(r)
    eng.run_to_completion()
    n_flushes = 3                         # ceil(12 / 4)
    assert tel.ticks(eng.obs_name) == n_flushes
    assert mon.windows_evaluated == n_flushes
    assert mon.summary()["breaches_by_rule"]["p99"] == n_flushes
    assert not det.events
    ew = tel.metrics.ewma(f"{eng.obs_name}.hit_rate_t")
    assert ew.get() is not None and int(ew.updates.max()) >= 1
    # the windowed hit-rate feed matches the cumulative cache counters
    m = tel.metrics
    hits = m.rolling_counter(f"{eng.obs_name}.window.hits",
                             window=tel.window)
    lookups = m.rolling_counter(f"{eng.obs_name}.window.lookups",
                                window=tel.window)
    assert hits.lifetime_total == eng.cache_stats().hits
    assert lookups.lifetime_total == eng.cache_stats().lookups
    # queue-wait + service windowed splits observed per request
    lat = m.windowed_histogram(f"{eng.obs_name}.request_latency_s",
                               unit="s", window=tel.window)
    assert lat.lifetime_count == 12


def test_pipelined_engine_records_stage_windows():
    cfg = _smoke_cfg(depth=2)
    params = dlrm_mod.init_params(jax.random.key(3), cfg)
    tel = Telemetry(window=4)
    piped = make_dlrm_engine(params, cfg, batch_size=4, telemetry=tel)
    rng = np.random.default_rng(4)
    for r in _zipf_requests(cfg, 8, rng):
        piped.submit(r)
    piped.run_to_completion()
    snap = tel.metrics.snapshot()
    for stage in ("admit", "fetch", "scatter", "forward", "swap"):
        name = f"{piped.obs_name}.stage.{stage}_s"
        assert name in snap["windowed"], sorted(snap["windowed"])
        assert snap["windowed"][name]["lifetime_count"] == 2  # 2 batches


# ---------------------------------------------------------------------------
# Flash crowd: a priced plan served under stationary vs rotated traffic
# ---------------------------------------------------------------------------

# six same-spec tables under a tight budget: every table cached, in
# pools of more than one size; the hot set jumps at batch ROTATE_AT
CROWD = dict(tables=6, rows=2048, dim=16, pooling=8, batch=8,
             budget=48_000, window=4, batches=24, zipf_a=0.9)
ROTATE_AT = 12
DETECT_BOUND = 8          # micro-batches from the rotation to the event


def _serve_crowd(rotate_every):
    """Serve CROWD's traffic from an engine warmed on the popularity the
    planner priced; returns (monitor, detector) after every batch."""
    c = CROWD
    T, R, L, B = c["tables"], c["rows"], c["pooling"], c["batch"]
    p = plan([TableSpec(f"t{i}", rows=R, dim=c["dim"], pooling=L)
              for i in range(T)],
             num_shards=2, batch_per_shard=B,
             hbm_budget_bytes=c["budget"], hw=H100_DGX, zipf_a=c["zipf_a"])
    assert [pl.strategy for pl in p.placements] == ["cached"] * T
    expected = expected_hit_rates(p, T)
    base = dataclasses.replace(
        dlrm_cfg.smoke(), num_sparse_features=T, rows_per_table=R,
        embedding_dim=c["dim"], pooling=L, bottom_mlp=(32, c["dim"]),
        kernel_mode="reference")
    freqs = (np.arange(1, R + 1, dtype=np.float64) ** -c["zipf_a"]) * 1e7
    cfg = dataclasses.replace(
        base, sharding_plan=p,
        cache=dataclasses.replace(base.cache, warmup_freqs=freqs))
    tel = Telemetry(window=c["window"])
    eng = make_dlrm_engine(dlrm_mod.init_params(jax.random.key(0), base),
                           cfg, batch_size=B, telemetry=tel)
    # the floor sits below the stationary windowed hit rate and above
    # the post-rotation crater
    floor = max(0.05, float(expected.mean()) - 0.15)
    mon = SLOMonitor(tel, SLOPolicy(name="serving", hit_rate_floor=floor,
                                    min_window_lookups=1),
                     engine=eng.obs_name)
    det = DriftDetector(tel, expected, engine=eng.obs_name,
                        threshold=0.15, min_updates=3)
    gen = dlrm_drift_batches(cfg, B, seed=3, zipf_a=c["zipf_a"],
                             rotate_every=rotate_every)
    rid = 0
    for _ in range(c["batches"]):
        d = next(gen)
        idx = np.asarray(d["batch"].indices)
        lens = np.asarray(d["batch"].lengths)
        for i in range(B):
            eng.submit(CTRRequest(
                rid=rid, dense=d["dense"][i],
                indices=idx[:, i, :].astype(np.int32),
                lengths=lens[:, i].astype(np.int32)))
            rid += 1
        eng.run_to_completion()
    return mon, det


@pytest.fixture(scope="module")
def flash_crowd():
    return _serve_crowd(rotate_every=ROTATE_AT)


def test_stationary_traffic_raises_no_drift_and_no_breach():
    """Traffic that matches the plan is the null hypothesis: every
    window is judged and nothing fires."""
    mon, det = _serve_crowd(rotate_every=0)
    assert mon.windows_evaluated == CROWD["batches"]
    assert det.summary()["events"] == 0, det.summary()
    assert mon.breaches == 0, mon.summary()


def test_flash_crowd_trips_drift_after_the_rotation_within_bound(
        flash_crowd):
    _, det = flash_crowd
    first = det.first_detection_tick
    assert first is not None, "drift never fired on the rotated hot set"
    # ticks are 1-based, so batch ROTATE_AT (0-based) is tick ROTATE_AT+1
    assert 0 < first - ROTATE_AT <= DETECT_BOUND, first


def test_flash_crowd_breaches_the_hit_rate_floor(flash_crowd):
    mon, _ = flash_crowd
    assert mon.summary()["breaches_by_rule"].get("hit_rate", 0) > 0, \
        mon.summary()
