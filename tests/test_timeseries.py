"""Windowed time-series instruments (repro/obs/timeseries.py).

The load-bearing property: a :class:`WindowedHistogram`'s windowed
aggregates (count/sum/min/max/quantiles) after any observe/rotate
sequence must EXACTLY equal a fresh histogram fed only the observations
still inside the window — i.e. O(1) ring eviction is indistinguishable
from a brute-force rebuild.  Randomized sequences drive that invariant;
the rest covers rolling counters, masked EWMA updates, and the
registry's prefix-scoped rotation (two engines sharing one registry
must never cross-rotate).
"""
import numpy as np
import pytest

from repro.obs import MetricsRegistry
from repro.obs.metrics import LogBuckets
from repro.obs.timeseries import (
    EwmaSeries,
    RollingCounter,
    WindowedHistogram,
)


# ---------------------------------------------------------------------------
# WindowedHistogram vs brute-force rebuild
# ---------------------------------------------------------------------------

def _brute_force(window_values, **kw):
    """A fresh histogram fed exactly the in-window observations."""
    ref = WindowedHistogram("ref", **kw)
    for v in window_values:
        ref.observe(v)
    return ref


@pytest.mark.parametrize("seed", range(5))
def test_windowed_quantiles_match_brute_force(seed):
    rng = np.random.default_rng(seed)
    window = int(rng.integers(2, 6))
    wh = WindowedHistogram("lat", window=window)
    ticks = [[]]          # per-tick observation lists (last = open tick)
    for _ in range(60):
        if rng.random() < 0.3:
            wh.rotate()
            ticks.append([])
        else:
            # span several decades so many buckets are exercised
            v = float(10.0 ** rng.uniform(-6, 2))
            wh.observe(v)
            ticks[-1].append(v)
        in_window = [v for tick in ticks[-window:] for v in tick]
        ref = _brute_force(in_window, window=window)
        assert wh.count == ref.count == len(in_window)
        assert wh.total == pytest.approx(ref.total)
        if in_window:
            assert wh.min == min(in_window)
            assert wh.max == max(in_window)
            for q in (0.0, 0.25, 0.5, 0.95, 0.99, 1.0):
                assert wh.quantile(q) == pytest.approx(ref.quantile(q)), \
                    (q, window, len(in_window))
        else:
            assert wh.quantile(0.5) == 0.0


def test_windowed_eviction_is_o_of_distinct_buckets():
    # the eviction subtracts the oldest tick's SPARSE bucket dict — the
    # aggregate array must return to exactly zero when everything ages out
    wh = WindowedHistogram("lat", window=2)
    for v in (1e-3, 2e-3, 5e-1, 40.0):
        wh.observe(v)
    wh.rotate()   # observations now in the closed tick
    wh.rotate()   # evicted
    assert wh.count == 0 and wh.total == 0.0
    assert wh.lifetime_count == 4 and wh.rotations == 2
    assert wh.p99 == 0.0


def test_windowed_to_dict_schema():
    wh = WindowedHistogram("lat", window=4)
    wh.observe(1e-3)
    wh.rotate()
    d = wh.to_dict()
    assert set(d) == {"unit", "window", "ticks", "count", "sum", "min",
                      "max", "mean", "p50", "p95", "p99",
                      "lifetime_count", "rotations"}
    assert d["window"] == 4 and d["ticks"] == 2
    assert d["count"] == 1 and d["lifetime_count"] == 1


def test_windowed_quantile_clamped_to_observed_range():
    wh = WindowedHistogram("lat", window=3)
    wh.observe(3e-3)
    # a single observation: every quantile is that value, not a bucket
    # midpoint outside the observed range
    assert wh.quantile(0.0) == pytest.approx(3e-3)
    assert wh.quantile(1.0) == pytest.approx(3e-3)


def test_windowed_rejects_bad_args():
    with pytest.raises(ValueError):
        WindowedHistogram("x", window=0)
    wh = WindowedHistogram("x", window=2)
    with pytest.raises(ValueError):
        wh.quantile(1.5)


def test_log_buckets_shared_layout():
    # the windowed histogram and the cumulative Histogram share
    # LogBuckets, so their quantile math is identical by construction
    b = LogBuckets(lo=1e-7, hi=1e4, buckets_per_decade=10)
    assert b.index(0.0) == 0                     # underflow
    assert b.index(1e9) == b.n - 1               # overflow
    assert b.edge(1) == pytest.approx(1e-7)


# ---------------------------------------------------------------------------
# RollingCounter / EwmaSeries
# ---------------------------------------------------------------------------

def test_rolling_counter_window_sum():
    rc = RollingCounter("hits", window=3)
    rc.inc(5)
    rc.rotate()        # ticks: [5][open]
    rc.inc(2)
    rc.rotate()        # [5][2][open]
    rc.inc(1)
    assert rc.total == 8 and rc.lifetime_total == 8
    rc.rotate()        # [2][1][open] — the 5 aged out
    assert rc.total == 3
    assert rc.rate == pytest.approx(1.0)   # 3 over 3 ticks
    d = rc.to_dict()
    assert d["total"] == 3 and d["lifetime_total"] == 8


def test_ewma_masked_update():
    ew = EwmaSeries("hit_rate_t", alpha=0.5)
    assert ew.get() is None                  # lazy: no shape yet
    ew.update(np.array([0.8, 0.4]))
    np.testing.assert_allclose(ew.get(), [0.8, 0.4])   # first = direct set
    # masked element 1 keeps its value and gains no evidence
    ew.update(np.array([0.0, 0.9]), mask=np.array([False, True]))
    np.testing.assert_allclose(ew.get(), [0.8, 0.65])
    np.testing.assert_array_equal(ew.updates, [1, 2])
    # a masked-out first update must NOT seed the value
    ew2 = EwmaSeries("x", alpha=0.5)
    ew2.update(np.array([0.3, 0.7]), mask=np.array([True, False]))
    np.testing.assert_allclose(ew2.get(), [0.3, 0.0])
    np.testing.assert_array_equal(ew2.updates, [1, 0])
    ew2.update(np.array([0.0, 0.9]), mask=np.array([False, True]))
    np.testing.assert_allclose(ew2.get(), [0.3, 0.9])  # first real update


# ---------------------------------------------------------------------------
# Registry integration: prefix rotation, config pinning
# ---------------------------------------------------------------------------

def test_registry_prefix_rotation_is_scoped():
    m = MetricsRegistry()
    a = m.windowed_histogram("dlrm.request_latency_s", window=2)
    b = m.windowed_histogram("dlrm_pipelined.request_latency_s", window=2)
    c = m.rolling_counter("dlrm.window.hits", window=2)
    a.observe(1e-3)
    b.observe(1e-3)
    c.inc(1)
    assert m.rotate_windows(prefix="dlrm.") == 2     # a and c, NOT b
    assert a.rotations == 1 and c.rotations == 1
    assert b.rotations == 0
    # EWMA series are time-decayed, never rotated
    m.ewma("dlrm.hit_rate_t").update(np.array([1.0]))
    assert m.rotate_windows(prefix="dlrm.") == 2


def test_registry_pins_window_and_alpha():
    m = MetricsRegistry()
    m.windowed_histogram("lat", window=8)
    assert m.windowed_histogram("lat", window=8).window == 8
    with pytest.raises(ValueError):
        m.windowed_histogram("lat", window=16)
    m.rolling_counter("hits", window=4)
    with pytest.raises(ValueError):
        m.rolling_counter("hits", window=8)
    m.ewma("hr", alpha=0.25)
    with pytest.raises(ValueError):
        m.ewma("hr", alpha=0.5)


def test_registry_snapshot_includes_windowed_sections():
    m = MetricsRegistry()
    m.windowed_histogram("lat", window=2).observe(1e-3)
    m.rolling_counter("hits", window=2).inc(1)
    m.ewma("hr").update(np.array([0.5]))
    snap = m.snapshot()
    assert "lat" in snap["windowed"]
    assert "hits" in snap["rolling"]
    assert snap["ewma"]["hr"]["values"] == [0.5]
