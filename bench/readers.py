"""The arithmetic of the per-layer metrics; each ``bench/metrics/<name>.py``
binds one of these as its ``read(run)``.  A reader that finds nothing to
read returns None, and the run leaves that metric out of its line."""
from __future__ import annotations

import importlib

from bench import stats
from bench.trace import COLLECTIVE


def submit_us(run):
    """Host microseconds per sample inside the engine's ``submit``."""
    w = run.window
    return w.submit_s / w.submitted * 1e6 if w.submitted else None


def query_p99_ms(run):
    """99th percentile (nearest rank) of the window's query latencies in
    milliseconds, every query due in the window counted, an unanswered one
    as infinite: the end-to-end definition, read in the traced run."""
    lat = run.window.latencies_s
    if lat is None or not len(lat):
        return None
    return stats.percentile(list(lat * 1e3), 99)


def hit_rate(run):
    """Share of the window's lookups the tiered store's HBM pool served."""
    if run.cache is None or run.cache[1] == 0:
        return None
    hits, lookups = run.cache[:2]
    return 100.0 * hits / lookups


def prefetch_ms(run):
    """Host milliseconds per micro-batch in the tiered store's prefetch
    (admission, cold fetch) and pool scatter."""
    if run.cache is None or run.cache[3] == 0:
        return None
    return run.cache[2] / run.cache[3] * 1e3


def step_mfu(run):
    """The model's operations per scored sample, times samples scored per
    second of the traced window, over the chips' bf16 peak."""
    w = run.window
    if run.peaks is None or not w.scored:
        return None
    rate = w.scored / w.seconds
    return 100.0 * run.flops_per_sample * rate / (run.chips
                                                  * run.peaks["flops"])


def tbe_roofline(run):
    """The least time of the window's TBE calls (their least bytes over
    HBM bandwidth) over the kernel's device time, summed over chips."""
    if run.trace is None or run.peaks is None or run.tbe_bytes is None:
        return None
    model = importlib.import_module(
        f"bench.models.{run.cell.config['model']}")
    kernel_s = sum(run.trace.matching_s(model.TBE_KERNEL))
    if kernel_s <= 0:
        return None
    return 100.0 * run.tbe_bytes / run.peaks["hbm_bytes_per_s"] / kernel_s


def rw_collective_ms(run):
    """Device milliseconds per micro-batch in collectives, mean over chips."""
    if run.trace is None or run.chips < 2 or not run.window.batches:
        return None
    per_chip = run.trace.matching_s(COLLECTIVE.pattern)
    return sum(per_chip) / run.chips / len(run.window.batches) * 1e3


def device_idle(run):
    """Share of the traced window in which no op ran, mean over chips."""
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_mean_s() / run.trace.window_s)
