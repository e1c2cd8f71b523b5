"""One run of one cell: set-up, the measured window, the check against the
plain reference, and the result line.

Everything a cell is made of is found by name: its entry in
``BENCHMARK.json``, the configuration file that entry names, the traffic
mix ``bench/traffic/<mix>.json``, the limit of its check
``bench/checks/<cell>.json``, the model's adapter
``bench/models/<model>.py`` (with ``<model>_reference.py`` beside it) and
each per-layer metric's reader ``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import shutil
import time
from pathlib import Path
from typing import Optional

import numpy as np

from bench import stats, traffic

BENCH = Path(__file__).resolve().parent
CHECK_SAMPLES = 4096    # scored samples the reference recomputes per run
DRAIN_S = 60.0          # how long past the window an answer may still come


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict              # the configuration file's contents
    mix: dict                 # the traffic file's contents
    check: dict               # the limit of each number compared
    end_to_end: list          # BENCHMARK.json entries this cell reports
    per_layer: list


def load_cell(root: Path, workload: str) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if workload in m.get("workloads", [])
                 or ("workloads" not in m and m["moves"] in moved)]
    return Cell(workload, w["chips"],
                json.loads((root / conf["file"]).read_text()),
                json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                           .read_text()),
                json.loads((BENCH / "checks" / f"{workload}.json")
                           .read_text()),
                e2e, per_layer)


def reader(metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Window:
    """What the measured window did; the per-layer readers read it."""

    mode: str
    seconds: float            # host clock, first submit to last answer
    submitted: int            # samples
    scored: int               # samples
    submit_s: float           # host time inside ``submit`` calls
    batches: list             # rids of each micro-batch, in order
    answers: dict             # rid -> pCTR
    latencies_s: Optional[np.ndarray] = None   # per query (server)
    attempted: int = 0        # samples (offline) or queries (server)
    failed: int = 0
    late_s: float = 0.0       # how late the generator ran, at worst


@dataclasses.dataclass
class Run:
    """A finished window with what the readers need besides."""

    cell: Cell
    window: Window
    chips: int
    peaks: Optional[dict]
    cache: Optional[tuple]    # counters' movement over the window
    trace: object             # bench.trace.Summary, or None
    tbe_bytes: Optional[float]   # least bytes of every TBE call, all chips
    flops_per_sample: int


def no_annotation(name: str):
    return contextlib.nullcontext()


def _annotate(on: bool):
    if not on:
        return no_annotation
    import jax

    return jax.profiler.TraceAnnotation


class _Compiles:
    """Counts backend compilations, so that the window can show none."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------

def submit(system, pool, first: int, n: int) -> None:
    """Submit requests ``first .. first + n - 1``: request ``r`` carries the
    pool's sample ``r mod len(pool)``."""
    eng, req = system.engine, system.request
    size = len(pool)
    for r in range(first, first + n):
        s = r % size
        eng.submit(req(r, pool.dense[s], pool.ids[s], pool.lengths))


def offline(system, pool, mix: dict, seconds: float, start: int,
            annotate) -> Window:
    """Queue ``batches_per_call`` micro-batches, drain them, and again,
    until ``seconds`` have passed; every call runs to its end."""
    per_call = mix["batches_per_call"] * system.cfg["batch_size"]
    B = system.cfg["batch_size"]
    answers, batches = {}, []
    submit_s, rid = 0.0, start
    t0 = time.perf_counter()
    with annotate("bench.window"):
        while True:
            a = time.perf_counter()
            with annotate("bench.submit"):
                submit(system, pool, rid, per_call)
            submit_s += time.perf_counter() - a
            with annotate("bench.drain"):
                answers.update(system.drain())
            batches.extend(range(r, min(r + B, rid + per_call))
                           for r in range(rid, rid + per_call, B))
            rid += per_call
            if time.perf_counter() - t0 >= seconds:
                break
    elapsed = time.perf_counter() - t0
    submitted = rid - start
    return Window("offline", elapsed, submitted, len(answers), submit_s,
                  batches, answers, attempted=submitted,
                  failed=submitted - len(answers))


def server(system, pool, mix: dict, seconds: float, start: int,
           annotate) -> Window:
    """Open loop: submit each query at its due time; flush a micro-batch
    when ``batch_size`` samples wait or the oldest has waited
    ``max_wait_ms``; a query is answered when its last sample's score is
    back on the host."""
    due, sizes = traffic.query_schedule(mix, seconds)
    B, wait = system.cfg["batch_size"], mix["max_wait_ms"] / 1e3
    first = start + np.concatenate([[0], np.cumsum(sizes)[:-1]])
    left = sizes.copy()
    done = np.full(len(due), np.inf)
    eng = system.engine
    answers, batches = {}, []
    submit_s, late, q = 0.0, 0.0, 0
    t0 = time.perf_counter()
    with annotate("bench.window"):
        while True:
            now = time.perf_counter() - t0
            if q < len(due) and due[q] <= now:
                with annotate("bench.submit"):
                    while q < len(due) and due[q] <= now:
                        late = max(late, now - due[q])
                        submit(system, pool, int(first[q]), int(sizes[q]))
                        q += 1
                submit_s += time.perf_counter() - t0 - now
                continue
            head = (due[np.searchsorted(first, eng.queue[0].rid, "right") - 1]
                    if eng.queue else math.inf)
            if len(eng.queue) >= B or now >= head + wait:
                with annotate("bench.flush"):
                    got = eng.flush()
                t = time.perf_counter() - t0
                rids = np.fromiter(got, np.int64, len(got))
                owners = np.searchsorted(first, rids, "right") - 1
                np.subtract.at(left, owners, 1)
                fin = np.unique(owners)
                fin = fin[left[fin] == 0]
                done[fin] = np.minimum(done[fin], t)
                answers.update(got)
                batches.append(rids)
                continue
            if q >= len(due) and not eng.queue:
                break
            if now > seconds + DRAIN_S:
                break
            nxt = min(due[q] if q < len(due) else math.inf, head + wait)
            time.sleep(max(0.0, nxt - (time.perf_counter() - t0)))
    elapsed = time.perf_counter() - t0
    submitted = int(sizes[:q].sum())
    lat = done - due
    return Window("server", elapsed, submitted, len(answers), submit_s,
                  batches, answers, latencies_s=lat, attempted=len(due),
                  failed=int(np.sum(~np.isfinite(lat))), late_s=late)


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def execute(cell: Cell, seed: int, seconds: float, trace_dir: Optional[Path],
            t0: float, devices, peaks: Optional[dict] = None):
    """Set up, measure, check.  Returns ``(result, compared, info)``: the
    result line's object, the numbers compared, each with its limit, and
    facts about the window for standard error."""
    import jax

    from bench import trace as trace_mod

    cfg, mix = cell.config, cell.mix
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    compiles = _Compiles()
    model = importlib.import_module(f"bench.models.{cfg['model']}")
    reference = importlib.import_module(
        f"bench.models.{cfg['model']}_reference")
    B = cfg["batch_size"]
    log = (traffic.log_counts(mix, cfg["rows_per_table"])
           if "cache_log_lookups" in mix and cfg["cache"] else None)
    system = model.System(seed, cfg, devices, log)
    pool = traffic.samples(seed, cfg, mix, mix["distinct_samples"])

    # warm-up: the window's shapes compile and the cache fills
    warm = mix["warmup_batches"] * B
    step = mix.get("batches_per_call", 1) * B
    for first in range(0, warm, step):
        submit(system, pool, first, min(step, warm - first))
        system.drain()
    before = system.cache_counters()
    compiled = compiles.n
    setup_s = time.perf_counter() - t0

    annotate = _annotate(trace_dir is not None)
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    if mix["mode"] == "offline":
        win = offline(system, pool, mix, seconds, warm, annotate)
    elif mix["mode"] == "server":
        win = server(system, pool, mix, seconds, warm, annotate)
    else:
        raise ValueError(f"unknown traffic mode {mix['mode']!r}")
    if trace_dir is not None:
        jax.profiler.stop_trace()
    in_window = compiles.n - compiled
    after = system.cache_counters()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    system.close()
    gc.collect()

    summary = tbe_bytes = None
    if trace_dir is not None:
        summary = trace_mod.summarize(trace_mod.load(str(trace_dir)),
                                      len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        tbe_bytes = _tbe_bytes(win, pool, cfg, len(devices), reference)
    cache = (None if before is None
             else tuple(a - b for a, b in zip(after, before)))
    run = Run(cell, win, len(devices), peaks, cache, summary, tbe_bytes,
              reference.flops_per_sample(cfg))

    gap = _check(win, pool, cfg, seed, system.mlp, reference)
    limit = cell.check["max_abs_dpctr"]
    compared = {"max_abs_dpctr": {"value": gap, "limit": limit},
                "unscored": {"value": win.failed, "limit": 0}}
    correct = bool(gap <= limit and win.failed == 0)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    if summary is None:
        metrics = _end_to_end(cell, win, setup_s)
    else:
        device.update(busy_s=summary.busy_mean_s(), window_s=summary.window_s)
        metrics = {}
        for m in cell.per_layer:
            value = reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": correct, "attempted": win.attempted,
              "failed": win.failed, "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["compared"] = compared
    info = {"setup_s": setup_s, "window_s": win.seconds,
            "samples_scored": win.scored, "micro_batches": len(win.batches),
            "compiles_in_window": in_window,
            "generator_late_max_ms": win.late_s * 1e3}
    return result, compared, info


def _end_to_end(cell: Cell, win: Window, setup_s: float) -> dict:
    values = {"setup_s": setup_s}
    if win.mode == "offline":
        values["samples_per_s"] = win.scored / win.seconds
    else:
        ms = list(win.latencies_s * 1e3)
        values["query_p50_ms"] = stats.percentile(ms, 50)
        values["query_p99_ms"] = stats.percentile(ms, 99)
    out = {}
    for m in cell.end_to_end:
        if m["name"] not in values:
            raise KeyError(f"{cell.name}: no end-to-end metric {m['name']!r}"
                           f" for {win.mode} traffic")
        out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def _check(win: Window, pool, cfg: dict, seed: int, mlp, reference) -> float:
    """The widest gap between a served pCTR and the reference's, over a
    sample of the window's answers drawn from the seed."""
    rids = np.fromiter(win.answers, np.int64, len(win.answers))
    if rids.size == 0:
        return math.inf
    rng = np.random.default_rng(seed)
    pick = rng.choice(rids.size, min(CHECK_SAMPLES, rids.size), replace=False)
    rids = np.sort(rids[pick])
    served = np.array([win.answers[r] for r in rids], np.float64)
    s = rids % len(pool)
    lengths = np.broadcast_to(pool.lengths, (s.size, pool.lengths.size))
    ref = reference.reference_scores(seed, cfg, mlp, pool.dense[s],
                                     pool.ids[s], np.ascontiguousarray(lengths))
    gap = np.abs(served - ref.astype(np.float64))
    return float(np.max(gap)) if np.all(np.isfinite(gap)) else math.inf


def _tbe_bytes(win: Window, pool, cfg: dict, chips: int, reference) -> float:
    """Least bytes of every TBE call in the window, summed over chips: each
    chip reads the distinct rows it holds of its micro-batch once, and
    every live slot's id and weight, and writes the pooled output.  Table
    ``t``'s rows follow table ``t - 1``'s, and row-wise each chip holds
    ``ceil(R_t / chips)`` consecutive rows of every table."""
    T, D = cfg["num_sparse_features"], cfg["embedding_dim"]
    R = traffic.per_table(cfg, "rows_per_table")
    offsets = np.cumsum(R) - R
    share = -(-R // chips)
    live = np.arange(pool.ids.shape[2]) < pool.lengths[:, None]    # (T, L)
    slots = int(pool.lengths.sum())
    total = 0.0
    for rids in win.batches:
        rids = np.asarray(list(rids), np.int64)
        flat = pool.ids[rids % len(pool)] + offsets[:, None]
        distinct = np.unique(flat[:, live])
        t = np.searchsorted(offsets, distinct, "right") - 1
        per_chip = np.bincount((distinct - offsets[t]) // share[t],
                               minlength=chips)
        for n in per_chip:
            total += reference.tbe_least_bytes(int(n), slots * len(rids),
                                               len(rids), T, D)
    return total
