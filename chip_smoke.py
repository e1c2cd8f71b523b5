"""Smoke test: DLRM serving on a TPU at ``CONFIG`` widths.

    python chip_smoke.py               # one chip: local, tiered, pipelined
    python chip_smoke.py --four-chips  # four chips: row-wise sharded
                                       # forward, remote cold tier

It drives the serving path through the entry points a user calls
(``DLRMConfig`` -> ``models.dlrm.init_params`` ->
``serving.engine.make_dlrm_engine`` -> ``submit`` /
``run_to_completion``) with tables drawn from ``--seed`` and seeded zipf
traffic, ``kernel_mode="pallas"``, and checks the scores:

  * one chip — ``local`` (the whole tables in HBM) within ``SCORE_TOL``
    of the plain ``jax.numpy`` reference forward; ``tiered`` (a 65,536
    rows/table slot pool over the host cold tier) and ``pipelined``
    (the same at depth 2) bitwise equal to ``local``;
  * four chips — the row-wise sharded forward (``allgather`` / ``a2a``
    x ``bulk`` / ``onesided``) and the tiered engine over a 4-host remote
    cold tier (``bulk`` / ``onesided``), each within ``SCORE_TOL`` of the
    single-chip reference, the two remote transports bitwise equal, and
    every one-sided program holding a collective Pallas kernel (a
    ``tpu_custom_call`` on a barrier semaphore, which the gather kernels
    do not take).

One line of smoke facts per phase (not benchmark numbers), then, last,
``{"ok": true, "device": {...}}``.  Exits non-zero at the first failure
and refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

BATCHES = 4           # micro-batches per phase
BATCH = 1024          # requests per micro-batch
SLOTS = 65536         # slot-pool rows per table (0.87 GB at CONFIG widths)
ZIPF_A = 1.05
# |pCTR(Pallas TBE) - pCTR(jnp reference)|: the kernel adds rows one at a
# time in f32, the reference pools with a HIGHEST-precision einsum, so the
# pooled values differ in their last bits.  The script runs every matmul
# at HIGHEST (f32) precision, so those bits move pCTR by ~1e-6; at the
# chip's default one-pass bf16 they can round an MLP input to the
# neighbouring bf16 value, which moved pCTR by 3.2e-3 on a v5e and would
# hide a wrong row.  A wrong row or weight moves it by far more than this.
SCORE_TOL = 1e-4


class SmokeFailure(AssertionError):
    pass


# ---------------------------------------------------------------------------
# Phase bookkeeping
# ---------------------------------------------------------------------------

_COMPILE_S = [0.0]


def _on_duration(event: str, seconds: float, **_):
    if event.startswith("/jax/core/compile/"):
        _COMPILE_S[0] += seconds


class Phase:
    """Times one phase and prints its smoke facts as one JSON line."""

    def __init__(self, name: str):
        self.name = name
        self.facts = {}

    def __enter__(self):
        self._c0, self._t0 = _COMPILE_S[0], time.perf_counter()
        return self

    def __exit__(self, kind, *_):
        if kind is not None:
            return False
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        print(json.dumps({
            "phase": self.name,
            "compile_s": _COMPILE_S[0] - self._c0,
            "wall_s": time.perf_counter() - self._t0,
            **self.facts,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        }), flush=True)
        return False


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def max_diff(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.max(np.abs(a.astype(np.float64) - b.astype(np.float64))))


# ---------------------------------------------------------------------------
# Traffic, serving and the reference
# ---------------------------------------------------------------------------

def make_traffic(cfg, n_batches: int, batch: int, seed: int,
                 zipf_a=ZIPF_A):
    """``n_batches`` micro-batches of seeded requests from the repo's
    generator, as host arrays ``(dense (B, F), ids (T, B, L), lengths
    (T, B))``; ``zipf_a=None`` draws the paper's uniform ids."""
    from repro.data.synthetic import dlrm_batches

    gen = dlrm_batches(cfg, batch, seed=seed, zipf_a=zipf_a)
    out = []
    for _ in range(n_batches):
        b = next(gen)
        out.append((np.asarray(b["dense"]), np.asarray(b["batch"].indices),
                    np.asarray(b["batch"].lengths)))
    return out


def serve(params, cfg, traffic, batch: int, ctx=None):
    """Submit every request of ``traffic`` to the engine ``cfg`` selects
    and score them all; returns (scores in submit order, micro-batches
    scored, capacity fallbacks taken, engine)."""
    from repro.serving.engine import CTRRequest, make_dlrm_engine

    engine = make_dlrm_engine(params, cfg, batch, ctx)
    rid = 0
    for dense, idx, lens in traffic:
        for i in range(dense.shape[0]):
            engine.submit(CTRRequest(rid, dense[i], idx[:, i], lens[:, i]))
            rid += 1
    if cfg.cache.pipeline_depth > 1:
        got = engine.run_to_completion()
        # every micro-batch the ring could not take went through the
        # serialized split flush, which the trace does not record
        scored = engine.cache_stats().batches
        fallbacks = scored - len(engine.trace.by_stage("forward"))
    else:
        got, scored = {}, 0          # run_to_completion, counting flushes
        while engine.queue:
            got.update(engine.flush())
            scored += 1
        fallbacks = scored - -(-rid // batch)
    check(len(got) == rid, f"{len(got)} of {rid} requests scored")
    scores = np.array([got[r] for r in range(rid)], np.float32)
    check(bool(np.isfinite(scores).all()), "non-finite scores")
    return scores, scored, fallbacks, engine


def reference_scores(params, host_tables, cfg, traffic):
    """The plain ``jax.numpy`` forward on one chip, fed the rows each
    micro-batch reads from ``host_tables``: per table the batch's
    distinct rows, packed into a compact ``(T, B*L, D)`` table with ids
    remapped into it — the pooled rows are the full tables' rows, and no
    full-size copy goes on a device."""
    import jax.numpy as jnp

    from repro.cache import CacheConfig
    from repro.core.jagged import JaggedBatch
    from repro.serving.engine import make_scorer

    ref_cfg = dataclasses.replace(cfg, kernel_mode="reference",
                                  rw_backend="bulk", cache=CacheConfig())
    scorer = make_scorer(ref_cfg)
    out = []
    for dense, idx, lens in traffic:
        T, B, L = idx.shape
        compact = np.zeros((T, B * L, host_tables.shape[-1]),
                           host_tables.dtype)
        local = np.empty_like(idx)
        for t in range(T):
            rows, inv = np.unique(idx[t], return_inverse=True)
            compact[t, :rows.size] = host_tables[t, rows]
            local[t] = inv.reshape(B, L)
        out.append(np.asarray(scorer(
            {**params, "tables": jnp.asarray(compact)}, jnp.asarray(dense),
            JaggedBatch(jnp.asarray(local), jnp.asarray(lens)))))
    return np.concatenate(out).astype(np.float32)


def collective_kernels(jitted, *args) -> int:
    """How many one-sided (barrier-synchronised) Pallas kernels the
    compiled program holds."""
    from repro.analysis.contracts import tpu_kernels

    return tpu_kernels(jitted.lower(*args).compile().as_text())[1]


# ---------------------------------------------------------------------------
# One chip: local, tiered, pipelined
# ---------------------------------------------------------------------------

def run_one_chip(cfg, kernel_mode: str, *, batches: int = BATCHES,
                 batch: int = BATCH, slots: int = SLOTS, seed: int = 0):
    import jax

    from repro.cache import CacheConfig
    from repro.models import dlrm as dlrm_mod

    cfg = dataclasses.replace(cfg, kernel_mode=kernel_mode)
    traffic = make_traffic(cfg, batches, batch, seed)

    with Phase("local") as ph:
        params = dlrm_mod.init_params(jax.random.key(seed), cfg)
        local, scored, fallbacks = serve(params, cfg, traffic, batch)[:3]
        ref = serve(params, dataclasses.replace(
            cfg, kernel_mode="reference"), traffic, batch)[0]
        d = max_diff(local, ref)
        ph.facts.update(batches=scored, fallbacks=fallbacks,
                        max_dscore_vs_reference=d, bound=SCORE_TOL)
    check(d <= SCORE_TOL, f"local vs jnp reference: max |dscore| {d} "
                          f"> {SCORE_TOL}")

    # the one host copy of the tables is the tiered cold tier; dropping
    # the last reference to the device copy frees its HBM
    params = {**params, "tables": np.asarray(params["tables"])}

    tiered = CacheConfig(rows=slots, cold_tier="host")
    for name, cache in (("tiered", tiered),
                        ("pipelined", dataclasses.replace(
                            tiered, pipeline_depth=2))):
        with Phase(name) as ph:
            got, scored, fallbacks = serve(
                params, dataclasses.replace(cfg, cache=cache), traffic,
                batch)[:3]
            d = max_diff(got, local)
            ph.facts.update(batches=scored, fallbacks=fallbacks,
                            max_dscore_vs_local=d)
        check(d == 0.0, f"{name} vs local: max |dscore| {d}, want bitwise "
                        f"equal")
        check(fallbacks == 0, f"{name}: {fallbacks} capacity fallbacks")


# ---------------------------------------------------------------------------
# Four chips: row-wise sharded forward, remote cold tier
# ---------------------------------------------------------------------------

def run_four_chips(cfg, kernel_mode: str, *, batches: int = BATCHES,
                   batch: int = BATCH, slots: int = SLOTS, seed: int = 0):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.cache import CacheConfig
    from repro.core.jagged import JaggedBatch
    from repro.core.parallel import make_context
    from repro.launch.mesh import auto_mesh
    from repro.models import dlrm as dlrm_mod
    from repro.serving.engine import make_scorer

    E = 4
    check(len(jax.devices()) >= E, f"needs {E} devices, found "
                                   f"{len(jax.devices())}")
    cfg = dataclasses.replace(cfg, kernel_mode=kernel_mode)
    mesh = auto_mesh((1, E), ("data", "model"), devices=jax.devices()[:E])
    ctx = make_context(mesh)
    rep = NamedSharding(mesh, P())
    # tables drawn row-sharded in place: no chip holds the whole tables
    params = jax.jit(
        lambda k: dlrm_mod.init_params(k, cfg),
        out_shardings={"tables": NamedSharding(mesh, P(None, "model", None)),
                       "bottom": rep, "top": rep})(jax.random.key(seed))
    host = np.asarray(params["tables"])
    mlp = {k: jax.device_put(v, jax.devices()[0])
           for k, v in params.items() if k != "tables"}

    # the row-wise phases use the paper's uniform ids (a2a buckets are
    # sized for balanced owners); the remote tier serves zipf traffic
    uniform = make_traffic(cfg, batches, batch, seed, zipf_a=None)
    ref = reference_scores(mlp, host, cfg, uniform)
    for impl in ("allgather", "a2a"):
        for backend in ("bulk", "onesided"):
            c = dataclasses.replace(cfg, rw_impl=impl, rw_backend=backend)
            with Phase(f"rw_{impl}_{backend}") as ph:
                got, scored, _, engine = serve(params, c, uniform, batch,
                                               ctx)
                d = max_diff(got, ref)
                ph.facts.update(batches=scored,
                                max_dscore_vs_reference=d, bound=SCORE_TOL)
                if backend == "onesided" and kernel_mode == "pallas":
                    dense, idx, lens = uniform[0]
                    ph.facts["onesided_kernels"] = collective_kernels(
                        make_scorer(c, ctx), params, dense,
                        JaggedBatch(idx, lens))
            check(d <= SCORE_TOL, f"rw {impl}/{backend} vs reference: "
                                  f"max |dscore| {d} > {SCORE_TOL}")
            check(ph.facts.get("onesided_kernels", 1) > 0,
                  f"rw {impl}/{backend}: no one-sided kernel in the program")
            del engine
    del params

    zipf = make_traffic(cfg, batches, batch, seed + 1)
    ref = reference_scores(mlp, host, cfg, zipf)
    remote = {}
    for backend in ("bulk", "onesided"):
        cache = CacheConfig(rows=slots, cold_tier="remote", remote_hosts=E,
                            remote_backend=backend)
        with Phase(f"remote_{backend}") as ph:
            got, scored, fallbacks, engine = serve(
                {**mlp, "tables": host},
                dataclasses.replace(cfg, cache=cache), zipf, batch)
            d = max_diff(got, ref)
            stats = engine.cache_stats()
            ph.facts.update(batches=scored, fallbacks=fallbacks,
                            misses_remote=stats.misses_remote,
                            max_dscore_vs_reference=d, bound=SCORE_TOL)
            if backend == "onesided" and kernel_mode == "pallas":
                store = engine.cache.cold
                addr = np.zeros(1024, np.int32)
                ph.facts["onesided_kernels"] = collective_kernels(
                    store._fetch, store.shards, addr, addr)
        check(d <= SCORE_TOL, f"remote/{backend} vs reference: max "
                              f"|dscore| {d} > {SCORE_TOL}")
        check(stats.misses_remote > 0, f"remote/{backend}: no remote miss")
        check(fallbacks == 0, f"remote/{backend}: {fallbacks} capacity "
                              f"fallbacks")
        check(ph.facts.get("onesided_kernels", 1) > 0,
              f"remote/{backend}: no one-sided kernel in the fetch program")
        remote[backend] = got
        del engine
    d = max_diff(remote["bulk"], remote["onesided"])
    check(d == 0.0, f"remote bulk vs onesided: max |dscore| {d}, want "
                    f"bitwise equal")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cross-chip paths, on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found platform {dev.platform!r}; "
              f"this smoke test runs on a TPU only", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    try:
        from repro.configs.dlrm import CONFIG
        from repro.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package from {SRC}: "
              f"{e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    run = run_four_chips if args.four_chips else run_one_chip
    try:
        with jax.default_matmul_precision("highest"):   # see SCORE_TOL
            run(CONFIG, "pallas", seed=args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
