"""Traffic: a run's requests, drawn from the seed by one general generator.

A mix is a data file, ``bench/traffic/<mix>.json``; its keys:

* ``mode``: ``"offline"`` (micro-batches queued back to back) or
  ``"server"`` (queries on an open-loop schedule);
* ``ids``: ``{"dist": "uniform"}`` or ``{"dist": "zipf", "a": <a>}``; zipf
  ranks follow the zeta distribution truncated to exactly the table's
  rows (rank 0, the hottest, is row 0), as ``repro.core.jagged.zipf_ranks``
  draws for ``a <= 1``; every sample looks up ``pooling`` rows of every
  table, the paper's fixed pooling (§4.3);
* ``cache_log_lookups`` (optional): where the configuration has a tiered
  store, its frequency counters are seeded from a log of this many
  lookups per table of the same distribution (``log_counts``), as a
  deployment warms its cache from logged traffic;
* ``distinct_samples``: how many distinct samples the run draws; the run
  cycles through them in order, the warm-up first;
* ``warmup_batches``: micro-batches scored before the window, to compile
  and to bring a cache to its steady state;
* offline: ``batches_per_call``, the micro-batches queued per call;
* server: ``rate_qps``, ``query_samples`` ``[lo, hi]`` (sizes uniform
  over ``lo..hi``), ``schedule_seed`` (the order of the arrivals, see
  ``query_schedule``) and ``max_wait_ms`` (a micro-batch leaves when full
  or when its oldest sample has waited this long).

The configuration's ``rows_per_table`` and ``pooling`` are each one int
for every table or a list of ``num_sparse_features`` ints, one per table
(``per_table``): table ``t`` then draws ``pooling[t]`` ids from its own
``rows_per_table[t]`` rows, and its slots past ``pooling[t]`` hold 0.  A
list of equal entries draws exactly what the int draws.

Samples are drawn on the device in one program per chunk and copied to
the host once, so a run's set-up pays little for them.  The same seed
gives the same samples.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.dlrm_reference import seed_words

CHUNK = 8192           # samples drawn per device program
_TRAFFIC_STREAM = 7    # fold_in tag that keeps traffic apart from weights


@dataclasses.dataclass
class Samples:
    dense: np.ndarray      # (n, F) float32
    ids: np.ndarray        # (n, T, max L_t) int32, table rows; 0 past L_t
    lengths: np.ndarray    # (T,) int32, each table's pooling L_t, every sample

    def __len__(self) -> int:
        return self.dense.shape[0]


def per_table(cfg: dict, key: str) -> np.ndarray:
    """``cfg[key]`` as int64 ``(T,)``: an int repeated for each of the
    ``num_sparse_features`` tables, or a list of one int per table."""
    T = cfg["num_sparse_features"]
    v = cfg[key]
    out = np.asarray([v] * T if isinstance(v, int) else v, np.int64)
    if out.shape != (T,) or out.min() < 1:
        raise ValueError(f"{key} must be a positive int or a list of "
                         f"{T} positive ints, got {v!r}")
    return out


def zipf_alias(a: float, rows: int):
    """Walker's alias table (Vose's construction) of the truncated zeta
    distribution over ``rows`` ranks, ``p_k ∝ k**-a``: ``(threshold
    float32 (rows,), alias int32 (rows,))``.  A draw takes a uniform
    column ``i`` and a uniform ``u``: rank ``i`` if ``u < threshold[i]``,
    else ``alias[i]``; two gathers per draw, exact to the thresholds'
    float32 rounding."""
    if a <= 0:
        raise ValueError(f"zipf a must be positive, got {a}")
    pmf = np.arange(1, rows + 1, dtype=np.float64) ** -a
    q = (pmf * (rows / pmf.sum())).tolist()
    threshold, alias = [1.0] * rows, list(range(rows))
    small = [i for i, x in enumerate(q) if x < 1.0]
    large = [i for i, x in enumerate(q) if x >= 1.0]
    while small and large:
        s, big = small.pop(), large[-1]
        threshold[s], alias[s] = q[s], big
        q[big] += q[s] - 1.0
        if q[big] < 1.0:
            small.append(large.pop())
    return (np.asarray(threshold, np.float32), np.asarray(alias, np.int32))


def log_counts(mix: dict, rows):
    """Lookups per row in a log of ``mix["cache_log_lookups"]`` lookups
    per table drawn from the mix's id distribution: what a deployment
    seeds its cache's frequency counters from.  For ``rows`` an int,
    int64 ``(rows,)``, the same for every table; for a list of each
    table's rows, a list of int64 ``(rows[t],)``, one per table (tables of
    one size share one read-only array)."""
    n = mix["cache_log_lookups"]

    def counts(r: int) -> np.ndarray:
        if mix["ids"]["dist"] == "uniform":
            c = np.full(r, n // r, np.int64)
        else:
            pmf = np.arange(1, r + 1, dtype=np.float64) ** -mix["ids"]["a"]
            c = np.round(pmf / pmf.sum() * n).astype(np.int64)
        c.flags.writeable = False
        return c

    if isinstance(rows, int):
        return counts(rows)
    by_size = {r: counts(r) for r in sorted(set(rows))}
    return [by_size[r] for r in rows]


@functools.partial(jax.jit, static_argnames=("shape", "dense_features",
                                             "rows", "zipf"))
def _draw(key, lengths, threshold, alias, *, shape, dense_features, rows,
          zipf):
    """``shape = (n, T, max L_t)``; ``rows``, a tuple, and ``lengths``,
    int32 ``(T,)``, give each table's ``R_t`` and ``L_t``; the zipf alias
    tables are those of the distinct ``R_t`` in ascending order, end to
    end.  Ids come from one ``randint`` per distinct ``R_t``, each over the
    whole shape with the same key: a scalar bound draws for table ``t``
    what one int for every table draws, and compiles in seconds where an
    array of bounds takes the TPU compiler about a minute."""
    k_col, k_u, k_dense = jax.random.split(key, 3)
    sizes = sorted(set(rows))
    size_of = np.asarray(rows)[None, :, None]
    ids = jax.random.randint(k_col, shape, 0, sizes[0])
    for r in sizes[1:]:
        ids = jnp.where(size_of == r, jax.random.randint(k_col, shape, 0, r),
                        ids)
    if zipf:
        u = jax.random.uniform(k_u, shape)
        starts = np.cumsum([0] + sizes[:-1])
        at = ids + starts[np.searchsorted(sizes, size_of)]
        ids = jnp.where(u < threshold[at], ids, alias[at])
    ids = jnp.where(jnp.arange(shape[2]) < lengths[:, None], ids, 0)
    dense = jax.random.normal(k_dense, (shape[0], dense_features))
    return dense, ids.astype(jnp.int32)


def samples(seed: int, cfg: dict, mix: dict, n: int) -> Samples:
    """``n`` samples of the mix's id distribution for configuration
    ``cfg``, drawn from ``seed``.  Zipf draws build ``zipf_alias`` once
    for each distinct table size."""
    R, L = per_table(cfg, "rows_per_table"), per_table(cfg, "pooling")
    dist = mix["ids"]["dist"]
    if dist == "zipf":
        tables = [zipf_alias(mix["ids"]["a"], r) for r in sorted(set(R))]
        threshold = jnp.asarray(np.concatenate([t for t, _ in tables]))
        alias = jnp.asarray(np.concatenate([a for _, a in tables]))
    elif dist == "uniform":
        threshold = alias = jnp.zeros((1,), jnp.int32)
    else:
        raise ValueError(f"unknown id distribution {dist!r}")
    key = jax.random.fold_in(
        jax.random.wrap_key_data(jnp.asarray(seed_words(seed)),
                                 impl="threefry2x32"), _TRAFFIC_STREAM)
    dense, ids = [], []
    for c in range(-(-n // CHUNK)):
        d, i = _draw(jax.random.fold_in(key, c), jnp.asarray(L, jnp.int32),
                     threshold, alias, shape=(CHUNK, R.size, int(L.max())),
                     dense_features=cfg["num_dense_features"],
                     rows=tuple(R.tolist()), zipf=dist == "zipf")
        dense.append(np.asarray(d))
        ids.append(np.asarray(i))
    return Samples(np.concatenate(dense)[:n], np.concatenate(ids)[:n],
                   L.astype(np.int32))


def query_schedule(mix: dict, seconds: float):
    """Open-loop queries due in ``[0, seconds)``: ``(due times (Q,),
    sizes (Q,))``.

    The schedule belongs to the mix, not to the run's seed, which draws
    the queries' samples: ``Q = rate * seconds`` queries whose gaps are
    the ``Q`` quantiles of the exponential distribution (a Poisson
    process's gaps) and whose sizes are the ``Q`` quantiles of the uniform
    size range, each shuffled by the mix's ``schedule_seed``, the gaps then
    scaled to fill the window.  So every run offers the same bursts: near
    capacity the order of gaps and sizes alone moved the 99th percentile
    twofold between seeds."""
    rate = mix["rate_qps"]
    lo, hi = mix["query_samples"]
    q = max(1, round(rate * seconds))
    grid = (np.arange(q) + 0.5) / q
    gaps = -np.log1p(-grid) / rate
    sizes = lo + np.floor(grid * (hi - lo + 1)).astype(np.int64)
    rng = np.random.default_rng(mix["schedule_seed"])
    rng.shuffle(gaps)
    rng.shuffle(sizes)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (seconds / gaps.sum())
    return due, sizes
