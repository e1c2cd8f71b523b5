"""Collective-communication layer: the NCCL/NVSHMEM split, TPU-adapted.

The paper compares two communication regimes:

  * NCCL  — host-launched, bandwidth-optimized bulk collectives. TPU
    analogue: XLA collectives (``jax.lax.*`` inside ``shard_map``),
    compiler-scheduled over ICI. Backend name: ``"bulk"``.
  * NVSHMEM — device-initiated one-sided communication, latency-optimized
    for small messages. TPU analogue: Pallas ``make_async_remote_copy``
    ring kernels (see kernels/onesided_a2a.py). Backend name:
    ``"onesided"``. Every collective of the phase pipeline has a
    one-sided form (all-gather and all-reduce exchange E copies through
    the a2a kernel); a ``"onesided"`` request runs the kernel — on TPU,
    or in the Pallas interpreter with ``interpret=True`` — and never
    falls back to lax. The latency difference is modelled analytically
    in core/perf_model.py, mirroring how the paper projects.

Every wrapper records (op, payload bytes, axis size) into an optional
instrumentation log so benchmarks can account collective traffic without
HLO parsing (the roofline additionally parses HLO as ground truth).

The paper notes NVSHMEM 2.9 lacked a reduce-scatter primitive and emulated
it with all-to-all + local sum (§4.4); ``reduce_scatter`` here exposes
``emulate_with_a2a=True`` to reproduce exactly that code path.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp

from jax.lax import axis_size
import numpy as np


# ---------------------------------------------------------------------------
# Instrumentation
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CollectiveEvent:
    op: str            # all_to_all | all_gather | reduce_scatter | all_reduce
    #                  # | permute | fetch_rows
    bytes_in: int      # local payload bytes entering the collective
    axis_size: int
    backend: str
    # wall-clock ``time.perf_counter`` stamps, recorded at dispatch so
    # the event can land on the unified trace timeline (repro/obs/).
    # 0.0/0.0 is the back-compat default: positional 4-field
    # constructions keep working, and trace-time records (t0 == t1) are
    # distinguishable from runtime-timed ones (t1 > t0).
    t0: float = 0.0
    t1: float = 0.0


class _Log(threading.local):
    def __init__(self):
        self.events: Optional[List[CollectiveEvent]] = None


_LOG = _Log()

# process-wide event sink (repro.obs.Tracer): unlike the thread-local
# instrument() log, events recorded on BACKGROUND threads (the pipelined
# engine's prefetch worker) reach it too
_SINK: Optional[Callable[[CollectiveEvent], object]] = None


def set_event_sink(fn: Optional[Callable[[CollectiveEvent], object]]):
    """Install a process-wide CollectiveEvent callback (None removes it);
    returns the previous sink so callers can restore it."""
    global _SINK
    prev, _SINK = _SINK, fn
    return prev


@contextlib.contextmanager
def instrument():
    """Collect CollectiveEvents emitted while tracing under this context."""
    prev, _LOG.events = _LOG.events, []
    try:
        yield _LOG.events
    finally:
        _LOG.events = prev


def _emit(ev: CollectiveEvent):
    if _LOG.events is not None:
        _LOG.events.append(ev)
    if _SINK is not None:
        _SINK(ev)


def _record(op: str, array, axis_name, backend: str):
    if _LOG.events is None and _SINK is None:
        return
    size = int(np.prod(array.shape)) * jnp.dtype(array.dtype).itemsize
    t = time.perf_counter()
    _emit(CollectiveEvent(op, size, axis_size(axis_name), backend, t, t))


def record_runtime(op: str, nbytes: int, n_devices: int, backend: str,
                   t0: float, t1: float):
    """Record a RUNTIME-timed collective event (``t1 > t0``).

    ``_record`` fires at jit trace time only — a compiled program never
    re-traces, so its events carry no per-execution wall clock.  Callers
    that execute a compiled collective (e.g. ``RemoteStore.fetch``)
    record the measured dispatch->materialize interval here instead.
    """
    if _LOG.events is None and _SINK is None:
        return
    _emit(CollectiveEvent(op, int(nbytes), int(n_devices), backend,
                          float(t0), float(t1)))


# ---------------------------------------------------------------------------
# Collectives (call inside shard_map)
# ---------------------------------------------------------------------------

def _onesided(backend: str) -> bool:
    if backend not in ("bulk", "onesided"):
        raise ValueError(f"unknown backend {backend!r}; pick 'bulk' or "
                         f"'onesided'")
    return backend == "onesided"


def _exchange(x, axis_name, interpret):
    """One-sided all-gather core: ``out[i]`` on every rank is rank i's
    ``x`` — the a2a kernel over E copies of ``x``."""
    from repro.kernels.onesided_a2a import onesided_all_to_all
    E = axis_size(axis_name)
    return onesided_all_to_all(jnp.broadcast_to(x, (E,) + x.shape),
                               axis_name, interpret=interpret)


def all_to_all(x, axis_name, *, split_axis=0, concat_axis=0, backend="bulk",
               interpret=False):
    """All-to-all: dim ``split_axis`` (size == axis size) is exchanged.

    ``backend="onesided"`` runs the Pallas RDMA kernel (``interpret``
    runs it in the Pallas interpreter) and exchanges dim 0 only."""
    _record("all_to_all", x, axis_name, backend)
    if _onesided(backend):
        if split_axis != 0 or concat_axis != 0 or x.ndim < 2:
            raise ValueError(
                "the one-sided all_to_all exchanges dim 0 of an array of "
                f"rank >= 2 (got split_axis={split_axis}, "
                f"concat_axis={concat_axis}, ndim={x.ndim})")
        from repro.kernels.onesided_a2a import onesided_all_to_all
        return onesided_all_to_all(x, axis_name, interpret=interpret)
    return jax.lax.all_to_all(
        x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=False
    )


def all_gather(x, axis_name, *, axis=0, tiled=False, backend="bulk",
               interpret=False):
    _record("all_gather", x, axis_name, backend)
    if _onesided(backend):
        parts = _exchange(x, axis_name, interpret)
        if tiled:
            return jnp.concatenate(list(parts), axis=axis)
        return jnp.moveaxis(parts, 0, axis)
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def all_reduce(x, axis_name, *, backend="bulk", interpret=False):
    _record("all_reduce", x, axis_name, backend)
    if _onesided(backend):
        return _exchange(x, axis_name, interpret).sum(axis=0)
    return jax.lax.psum(x, axis_name)


def reduce_scatter(
    x, axis_name, *, scatter_axis=0, backend="bulk", emulate_with_a2a=False,
    interpret=False,
):
    """Reduce-scatter over leading dim of size == axis size.

    ``emulate_with_a2a`` reproduces the paper's NVSHMEM 2.9 workaround
    (§4.4): all-to-all the partials, then sum locally. Numerically
    identical; costs an extra factor ~E/2 of traffic vs the fused
    collective — the benchmarks quantify exactly this gap.
    """
    _record("reduce_scatter", x, axis_name, backend)
    if _onesided(backend):
        # NVSHMEM 2.9 has no reduce-scatter primitive (§4.4): the one-sided
        # backend ALWAYS uses the a2a + local-sum emulation, like the paper.
        if scatter_axis != 0:
            raise ValueError("the one-sided reduce_scatter scatters dim 0")
        from repro.kernels.onesided_a2a import onesided_reduce_scatter
        return onesided_reduce_scatter(x, axis_name, interpret=interpret)
    if emulate_with_a2a:
        exchanged = jax.lax.all_to_all(
            x, axis_name, split_axis=scatter_axis, concat_axis=scatter_axis
        )
        return exchanged.sum(axis=scatter_axis)
    return jax.lax.psum_scatter(
        x, axis_name, scatter_dimension=scatter_axis, tiled=False
    )


def fetch_rows(shard, local_addr, owner, axis_name, *, backend="bulk",
               interpret=False):
    """Batched cross-rank row fetch — the tiered cache's cold-tier transport.

    Each rank holds a flat ``(rows_local, D)`` slice of the cluster-wide
    embedding row space and wants M rows scattered across its peers:

      shard:      (rows_local, D) this rank's row slice (all tables
                  concatenated, owner-local flat addressing).
      local_addr: (M,) owner-local flat address of each row THIS rank wants.
      owner:      (M,) owning rank of each requested row.

    Returns ``(M, D)`` — the requested payloads.  Protocol (both
    transports): replicate the small request list (the index traffic of
    the paper's phase-1 permute), each owner gathers the rows it holds,
    then the payloads move back to the requester:

      * ``backend="bulk"``   — one ``psum_scatter`` over the stacked
        (E, M, D) contributions (host-launched bulk collective);
      * ``backend="onesided"`` — per-row device-initiated RDMA puts
        (kernels/onesided_a2a.onesided_fetch_rows), the NVSHMEM-analogue
        row fetch that wins at embedding-row message sizes.

    Each row has exactly one owner, so the sum-over-owners is a select.
    Call INSIDE shard_map over ``axis_name``.  One CollectiveEvent
    (op="fetch_rows") is recorded with the stacked payload bytes so
    benchmarks can account the traffic without HLO parsing.
    ``interpret`` runs the one-sided kernel in the Pallas interpreter.
    """
    rank = jax.lax.axis_index(axis_name)
    req_addr = jax.lax.all_gather(local_addr, axis_name)      # (E, M)
    req_owner = jax.lax.all_gather(owner, axis_name)          # (E, M)
    mine = req_owner == rank
    safe = jnp.where(mine, req_addr, 0)
    contrib = shard[safe] * mine[..., None].astype(shard.dtype)  # (E, M, D)
    _record("fetch_rows", contrib, axis_name, backend)
    if _onesided(backend):
        from repro.kernels.onesided_a2a import onesided_fetch_rows
        return onesided_fetch_rows(contrib, axis_name, interpret=interpret)
    return jax.lax.psum_scatter(
        contrib, axis_name, scatter_dimension=0, tiled=False)


def permute_ring(x, axis_name, *, shift=1, backend="bulk"):
    """Ring collective-permute (building block for pipelined schedules)."""
    _record("permute", x, axis_name, backend)
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)
