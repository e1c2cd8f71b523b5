"""Pipelined serving: double-buffered slot pools overlapping prefetch
with forward scoring (the PR-4 subsystem).

Why
---
The paper's central finding is that the distributed embedding-bag path
is dominated by communication and synchronization, and the tiered store
(repro/cache/) already shrank that traffic to the MISS payload.  What
remained (ROADMAP open item 1) was that ``DLRMEngine.flush`` serialized
cold-fetch -> pool-scatter -> forward, so every micro-batch still paid
the full ``fetch_rows`` latency on the critical path.  Scale-out
serving systems (capacity-driven scale-out inference, SURGE's
superbatch scheduling — PAPERS.md) recover throughput by HIDING fetch
latency behind compute rather than only shrinking it; the engine
already knows the next micro-batch's working set at admission time, so
there is no reason to wait.

The epoch / double-buffer protocol
----------------------------------
``double_buffer.DoubleBufferedSlotPool`` keeps ``depth`` full
``(T, S, D)`` slot pools (each with its own ``SlotPoolManager``
metadata) over ONE shared cold tier and ONE shared ``CacheStats``:

  * batch k's forward reads the LIVE buffer — nothing writes it;
  * batch k+1's admission metadata, cold ``fetch_rows`` and pool
    scatter all target the SHADOW buffer concurrently;
  * ``swap()`` rotates the ring and advances the shadow manager's
    EPOCH, publishing the prepared batch.  Plans are epoch-stamped
    (``SlotPoolManager.prepare_next``) and a commit refuses a plan
    whose epoch does not match — a dropped swap cannot silently serve
    a pool that never received its rows.  A failed fetch/scatter
    invalidates the plan's residency (no stale slots).

``scheduler.PipelineScheduler`` runs the stages
``admit -> fetch -> scatter -> forward -> swap``, exploiting JAX async
dispatch (no ``block_until_ready`` between stages; the scatter is a
donated jit that queues behind the in-flight forward) and running the
cold fetch on a background thread so it overlaps the forward under
sync dispatch too.  Every stage records a wall-clock ``StageSpan`` —
overlap is measured (``PipelineTrace.overlap_s``), not assumed.

When depth 2 wins
-----------------
Steady-state per-batch latency drops from ``prefetch + forward`` to
``max(prefetch, forward)`` (``perf_model.overlapped_phase_times``), so
the win is largest when the two are comparable: meaningful miss
traffic (cold or churning working sets, remote cold tiers where
``fetch_rows`` crosses the network) under a compute-heavy forward.  At
hit rates near 1.0 there is nothing to hide; at depth 1 the engine
degenerates to the serialized path exactly.  The price is ``depth``
pools' HBM and slightly colder per-buffer hit rates (each buffer sees
every ``depth``-th batch).

Exactness contract: the pipelined engine's scores are BITWISE equal to
the serialized engine's under any eviction churn — a batch's working
set is always fully resident in its own buffer before its forward
runs, and the pooled output is invariant to slot layout (same kernel,
same summation order, same row payloads).

Consumers: ``serving.engine.PipelinedDLRMEngine`` (selected by
``DLRMConfig.pipeline_depth``), and the forced-multi-device checks in
tests/_pipeline_checks.py.
"""
from repro.pipeline.double_buffer import DoubleBufferedSlotPool
from repro.pipeline.scheduler import (
    STAGES,
    PipelineScheduler,
    PipelineTrace,
    StageSpan,
)

__all__ = [
    "DoubleBufferedSlotPool",
    "PipelineScheduler",
    "PipelineTrace",
    "StageSpan",
    "STAGES",
]
