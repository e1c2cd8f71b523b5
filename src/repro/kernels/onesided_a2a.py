"""Device-initiated one-sided collectives — the NVSHMEM analogue on TPU.

The paper's NVSHMEM embedding bag issues fine-grained one-sided puts from
inside the CUDA kernel, skipping host-launched collective scheduling —
that is what wins at small message sizes (§3, Fig. 1). The TPU-native
equivalent is a Pallas kernel issuing ``pltpu.make_async_remote_copy``
RDMA over ICI, device-initiated, with semaphore completion — no XLA
collective scheduling on the critical path.

``onesided_all_to_all(x, axis_name)``: x (E, C, ...) sharded over an
E-rank mesh axis; rank r's chunk x[d] lands in the output's row r on rank
d — identical semantics to ``jax.lax.all_to_all(x, a, 0, 0)`` (verified
against it in the tests via interpret mode, which models the remote DMA).

Every kernel here follows one protocol:

  1. barrier — each rank signals the barrier semaphore of every peer
     (``pltpu.get_barrier_semaphore()``, tied to the kernel's
     ``collective_id``) and waits for all E-1 signals, so no rank writes
     into a peer's output before that peer is in the kernel;
  2. puts — rank r sends to peers in the rotated order (r+1, r+2, ...) so
     no destination is hot at any step; all puts start back-to-back
     (non-blocking, the put_nbi model) before any completion wait; the
     rank's own chunk is a local DMA;
  3. completion — every rank waits for all of its sends and for all of
     the receives its peers aimed at it, so nothing is in flight when
     the kernel returns.

Operands stay in HBM (``memory_space=pl.ANY``): the DMAs go HBM to HBM
and nothing is staged through VMEM. The paper's reduce-scatter workaround
(NVSHMEM 2.9 had no reduce-scatter primitive: a2a then local sum, §4.4)
is ``onesided_reduce_scatter``.

Call INSIDE shard_map over ``axis_name``. On CPU test runs pass
``interpret=True``; on a TPU slice the same kernel lowers to Mosaic RDMA.
``core/comm.py`` routes ``backend="onesided"`` here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_A2A_ID, _RING_ID, _FETCH_ID = 7, 8, 9   # one collective_id per pattern
_PUTS_IN_FLIGHT = 64                     # per-row puts outstanding per rank


def _peer(axis_name: str, rank):
    return dict(device_id={axis_name: rank},
                device_id_type=pltpu.DeviceIdType.MESH)


def _barrier(axis_name: str, num_ranks: int):
    """Signal every peer's barrier semaphore, then wait for all of
    theirs: on return every rank of the axis is inside this kernel."""
    sem = pltpu.get_barrier_semaphore()
    me = jax.lax.axis_index(axis_name)
    for i in range(1, num_ranks):
        pltpu.semaphore_signal(
            sem, 1, **_peer(axis_name, jax.lax.rem(me + i, num_ranks)))
    pltpu.semaphore_wait(sem, num_ranks - 1)


def _call(kernel, x, collective_id, interpret, num_sems=3):
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA] * num_sems,
        compiler_params=pltpu.CompilerParams(
            collective_id=collective_id, has_side_effects=True),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(x)


def _a2a_kernel(x_ref, o_ref, send_sem, recv_sem, local_sem, *,
                axis_name: str, num_ranks: int):
    me = jax.lax.axis_index(axis_name)
    _barrier(axis_name, num_ranks)
    local = pltpu.make_async_copy(x_ref.at[me], o_ref.at[me], local_sem)
    local.start()
    puts = []
    for i in range(1, num_ranks):                     # rotated schedule
        dst = jax.lax.rem(me + i, num_ranks)
        puts.append(pltpu.make_async_remote_copy(
            src_ref=x_ref.at[dst], dst_ref=o_ref.at[me],
            send_sem=send_sem, recv_sem=recv_sem, **_peer(axis_name, dst)))
        puts[-1].start()                              # put_nbi: start all
    for p in puts:                                    # then complete
        p.wait()
    local.wait()


def onesided_all_to_all(x: jax.Array, axis_name: str, *,
                        interpret: bool = False) -> jax.Array:
    """x (E, C, ...) -> (E, C, ...): out[i] on rank j == x[j] from rank i.

    Must run inside shard_map over ``axis_name`` whose size == x.shape[0].
    """
    # each chunk is sent as whole (rows, 128) tiles: the compiler refuses
    # a DMA of one row of a 2-D (E, n) array, whose rows share a tile
    E, n = x.shape[0], x[0].size
    lanes = -(-n // 128) * 128
    tiles = jnp.pad(x.reshape(E, n), ((0, 0), (0, lanes - n)))
    kernel = functools.partial(_a2a_kernel, axis_name=axis_name,
                               num_ranks=E)
    out = _call(kernel, tiles.reshape(E, lanes // 128, 128), _A2A_ID,
                interpret)
    return out.reshape(E, lanes)[:, :n].reshape(x.shape)


def onesided_reduce_scatter(x: jax.Array, axis_name: str, *,
                            interpret: bool = False) -> jax.Array:
    """Paper §4.4 workaround: one-sided a2a + local sum.

    x (E, M, ...) -> (M, ...) = sum over source ranks of x_src[my_rank].
    """
    exchanged = onesided_all_to_all(x, axis_name, interpret=interpret)
    return exchanged.sum(axis=0)


def _fetch_rows_kernel(x_ref, o_ref, send_sem, recv_sem, local_sem, *,
                       axis_name: str, num_ranks: int, num_rows: int):
    """Per-ROW remote puts: the row-fetch transport of the tiered cache.

    ``x_ref (E, M, D)``: rank r's contribution to each peer's M requested
    rows (the rows r owns, zeros elsewhere).  Every (dst, m) pair is one
    fine-grained put of a single D-row — message size = one embedding row,
    exactly the small-message regime where device-initiated transport wins
    (§3 Fig. 1) — landing in the peer's ``o_ref[my_id, m]`` slot.  The
    (E-1)*M puts run in one loop over (peer, row) in rotated peer order
    with at most ``_PUTS_IN_FLIGHT`` outstanding: put k starts, then put
    k - _PUTS_IN_FLIGHT completes."""
    me = jax.lax.axis_index(axis_name)
    _barrier(axis_name, num_ranks)
    local = pltpu.make_async_copy(x_ref.at[me], o_ref.at[me], local_sem)
    local.start()
    n = (num_ranks - 1) * num_rows

    def put(k):
        # every put moves one row on the same semaphores, so waiting on
        # put k waits for one send and one receive
        dst = jax.lax.rem(me + 1 + k // num_rows, num_ranks)
        m = k % num_rows
        return pltpu.make_async_remote_copy(
            src_ref=x_ref.at[dst, m], dst_ref=o_ref.at[me, m],
            send_sem=send_sem, recv_sem=recv_sem, **_peer(axis_name, dst))

    def step(k, carry):
        put(k).start()

        @pl.when(k >= _PUTS_IN_FLIGHT)
        def _():
            put(k - _PUTS_IN_FLIGHT).wait()

        return carry

    def drain(k, carry):
        put(k).wait()
        return carry

    jax.lax.fori_loop(0, n, step, 0)
    jax.lax.fori_loop(max(n - _PUTS_IN_FLIGHT, 0), n, drain, 0)
    local.wait()


def onesided_fetch_rows(contrib: jax.Array, axis_name: str, *,
                        interpret: bool = False) -> jax.Array:
    """Row-fetch gather: ``contrib (E, M, D)`` -> ``(M, D)`` fetched rows.

    ``contrib[q]`` holds the rows of peer q's M requests that THIS rank
    owns (zeros elsewhere; every row has exactly one owner).  The kernel
    pushes each row to its requester with a device-initiated one-sided
    put (one DMA per row — no host-launched collective scheduling on the
    critical path), then the requester sums over owners, which for
    single-owner rows is a select.  Semantically identical to the bulk
    ``psum_scatter`` in ``core/comm.fetch_rows`` (verified in interpret
    mode by tests/_tiering_checks.py).

    Must run inside shard_map over ``axis_name`` == contrib.shape[0]."""
    kernel = functools.partial(
        _fetch_rows_kernel, axis_name=axis_name,
        num_ranks=contrib.shape[0], num_rows=contrib.shape[1])
    return _call(kernel, contrib, _FETCH_ID, interpret).sum(axis=0)


def onesided_ring_permute(x: jax.Array, axis_name: str, *, shift: int = 1,
                          interpret: bool = False) -> jax.Array:
    """One-sided ring shift (building block for pipelined schedules):
    rank r's ``x`` lands on rank ``(r + shift) % E``."""
    n = jax.lax.axis_size(axis_name)
    if shift % n == 0:
        return x

    def kernel(x_ref, o_ref, send_sem, recv_sem):
        _barrier(axis_name, n)
        dst = jax.lax.rem(jax.lax.axis_index(axis_name) + shift % n, n)
        put = pltpu.make_async_remote_copy(
            src_ref=x_ref, dst_ref=o_ref, send_sem=send_sem,
            recv_sem=recv_sem, **_peer(axis_name, dst))
        put.start()
        put.wait()

    return _call(kernel, x, _RING_ID, interpret, num_sems=2)
