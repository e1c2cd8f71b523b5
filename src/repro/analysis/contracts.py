"""Kernel & collective contract auditor.

The paper's structural claims are launch/traffic INVARIANTS — one fused
gather+pool ``pallas_call`` per forward, no collectives on the cached
hot path, a donated (in-place) slot-pool scatter — and until this PR
they were enforced by ad-hoc ``str(jaxpr).count("pallas_call")`` asserts
scattered over tests and benchmark drivers.  This module makes them one
declarative surface:

  * :class:`KernelContract` — the spec a hot entry point promises:
    launch-count bounds, the allowed collective set, required buffer
    donation on named argnums, a float-dtype ceiling (no silent
    f64/f32 upcasts), and a host-transfer ban (no callbacks /
    device_put in serving paths).
  * :func:`audit` — the reusable jaxpr walker: traces ``fn`` over
    ``args`` (arrays or ShapeDtypeStructs), recursively summarizes
    every primitive (through pjit / shard_map / custom_vjp / cond
    sub-jaxprs), and judges the summary against a contract.  Donation
    is verified on the lowered StableHLO (``tf.aliasing_output`` on the
    donated operand), so a dropped ``donate_argnums`` fails the audit
    even on backends that skip donation at runtime (CPU).
  * :func:`audit_hlo` / :func:`parse_collectives` — the post-SPMD HLO
    side of the same contract for compiled programs (moved here from
    ``launch/dryrun.py``): per-op collective operand bytes + counts,
    judged against the contract's allowed set.

Hot modules ATTACH contracts (``KERNEL_CONTRACTS`` dicts in
``kernels/ops.py``, ``cache/cached_bag.py``, ``core/embedding_bag.py``,
``serving/engine.py``, ``cache/tiers.py``); tests, benchmarks, and the
``python -m repro.analysis --contracts`` CLI all audit against those
single declarations.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

# Collective primitives as they appear in jaxprs (under a checked
# shard_map, lax.psum traces as "psum_invariant" and lax.all_gather as
# "all_gather_invariant").  Every primitive of ``jax._src.lax.parallel``
# except the non-communicating ones in ``NON_COLLECTIVE_PARALLEL``.
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "psum_invariant", "all_gather", "all_gather_invariant",
    "all_gather_reduced", "all_to_all", "ragged_all_to_all", "ppermute",
    "psend", "precv", "pbroadcast", "reduce_scatter", "pmin", "pmax",
    "pgather", "unreduced_psum", "unreduced_reduce_scatter",
})

# Primitives of ``jax._src.lax.parallel`` that move no data: the rank
# index and two no-op type casts.
NON_COLLECTIVE_PARALLEL = frozenset({
    "axis_index", "preduced", "vary_unreduced_cast_p",
})

# Primitives that move data across the host<->device boundary (or call
# back into Python) — forbidden on serving paths, where every byte of
# traffic must be the explicit prefetch.
HOST_TRANSFER_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "device_put", "infeed", "outfeed",
})

# Collective ops as they appear in post-SPMD HLO text.
HLO_COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                      "all-to-all", "collective-permute")


@dataclasses.dataclass(frozen=True)
class KernelContract:
    """What one hot entry point promises, declaratively.

    ``min/max_pallas_calls`` bound the traced launch count (the fused
    TBE paths promise exactly one; the donated scatter promises zero).
    ``allowed_collectives`` whitelists primitive names (jaxpr names for
    :func:`audit`, HLO op names for :func:`audit_hlo`); anything else
    is a violation.  ``donate_argnums`` lists operands that MUST be
    buffer-aliased (donated) in the lowering.  ``max_float_bits`` caps
    every intermediate float dtype (64 never passes by default — no
    silent f64 upcasts; set 16 for bf16-only paths).
    """

    name: str
    min_pallas_calls: int = 1
    max_pallas_calls: int = 1
    allowed_collectives: Tuple[str, ...] = ()
    donate_argnums: Tuple[int, ...] = ()
    max_float_bits: int = 32
    forbid_host_transfers: bool = True
    note: str = ""


@dataclasses.dataclass
class JaxprSummary:
    """Primitive census of one traced program (sub-jaxprs included)."""

    pallas_calls: int = 0
    collectives: Dict[str, int] = dataclasses.field(default_factory=dict)
    host_transfers: Dict[str, int] = dataclasses.field(default_factory=dict)
    float_dtypes: set = dataclasses.field(default_factory=set)
    primitives: Dict[str, int] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class AuditReport:
    """The verdict: a summary plus every contract violation found."""

    contract: KernelContract
    violations: List[str]
    summary: Optional[JaxprSummary] = None

    @property
    def ok(self) -> bool:
        return not self.violations

    def raise_if_failed(self) -> "AuditReport":
        if self.violations:
            raise ContractViolation(self.contract.name, self.violations)
        return self


class ContractViolation(AssertionError):
    """Raised by :meth:`AuditReport.raise_if_failed` — an AssertionError
    so migrated test asserts keep their failure semantics."""

    def __init__(self, name: str, violations: Sequence[str]):
        self.contract_name = name
        self.violations = list(violations)
        lines = "\n  - ".join(violations)
        super().__init__(f"kernel contract {name!r} violated:\n  - {lines}")


# ---------------------------------------------------------------------------
# Jaxpr walking
# ---------------------------------------------------------------------------

def _iter_jaxprs(value):
    """Yield every (Closed)Jaxpr reachable from one eqn param value."""
    from jax.extend.core import ClosedJaxpr

    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif hasattr(value, "eqns"):                       # raw Jaxpr
        yield value
    elif isinstance(value, (tuple, list)):
        for item in value:
            yield from _iter_jaxprs(item)


def _is_benign_device_put(name: str, params) -> bool:
    """Trace-time constant staging (``device_put`` with no concrete
    target device) is how jax stages Python scalars into a trace — it
    moves nothing at runtime.  Only a device_put with a real placement
    is a serving-path transfer."""
    if name != "device_put":
        return False
    devices = params.get("devices", [])
    return all(d is None for d in devices)


def _walk(jaxpr, summary: JaxprSummary) -> None:
    import numpy as np

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        summary.primitives[name] = summary.primitives.get(name, 0) + 1
        if name == "pallas_call":
            summary.pallas_calls += 1
        if name in COLLECTIVE_PRIMITIVES:
            summary.collectives[name] = summary.collectives.get(name, 0) + 1
        if name in HOST_TRANSFER_PRIMITIVES and \
                not _is_benign_device_put(name, eqn.params):
            summary.host_transfers[name] = \
                summary.host_transfers.get(name, 0) + 1
        for var in eqn.outvars:
            dtype = getattr(getattr(var, "aval", None), "dtype", None)
            if dtype is not None and np.issubdtype(dtype, np.floating):
                summary.float_dtypes.add(str(dtype))
        for value in eqn.params.values():
            for sub in _iter_jaxprs(value):
                _walk(sub, summary)


def summarize(fn, args: Sequence) -> JaxprSummary:
    """Trace ``fn`` over ``args`` (arrays or ShapeDtypeStructs) and
    census every primitive, recursing through sub-jaxprs."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    summary = JaxprSummary()
    _walk(closed.jaxpr, summary)
    return summary


def count_pallas_calls(fn, *args) -> int:
    """Traced ``pallas_call`` launch-site count (the sweep helpers'
    raw number; under vmap the T instances are ONE batched call-site)."""
    return summarize(fn, args).pallas_calls


def _float_bits(dtype_str: str) -> int:
    import numpy as np

    return np.dtype(dtype_str).itemsize * 8


# %argN: tensor<...> {tf.aliasing_output = K : i32} in the lowered
# StableHLO main signature — the donation/buffer-aliasing marker.
_ALIAS_RE = re.compile(r"%arg(\d+):[^,)]*\{[^}]*tf\.aliasing_output")


def donated_argnums(lowered_text: str) -> Tuple[int, ...]:
    """Argnums carrying the buffer-donation marker in a lowering."""
    return tuple(sorted(int(m.group(1))
                        for m in _ALIAS_RE.finditer(lowered_text)))


def audit(fn, args: Sequence, contract: KernelContract) -> AuditReport:
    """Judge ``fn`` traced over ``args`` against ``contract``.

    When the contract requires donation, ``fn`` must be the jitted
    callable itself (``jax.jit(..., donate_argnums=...)`` result) so
    its lowering can be inspected for the aliasing marker.
    """
    summary = summarize(fn, args)
    violations: List[str] = []

    n = summary.pallas_calls
    if not contract.min_pallas_calls <= n <= contract.max_pallas_calls:
        want = (f"exactly {contract.max_pallas_calls}"
                if contract.min_pallas_calls == contract.max_pallas_calls
                else f"{contract.min_pallas_calls}.."
                     f"{contract.max_pallas_calls}")
        violations.append(f"pallas_call launches: got {n}, contract "
                          f"requires {want}")

    allowed = set(contract.allowed_collectives)
    for prim, count in sorted(summary.collectives.items()):
        if prim not in allowed:
            violations.append(
                f"forbidden collective {prim!r} traced {count}x "
                f"(allowed: {sorted(allowed) or 'none'})")

    if contract.forbid_host_transfers:
        for prim, count in sorted(summary.host_transfers.items()):
            violations.append(
                f"host transfer/callback {prim!r} traced {count}x on a "
                f"serving path")

    for dtype_str in sorted(summary.float_dtypes):
        bits = _float_bits(dtype_str)
        if bits > contract.max_float_bits:
            violations.append(
                f"float dtype {dtype_str} ({bits} bits) exceeds the "
                f"{contract.max_float_bits}-bit ceiling (silent upcast)")

    if contract.donate_argnums:
        lower = getattr(fn, "lower", None)
        if lower is None:
            violations.append(
                f"contract requires donation of argnums "
                f"{contract.donate_argnums} but fn is not a jitted "
                f"callable (no .lower to inspect)")
        else:
            aliased = set(donated_argnums(lower(*args).as_text()))
            missing = sorted(set(contract.donate_argnums) - aliased)
            if missing:
                violations.append(
                    f"argnums {missing} are not donated/buffer-aliased "
                    f"in the lowering (dropped donate_argnums — the "
                    f"scatter would copy the whole pool)")

    return AuditReport(contract, violations, summary)


# ---------------------------------------------------------------------------
# Post-SPMD HLO side (compiled programs) — moved from launch/dryrun.py
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{(\{[0-9,]+\})")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of the LAST shape in a (possibly tuple) HLO shape str."""
    matches = _SHAPE_RE.findall(shape_str)
    if not matches:
        return 0
    dt, dims = matches[-1]
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def parse_collectives(hlo_text: str):
    """Per-device operand bytes and op counts by collective, from one
    SPMD module's text."""
    out = dict.fromkeys(HLO_COLLECTIVE_OPS, 0)
    counts = dict.fromkeys(out, 0)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        result = _shape_bytes(shape_str)
        g = 1
        mg = _IOTA_GROUPS_RE.search(line)
        if mg:
            g = int(mg.group(2))
        else:
            mg2 = _GROUPS_RE.search(line)
            if mg2:
                g = mg2.group(1).count(",") + 1
        if op == "all-gather":
            operand = result // max(g, 1)
        elif op == "reduce-scatter":
            operand = result * g
        else:
            operand = result
        out[op] += operand
        counts[op] += 1
    return out, counts


def audit_hlo(hlo_text: str, contract: KernelContract) -> AuditReport:
    """Judge a compiled program's HLO collective census against the
    contract's allowed set (HLO op names, e.g. ``all-reduce``)."""
    _, counts = parse_collectives(hlo_text)
    allowed = set(contract.allowed_collectives)
    violations = [
        f"compiled HLO issues {count}x {op} (allowed: "
        f"{sorted(allowed) or 'none'})"
        for op, count in sorted(counts.items())
        if count and op not in allowed
    ]
    return AuditReport(contract, violations)


# One Pallas TPU kernel in compiled HLO: the custom call up to the next
# instruction (its attributes and serialized body hold no "%").
_TPU_KERNEL_RE = re.compile(r'custom_call_target="tpu_custom_call"[^%]*')


def tpu_kernels(hlo_text: str) -> Tuple[int, int]:
    """``(Pallas TPU kernels, of which collective)`` in a compiled TPU
    program.  A collective kernel synchronises with its peers on a
    barrier semaphore (``"barrier_type":"CUSTOM"``): the one-sided RDMA
    kernels do, the embedding gather kernels do not, so a one-sided
    path that fell back to ``lax`` collectives counts none."""
    kernels = _TPU_KERNEL_RE.findall(hlo_text)
    return len(kernels), sum('"barrier_type":"CUSTOM"' in k
                             for k in kernels)


def repo_contracts() -> Dict[str, KernelContract]:
    """Every contract attached to a hot module, by qualified name."""
    from repro.cache import cached_bag, tiers
    from repro.core import embedding_bag
    from repro.kernels import ops
    from repro.serving import engine

    out: Dict[str, KernelContract] = {}
    for mod in (ops, cached_bag, embedding_bag, engine, tiers):
        for contract in getattr(mod, "KERNEL_CONTRACTS", {}).values():
            out[contract.name] = contract
    return out
