"""Trip-count-aware cost extraction from post-SPMD HLO text.

XLA's ``compiled.cost_analysis()`` counts each while-loop BODY once,
ignoring trip counts — under a scan-over-layers design that undercounts a
96-layer model by ~96x. This module re-derives the three roofline inputs
by walking the HLO call graph:

    cost(computation) = own ops + sum_{call sites} multiplier * cost(callee)

where a ``while`` site's multiplier is its ``known_trip_count`` (emitted
by XLA whenever the trip count is static — true for every scan in this
codebase) and fusion/call sites multiply by 1.

Extracted per device:
  * flops           — 2 * numel(result) * prod(contracting dims) per dot
                      (matmuls are >95% of model FLOPs; elementwise ignored)
  * collective bytes — operand bytes of all-gather / all-reduce /
                      reduce-scatter / all-to-all / collective-permute
  * hbm bytes (approx) — sum of instruction result bytes x2 (read+write),
                      counting fusions as one read-inputs/write-output —
                      an upper-ish bound on steady-state HBM traffic,
                      cross-checked against cost_analysis() for unscanned
                      graphs (tests/test_hlo_cost.py)

Validated against unrolled-scan ground truth in the tests.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*((?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\])"
    r"(?:\{[^}]*\})?)\s+([a-z0-9\-]+)\((.*)$")
_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_CALLS = re.compile(r"(?:calls|to_apply|body)=%?([\w.\-]+)")
_COND = re.compile(r"condition=%?([\w.\-]+)")
_TRIP = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_EXPL = re.compile(r"replica_groups=\{(\{[0-9,]+\})")

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                  "all-to-all", "collective-permute")


def _shapes_of(shape_str: str) -> List[Tuple[str, List[int]]]:
    return [(dt, [int(x) for x in dims.split(",") if x])
            for dt, dims in _SHAPE.findall(shape_str)]


def _bytes_of(shape_str: str, last_only=True) -> int:
    shapes = _shapes_of(shape_str)
    if not shapes:
        return 0
    pick = shapes[-1:] if last_only else shapes
    total = 0
    for dt, dims in pick:
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


@dataclasses.dataclass
class _Comp:
    name: str
    instrs: Dict[str, str]                        # name -> shape str
    flops: float = 0.0
    hbm_bytes: float = 0.0
    coll: Dict[str, float] = dataclasses.field(
        default_factory=lambda: dict.fromkeys(COLLECTIVE_OPS, 0.0))
    coll_counts: Dict[str, float] = dataclasses.field(
        default_factory=lambda: dict.fromkeys(COLLECTIVE_OPS, 0.0))
    # float elements moved by collectives (for native-dtype normalization:
    # the CPU backend upcasts bf16 compute to f32, so byte counts from CPU
    # HLO overstate a bf16 TPU program ~2x; elements are invariant)
    coll_float_elems: Dict[str, float] = dataclasses.field(
        default_factory=lambda: dict.fromkeys(COLLECTIVE_OPS, 0.0))
    hbm_float_elems: float = 0.0
    hbm_other_bytes: float = 0.0
    param_bytes: float = 0.0
    param_float_elems: float = 0.0
    param_other_bytes: float = 0.0
    # (op, bytes, shape_str, metadata) — for breakdowns
    coll_instances: List[Tuple[str, float, str, str]] = dataclasses.field(
        default_factory=list)
    # (callee, multiplier, kind) — kind "loop" (while/conditional bodies:
    # all metrics recurse) vs "fusion" (flops/collectives recurse; HBM does
    # NOT — fusion-internal intermediates live in registers/VMEM, only the
    # fusion's own result is HBM traffic and is counted at the call site)
    sites: List[Tuple[str, float, str]] = dataclasses.field(
        default_factory=list)
    unknown_trip: bool = False


def _parse_computations(hlo: str) -> Dict[str, _Comp]:
    comps: Dict[str, _Comp] = {}
    cur: Optional[_Comp] = None
    pending_lines: List[str] = []
    for raw in hlo.splitlines():
        m = _COMP_HDR.match(raw.strip()) if "{" in raw else None
        if m and ("->" in raw):
            cur = _Comp(m.group(1), {})
            comps[cur.name] = cur
            continue
        if cur is None:
            continue
        if raw.strip() == "}":
            cur = None
            continue
        im = _INSTR.match(raw)
        if not im:
            continue
        name, shape_str, op, rest = im.groups()
        cur.instrs[name] = shape_str
        _account_instr(cur, name, shape_str, op, rest, raw)
    return comps


def _account_instr(comp: _Comp, name: str, shape_str: str, op: str,
                   rest: str, line: str):
    # --- call sites
    if op == "while":
        body = _CALLS.search(line)
        cond = _COND.search(line)
        tm = _TRIP.search(line)
        trip = float(tm.group(1)) if tm else 1.0
        if not tm:
            comp.unknown_trip = True
        if body:
            comp.sites.append((body.group(1), trip, "loop"))
        if cond:
            comp.sites.append((cond.group(1), trip, "loop"))
        return
    if op == "conditional":
        for callee in _CALLS.findall(line):
            comp.sites.append((callee, 1.0, "loop"))
    elif op in ("fusion", "call", "custom-call", "map", "reduce", "sort",
                "scatter", "select-and-scatter", "reduce-window",
                "all-reduce"):
        for callee in _CALLS.findall(line):
            comp.sites.append((callee, 1.0, "fusion"))
    # --- collectives
    base = op[:-6] if op.endswith("-start") else op
    if base in COLLECTIVE_OPS:
        result = _bytes_of(shape_str)
        g = 1
        mg = _GROUPS_IOTA.search(line)
        if mg:
            g = int(mg.group(2))
        else:
            mg2 = _GROUPS_EXPL.search(line)
            if mg2:
                g = mg2.group(1).count(",") + 1
        if base == "all-gather":
            operand = result / max(g, 1)
        elif base == "reduce-scatter":
            operand = result * g
        else:
            operand = result
        comp.coll[base] += operand
        comp.coll_counts[base] += 1
        shapes = _shapes_of(shape_str)
        if shapes and shapes[-1][0] in ("f32", "f64", "bf16", "f16"):
            itemsize = _DTYPE_BYTES[shapes[-1][0]]
            comp.coll_float_elems[base] += operand / itemsize
        meta = ""
        mm = re.search(r'op_name="([^"]*)"', line)
        if mm:
            meta = mm.group(1)[-120:]
        comp.coll_instances.append((base, operand, shape_str[:60], meta))
    # --- flops (dots dominate)
    if op == "dot":
        cm = _CONTRACT.search(line)
        contract = [int(x) for x in cm.group(1).split(",")] if cm and cm.group(1) else []
        lhs_name = rest.split("%", 1)[1].split(",")[0].split(")")[0] \
            if "%" in rest else None
        lhs_shape = comp.instrs.get(lhs_name or "", "")
        shapes = _shapes_of(lhs_shape)
        k = 1
        if shapes:
            dims = shapes[-1][1]
            for c in contract:
                if c < len(dims):
                    k *= dims[c]
        out = _shapes_of(shape_str)
        numel = 1
        for d in (out[-1][1] if out else []):
            numel *= d
        comp.flops += 2.0 * numel * k
    # --- hbm traffic approximation: write result once (+ the blanket x2
    # read/write factor in analyze()). ENTRY parameters (weight/input
    # reads) are added separately in analyze(): a while-body parameter is
    # the whole carry tuple INCLUDING the stacked scanned-over weights, so
    # blanket-counting it per iteration would overcount by ~num_layers.
    if op == "parameter":
        comp.param_bytes += _bytes_of(shape_str, last_only=False)
        for dt, dims in _shapes_of(shape_str):
            n = 1
            for d in dims:
                n *= d
            if dt in ("f32", "f64", "bf16", "f16"):
                comp.param_float_elems += n
            else:
                comp.param_other_bytes += n * _DTYPE_BYTES.get(dt, 4)
        return
    if op not in ("constant", "get-tuple-element", "tuple",
                  "bitcast", "while", "call"):
        comp.hbm_bytes += _bytes_of(shape_str, last_only=False)
        for dt, dims in _shapes_of(shape_str):
            n = 1
            for d in dims:
                n *= d
            if dt in ("f32", "f64", "bf16", "f16"):
                comp.hbm_float_elems += n
            else:
                comp.hbm_other_bytes += n * _DTYPE_BYTES.get(dt, 4)


def analyze(hlo: str) -> Dict[str, object]:
    """Full-module trip-count-weighted totals (per device)."""
    comps = _parse_computations(hlo)
    entry = None
    # ENTRY computation: the header line starts with 'ENTRY'
    for line in hlo.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_HDR.match(line.strip())
            if m:
                entry = m.group(1)
                break
    if entry is None or entry not in comps:
        # fall back: computation named main*
        for n in comps:
            if n.startswith("main"):
                entry = n
                break
    memo: Dict[str, Dict] = {}

    def cost(name: str, stack=()) -> Dict:
        if name in memo:
            return memo[name]
        if name not in comps or name in stack:
            return {"flops": 0.0, "hbm": 0.0,
                    "coll": dict.fromkeys(COLLECTIVE_OPS, 0.0),
                    "counts": dict.fromkeys(COLLECTIVE_OPS, 0.0),
                    "unknown_trip": False}
        c = comps[name]
        total = {"flops": c.flops, "hbm": c.hbm_bytes,
                 "hbm_fe": c.hbm_float_elems, "hbm_ob": c.hbm_other_bytes,
                 "coll": dict(c.coll), "counts": dict(c.coll_counts),
                 "coll_fe": dict(c.coll_float_elems),
                 "unknown_trip": c.unknown_trip}
        for callee, mult, kind in c.sites:
            sub = cost(callee, stack + (name,))
            total["flops"] += mult * sub["flops"]
            if kind == "loop":      # fusion internals are not HBM traffic
                total["hbm"] += mult * sub["hbm"]
                total["hbm_fe"] += mult * sub["hbm_fe"]
                total["hbm_ob"] += mult * sub["hbm_ob"]
            for k in COLLECTIVE_OPS:
                total["coll"][k] += mult * sub["coll"][k]
                total["counts"][k] += mult * sub["counts"][k]
                total["coll_fe"][k] += mult * sub["coll_fe"][k]
            total["unknown_trip"] |= sub["unknown_trip"]
        memo[name] = total
        return total

    t = cost(entry)
    # read+write approximation; entry parameters read once (weights/inputs)
    t["hbm"] *= 2.0
    t["hbm_fe"] *= 2.0
    t["hbm_ob"] *= 2.0
    ec = comps[entry]
    t["hbm"] += ec.param_bytes
    t["hbm_fe"] += ec.param_float_elems
    t["hbm_ob"] += ec.param_other_bytes
    return {
        "flops_per_device": t["flops"],
        "hbm_bytes_per_device_approx": t["hbm"],
        "hbm_float_elems_per_device": t["hbm_fe"],
        "hbm_other_bytes_per_device": t["hbm_ob"],
        "collective_bytes_per_device": t["coll"],
        "collective_float_elems_per_device": t["coll_fe"],
        "collective_exec_counts": t["counts"],
        "has_unknown_trip_counts": bool(t["unknown_trip"]),
    }


def collective_breakdown(hlo: str, top: int = 20):
    """Trip-weighted list of the heaviest collective instances."""
    comps = _parse_computations(hlo)
    entry = None
    for line in hlo.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_HDR.match(line.strip())
            if m:
                entry = m.group(1)
    # total trip multiplier per computation (entry = 1)
    mult: Dict[str, float] = {entry: 1.0}
    changed = True
    while changed:
        changed = False
        for name, c in comps.items():
            if name not in mult:
                continue
            for callee, m_, _kind in c.sites:
                new = mult[name] * m_
                if callee not in mult or mult[callee] < new:
                    # accumulate across multiple call sites
                    mult[callee] = mult.get(callee, 0.0) + new \
                        if callee in mult and mult[callee] != new else new
                    changed = True
    rows = []
    for name, c in comps.items():
        w = mult.get(name, 0.0)
        if w == 0:
            continue
        for op, operand, shape, meta in c.coll_instances:
            rows.append((op, operand * w, w, shape, meta))
    rows.sort(key=lambda r: -r[1])
    return rows[:top]
