"""Trip-count-aware cost analysis of compiled HLO text.

``compiled.cost_analysis()`` counts every while-loop body ONCE — a 28-layer
``lax.scan`` therefore under-reports FLOPs by ~28x.  The roofline needs real
per-step numbers, so this module parses ``compiled.as_text()`` into a call
graph, extracts static trip counts for scan loops, and accumulates:

* **dot FLOPs**  — 2 · |result| · |contracted dims|, for every ``dot`` in
  every computation, weighted by the product of trip counts along its call
  chain (fusion-wrapped dots included);
* **HBM bytes** — Σ (result + operand bytes) over the *top-level* ops of
  control-flow computations only (ENTRY, while bodies/conds, conditional
  branches, calls).  Ops inside fusion computations never touch HBM and ops
  inside ``to_apply`` scalar appliers (reduce/sort/…) would be massively
  over-counted, so both are excluded.  Post-fusion op boundaries ≈ actual
  memory traffic (a static upper bound that ignores cache reuse);
* **collective bytes** — per-chip ICI wire traffic with ring-algorithm
  factors: all-gather (n-1)/n·R, reduce-scatter (n-1)·R_out,
  all-reduce 2·(n-1)/n·R, all-to-all (n-1)/n·R, collective-permute R.

Trip counts come from ``known_trip_count`` backend configs when present, else
from the loop-condition's comparison constant (exact for jax-emitted scans).
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(?P<dt>[a-z][a-z0-9]*)\[(?P<dims>[0-9,]*)\]")
_COMP_HDR_RE = re.compile(r"^\s*(ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_OP_RE = re.compile(
    r"^\s*(ROOT\s+)?%?(?P<var>[\w.\-]+)\s*=\s*(?P<type>\(?[^=]*?\)?)\s*"
    r"(?P<op>[\w\-]+)\((?P<operands>.*?)\)(?P<rest>.*)$"
)
_CALL_RE = re.compile(r"(calls|to_apply|body|condition)=%?([\w.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TRIPS_KNOWN_RE = re.compile(r'known_trip_count[^0-9]*(\d+)')
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,\s]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(?P<rows>\d+),(?P<cols>\d+)\]")

_SKIP_BYTES_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "iota", "while", "conditional", "opt-barrier",
}

# ops that only touch a window of their operands: count 2·|window| instead of
# |operands| + |result| (else a lax.scan reading one layer's slice of the
# stacked parameters would be charged the full stack every trip)
_WINDOW_BYTES_OPS = {"dynamic-slice", "slice", "gather"}
_UPDATE_BYTES_OPS = {"dynamic-update-slice", "scatter"}

COLLECTIVE_OPS = {
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "all-reduce-start", "all-gather-start",
    "collective-permute-start",
}


def _shape_elems_bytes(shape_str: str) -> tuple[int, int]:
    """(total elements, total bytes) across every array in the type string."""
    elems = total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt = m.group("dt")
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in m.group("dims").split(","):
            if d.strip():
                n *= int(d)
        elems += n
        total += n * _DTYPE_BYTES[dt]
    return elems, total


@dataclasses.dataclass
class Op:
    var: str
    type_str: str
    op: str
    operands: list[str]
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    ops: list[Op]


def _split_top_commas(s: str) -> list[str]:
    out, depth, cur = [], 0, []
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur))
    return out


def parse_hlo(text: str) -> tuple[dict[str, Computation], str]:
    """Returns ({name: Computation}, entry_name)."""
    comps: dict[str, Computation] = {}
    entry = ""
    cur: Computation | None = None
    for line in text.splitlines():
        line = re.sub(r"/\*.*?\*/", "", line)  # strip /*index=N*/ comments
        hdr = _COMP_HDR_RE.match(line)
        if hdr:
            cur = Computation(hdr.group("name"), [])
            comps[cur.name] = cur
            if hdr.group(1):
                entry = cur.name
            continue
        if cur is None:
            continue
        if line.strip().startswith("}"):
            cur = None
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        operands = []
        for tok in _split_top_commas(m.group("operands")):
            nm = re.search(r"%([\w.\-]+)", tok)
            if nm:
                operands.append(nm.group(1))
        cur.ops.append(
            Op(m.group("var"), m.group("type"), m.group("op"), operands, line)
        )
    return comps, entry


def while_reachable(comps: dict[str, Computation]) -> set[str]:
    """Names of computations that execute inside some ``while`` loop.

    Seeds from every while op's body/condition and follows the full call
    graph (fusions, to_apply appliers, calls, conditional branches) — the
    "decode loop interior" the static-analysis HLO rules scan for stray
    copies/reshards of loop-invariant buffers.
    """
    roots: list[str] = []
    callees: dict[str, list[str]] = defaultdict(list)
    for comp in comps.values():
        for op in comp.ops:
            targets = [t for _, t in _CALL_RE.findall(op.line)]
            m = _BRANCH_RE.search(op.line)
            if m:
                targets.extend(re.findall(r"%?([\w.\-]+)", m.group(1)))
            callees[comp.name].extend(targets)
            if op.op == "while":
                roots.extend(targets)
    reachable: set[str] = set()
    stack = roots
    while stack:
        name = stack.pop()
        if name in reachable:
            continue
        reachable.add(name)
        stack.extend(callees.get(name, []))
    return reachable


def _trip_count(cond: Computation | None, while_line: str) -> int:
    m = _TRIPS_KNOWN_RE.search(while_line)
    if m:
        return int(m.group(1))
    if cond is None:
        return 1
    consts = []
    for op in cond.ops:
        if op.op == "constant":
            c = re.search(r"constant\((-?\d+)\)", op.line)
            if c:
                consts.append(int(c.group(1)))
    return max(consts) if consts else 1


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group("cols"))
    m = _GROUPS_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip()])
    return 2


@dataclasses.dataclass
class HloCost:
    flops: float
    hbm_bytes: float
    collective: dict
    trip_counts: dict[str, int]


def analyze(text: str, fused_scopes: tuple[str, ...] = ()) -> HloCost:
    """``fused_scopes``: jax.named_scope names whose interior ops live in
    VMEM on the TPU target (a validated Pallas kernel exists for them) —
    their HBM bytes are skipped; FLOPs are still counted.  The kernel's own
    boundary traffic is added analytically by the caller (launch/dryrun)."""
    comps, entry = parse_hlo(text)

    # ---- call graph: (caller, callee, multiplier_weight, callee_kind) ------
    edges: dict[str, list[tuple[str, float, str]]] = defaultdict(list)
    fusion_callees: set[str] = set()
    apply_callees: set[str] = set()
    for comp in comps.values():
        for op in comp.ops:
            if op.op == "while":
                body = cond = None
                for kind, target in _CALL_RE.findall(op.line):
                    if kind == "body":
                        body = target
                    elif kind == "condition":
                        cond = target
                trips = _trip_count(comps.get(cond), op.line)
                if body:
                    edges[body].append((comp.name, float(trips), "loop"))
                if cond:
                    edges[cond].append((comp.name, float(trips + 1), "loop"))
            elif op.op == "fusion":
                for kind, target in _CALL_RE.findall(op.line):
                    if kind == "calls":
                        edges[target].append((comp.name, 1.0, "fusion"))
                        fusion_callees.add(target)
            elif op.op == "conditional":
                m = _BRANCH_RE.search(op.line)
                if m:
                    for t in re.findall(r"%?([\w.\-]+)", m.group(1)):
                        edges[t].append((comp.name, 1.0, "branch"))
            elif op.op == "call":
                for kind, target in _CALL_RE.findall(op.line):
                    edges[target].append((comp.name, 1.0, "call"))
            else:
                for kind, target in _CALL_RE.findall(op.line):
                    if kind == "to_apply":
                        edges[target].append((comp.name, 1.0, "apply"))
                        apply_callees.add(target)

    mult_cache: dict[str, float] = {}

    def multiplier(name: str, _depth=0) -> float:
        if name == entry:
            return 1.0
        if name in mult_cache:
            return mult_cache[name]
        if _depth > 200 or name not in edges:
            return 1.0
        total = sum(multiplier(caller, _depth + 1) * w for caller, w, _ in edges[name])
        mult_cache[name] = total if total else 1.0
        return mult_cache[name]

    # ---- accumulate -----------------------------------------------------
    flops = 0.0
    hbm = 0.0
    coll_bytes: dict[str, float] = defaultdict(float)
    coll_count: dict[str, float] = defaultdict(float)
    trip_counts: dict[str, int] = {}

    for comp in comps.values():
        mult = multiplier(comp.name)
        # symbol table for operand shapes
        shapes = {op.var: op.type_str for op in comp.ops}
        count_bytes = comp.name not in fusion_callees and comp.name not in apply_callees

        for op in comp.ops:
            # --- dot FLOPs (everywhere) --------------------------------
            if op.op == "dot":
                _, rbytes = _shape_elems_bytes(op.type_str)
                relems, _ = _shape_elems_bytes(op.type_str)
                cdims = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.line)
                csize = 1
                if cdims and op.operands:
                    lhs_shape = shapes.get(op.operands[0], "")
                    dims_m = _SHAPE_RE.search(lhs_shape)
                    if dims_m:
                        dims = [int(d) for d in dims_m.group("dims").split(",") if d.strip()]
                        for ci in cdims.group(1).split(","):
                            if ci.strip() and int(ci) < len(dims):
                                csize *= dims[int(ci)]
                flops += 2.0 * relems * csize * mult
            elif op.op == "convolution":
                # rough: 2 * |out| * prod(kernel spatial+input feature)
                relems, _ = _shape_elems_bytes(op.type_str)
                kshape = shapes.get(op.operands[1], "") if len(op.operands) > 1 else ""
                kelems, _ = _shape_elems_bytes(kshape)
                kdim = _SHAPE_RE.search(kshape)
                ksz = 1
                if kdim:
                    dims = [int(d) for d in kdim.group("dims").split(",") if d.strip()]
                    if dims:
                        ksz = kelems // max(dims[-1], 1)  # all but output-feature dim
                flops += 2.0 * relems * ksz * mult

            # --- HBM bytes (control-flow computations only) --------------
            in_fused_scope = any(s in op.line for s in fused_scopes)
            if (count_bytes and not in_fused_scope
                    and op.op not in _SKIP_BYTES_OPS and not op.op.endswith("-done")):
                _, rbytes = _shape_elems_bytes(op.type_str)
                if op.op in _WINDOW_BYTES_OPS:
                    hbm += 2 * rbytes * mult
                elif op.op in _UPDATE_BYTES_OPS:
                    ubytes = 0
                    if len(op.operands) > 1:
                        _, ubytes = _shape_elems_bytes(shapes.get(op.operands[1], ""))
                    hbm += 2 * (ubytes or rbytes) * mult
                else:
                    obytes = 0
                    for nm in op.operands:
                        _, b = _shape_elems_bytes(shapes.get(nm, ""))
                        obytes += b
                    hbm += (rbytes + obytes) * mult

            # --- collectives ----------------------------------------------
            base_op = op.op.replace("-start", "")
            if base_op in ("all-reduce", "all-gather", "reduce-scatter",
                           "all-to-all", "collective-permute") and not op.op.endswith("-done"):
                _, r = _shape_elems_bytes(op.type_str)
                n = _group_size(op.line)
                if base_op == "all-gather":
                    b = (n - 1) / n * r
                elif base_op == "reduce-scatter":
                    b = (n - 1) * r
                elif base_op == "all-reduce":
                    b = 2 * (n - 1) / n * r
                elif base_op == "all-to-all":
                    b = (n - 1) / n * r
                else:
                    b = r
                coll_bytes[base_op] += b * mult
                coll_count[base_op] += mult

    return HloCost(
        flops=flops,
        hbm_bytes=hbm,
        collective={
            "bytes_by_type": dict(coll_bytes),
            "count_by_type": dict(coll_count),
            "total_bytes": float(sum(coll_bytes.values())),
        },
        trip_counts=trip_counts,
    )


def collective_stats(hlo_text: str) -> dict:
    """Back-compat shim: trip-aware collective traffic only."""
    return analyze(hlo_text).collective
