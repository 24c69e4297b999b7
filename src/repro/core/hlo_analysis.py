"""Post-optimization HLO analysis for the roofline terms (§Roofline).

``compiled.cost_analysis()`` counts while-loop bodies ONCE (verified on this
jax build: a 5-iteration scan reports 1 iteration of flops), so scanned-layer
models would be under-counted ~L-fold. This module parses
``compiled.as_text()`` — where XLA annotates every while with
``backend_config={"known_trip_count":{"n":...}}`` — and produces
trip-weighted:

  * ``flops``          dot/convolution MACs ×2 + fusion elementwise elems,
  * ``hbm_bytes``      per-instruction materialized result bytes + entry IO
                       (post-fusion, each surviving instruction is a buffer
                       write; operands of dots/fusions are buffer reads),
  * ``collective_bytes`` per-op ICI traffic with standard accounting:
        all-gather:        result_bytes × (g-1)/g
        all-reduce:        2 × operand_bytes × (g-1)/g
        reduce-scatter:    operand_bytes × (g-1)/g
        all-to-all:        operand_bytes × (g-1)/g
        collective-permute: operand_bytes
  * a per-collective breakdown for the §Perf iteration log.

This is per-DEVICE analysis (the compiled module is the SPMD program of one
participant).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field


_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|s32|s16|s8|s4|u64|u32|u16|u8|u4|pred|c64|c128)\[([\d,]*)\]")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "s32": 4, "s16": 2, "s8": 1, "s4": 1,
                "u64": 8, "u32": 4, "u16": 2, "u8": 1, "u4": 1, "pred": 1,
                "c64": 8, "c128": 16}
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\((.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->\s*.+\{\s*$")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{([^}]*)\}")
_CALLS_RE = re.compile(r"(?:calls=|body=|condition=|to_apply=)%([\w.\-]+)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")


def _shapes_bytes(text: str) -> float:
    """Total bytes of all shapes mentioned in a type string."""
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _first_shape_elems(text: str) -> tuple[float, float]:
    m = _SHAPE_RE.search(text)
    if not m:
        return 0.0, 0.0
    dt, dims = m.groups()
    n = 1
    if dims:
        for d in dims.split(","):
            if d:
                n *= int(d)
    return float(n), float(n * _DTYPE_BYTES[dt])


@dataclass
class Instr:
    name: str
    result_type: str
    op: str
    rest: str            # raw tail of the line (operands + attributes)

    @property
    def result_bytes(self) -> float:
        return _shapes_bytes(self.result_type)

    @property
    def result_elems(self) -> float:
        el, _ = _first_shape_elems(self.result_type)
        return el


@dataclass
class Computation:
    name: str
    instrs: list[Instr] = field(default_factory=list)
    is_entry: bool = False

    def by_name(self) -> dict[str, Instr]:
        return {i.name: i for i in self.instrs}


def parse_hlo_computations(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for line in text.splitlines():
        if not line.strip():
            cur = None
            continue
        mc = _COMP_RE.match(line)
        if mc and ("=" not in line.split("(")[0]):
            cur = Computation(name=mc.group(1),
                              is_entry=line.lstrip().startswith("ENTRY"))
            comps[cur.name] = cur
            continue
        if cur is None:
            continue
        mi = _INSTR_RE.match(line)
        if mi:
            name, rtype, op, rest = mi.groups()
            cur.instrs.append(Instr(name=name, result_type=rtype.strip(),
                                    op=op, rest=rest))
    return comps


def _dot_flops(instr: Instr, defs: dict[str, Instr],
               params_types: dict[str, str]) -> float:
    """2 * result_elems * prod(lhs contracting dims)."""
    ops = _OPERAND_RE.findall(instr.rest)
    if not ops:
        return 0.0
    lhs_name = ops[0]
    lhs_type = None
    if lhs_name in defs:
        lhs_type = defs[lhs_name].result_type
    elif lhs_name in params_types:
        lhs_type = params_types[lhs_name]
    if lhs_type is None:
        return 2.0 * instr.result_elems
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", instr.rest)
    mshape = _SHAPE_RE.search(lhs_type)
    if not mshape:
        return 2.0 * instr.result_elems
    dims = [int(d) for d in mshape.group(2).split(",") if d]
    k = 1
    if m and m.group(1):
        for d in m.group(1).split(","):
            if d and int(d) < len(dims):
                k *= dims[int(d)]
    # batch dims are part of result elems already
    return 2.0 * instr.result_elems * k


def _conv_flops(instr: Instr, defs: dict[str, Instr]) -> float:
    ops = _OPERAND_RE.findall(instr.rest)
    if len(ops) < 2 or ops[1] not in defs:
        return 2.0 * instr.result_elems
    rhs = defs[ops[1]]
    el, _ = _first_shape_elems(rhs.result_type)
    m = re.search(r"dim_labels=[\w\d]*_([\w\d]*)->", instr.rest)
    out_feat = 1.0
    if m:
        lbl = m.group(1)
        oi = lbl.find("o")
        ms = _SHAPE_RE.search(rhs.result_type)
        if oi >= 0 and ms:
            dims = [int(d) for d in ms.group(2).split(",") if d]
            if oi < len(dims):
                out_feat = float(dims[oi])
    return 2.0 * instr.result_elems * el / max(out_feat, 1.0)


def _group_size(instr: Instr, default: int) -> int:
    m = _GROUPS_RE.search(instr.rest)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_BRACE_RE.search(instr.rest)
    if m and m.group(1).strip():
        first = m.group(1).split("}")[0].split("{")[-1]
        return max(len([x for x in first.split(",") if x.strip() != ""]), 1)
    return default


def _collective_bytes(instr: Instr, defs: dict[str, Instr], n_devices: int,
                      logical_bf16: bool = False) -> float:
    """``logical_bf16``: XLA:CPU legalizes bf16 to f32 BEFORE SPMD
    partitioning, so f32 collectives in a bf16-compute program are counted
    at 2 bytes/element — the width the TPU (native bf16) would move. Raw
    values are preserved by the caller for comparison."""
    g = _group_size(instr, n_devices)
    frac = (g - 1) / g if g > 1 else 0.0
    out_bytes = instr.result_bytes
    # operand bytes: sum of operand defs if resolvable, else result bytes
    op_names = []
    paren = instr.rest.split(")")[0]
    op_names = [n for n in _OPERAND_RE.findall(paren)]
    in_bytes = sum(defs[n].result_bytes for n in op_names if n in defs) or out_bytes
    scale = 0.5 if (logical_bf16 and instr.result_type.startswith("f32")) \
        else 1.0
    if instr.op == "all-gather":
        return out_bytes * frac * scale
    if instr.op == "all-reduce":
        return 2.0 * in_bytes * frac * scale
    if instr.op == "reduce-scatter":
        return in_bytes * frac * scale
    if instr.op == "all-to-all":
        return in_bytes * frac * scale
    if instr.op == "collective-permute":
        return in_bytes * scale
    return 0.0


@dataclass
class HloCosts:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    hbm_bytes_once: float = 0.0   # loop-amortized traffic (see _is_slice_op):
                                  # a dynamic-(update-)slice touches ONE slice
                                  # per iteration -> one full buffer per loop
                                  # execution, NOT buffer x trip_count
    collective_bytes: float = 0.0
    collective_counts: dict = field(default_factory=dict)     # op -> count (trip-weighted)
    collective_bytes_by_op: dict = field(default_factory=dict)
    transcendentals: float = 0.0
    while_trips: list[float] = field(default_factory=list)

    def as_dict(self) -> dict:
        return dict(flops=self.flops, hbm_bytes=self.hbm_bytes,
                    collective_bytes=self.collective_bytes,
                    collective_counts=dict(self.collective_counts),
                    collective_bytes_by_op=dict(self.collective_bytes_by_op),
                    transcendentals=self.transcendentals,
                    while_trips=list(self.while_trips))


def _is_slice_op(instr: "Instr") -> bool:
    if instr.op in ("dynamic-update-slice", "dynamic-slice"):
        return True
    return instr.op == "fusion" and ("dynamic-update-slice" in instr.name
                                     or "dynamic-slice" in instr.name
                                     or "dynamic_update_slice" in instr.name)


_TRANSCENDENTAL_FUSION_HINT = re.compile(
    r"(exponential|tanh|logistic|rsqrt|sqrt|log|sine|cosine|erf|power)")

# ops whose result is written to HBM (skip pure bookkeeping ops)
_NO_TRAFFIC_OPS = {"parameter", "constant", "get-tuple-element", "tuple",
                   "bitcast", "after-all", "partition-id", "replica-id",
                   "iota"}


def analyze_hlo_text(text: str, n_devices: int = 1,
                     logical_bf16: bool = False) -> HloCosts:
    comps = parse_hlo_computations(text)
    entry = next((c for c in comps.values() if c.is_entry), None)
    if entry is None:
        return HloCosts()

    memo: dict[str, HloCosts] = {}

    def comp_cost(name: str) -> HloCosts:
        if name in memo:
            return memo[name]
        comp = comps.get(name)
        out = HloCosts()
        memo[name] = out           # cycles impossible in HLO, safe pre-bind
        if comp is None:
            return out
        defs = comp.by_name()
        params_types = {i.name: i.result_type for i in comp.instrs
                        if i.op == "parameter"}
        for instr in comp.instrs:
            op = instr.op
            if op == "while":
                trip = 1.0
                mt = _TRIP_RE.search(instr.rest)
                if mt:
                    trip = float(mt.group(1))
                out.while_trips.append(trip)
                called = _CALLS_RE.findall(instr.rest)
                for cn in called:
                    sub = comp_cost(cn)
                    out.flops += trip * sub.flops
                    out.hbm_bytes += trip * sub.hbm_bytes
                    # slice traffic amortizes over the loop: one buffer total
                    out.hbm_bytes += sub.hbm_bytes_once
                    out.collective_bytes += trip * sub.collective_bytes
                    out.transcendentals += trip * sub.transcendentals
                    for k, v in sub.collective_counts.items():
                        out.collective_counts[k] = out.collective_counts.get(k, 0) + trip * v
                    for k, v in sub.collective_bytes_by_op.items():
                        out.collective_bytes_by_op[k] = out.collective_bytes_by_op.get(k, 0) + trip * v
                    out.while_trips.extend(sub.while_trips)
                continue
            if op in ("call", "conditional", "async-start"):
                for cn in _CALLS_RE.findall(instr.rest):
                    sub = comp_cost(cn)
                    out.flops += sub.flops
                    out.hbm_bytes += sub.hbm_bytes
                    out.hbm_bytes_once += sub.hbm_bytes_once
                    out.collective_bytes += sub.collective_bytes
                    out.transcendentals += sub.transcendentals
                    for k, v in sub.collective_counts.items():
                        out.collective_counts[k] = out.collective_counts.get(k, 0) + v
                    for k, v in sub.collective_bytes_by_op.items():
                        out.collective_bytes_by_op[k] = out.collective_bytes_by_op.get(k, 0) + v
                continue
            if op in _NO_TRAFFIC_OPS:
                continue

            if op == "dot":
                out.flops += _dot_flops(instr, defs, params_types)
            elif op == "convolution":
                out.flops += _conv_flops(instr, defs)
            elif op == "fusion":
                out.flops += instr.result_elems          # ~1 flop/output elem
                if _TRANSCENDENTAL_FUSION_HINT.search(instr.rest):
                    out.transcendentals += instr.result_elems
                # fusions may wrap dots (kOutput fusions): recurse for flops only
                for cn in _CALLS_RE.findall(instr.rest):
                    sub_comp = comps.get(cn)
                    if sub_comp:
                        sdefs = sub_comp.by_name()
                        sparams = {i.name: i.result_type for i in sub_comp.instrs
                                   if i.op == "parameter"}
                        for si in sub_comp.instrs:
                            if si.op == "dot":
                                out.flops += _dot_flops(si, sdefs, sparams)
                            elif si.op == "convolution":
                                out.flops += _conv_flops(si, sdefs)
            elif op in COLLECTIVE_OPS:
                b = _collective_bytes(instr, defs, n_devices, logical_bf16)
                out.collective_bytes += b
                out.collective_counts[op] = out.collective_counts.get(op, 0) + 1
                out.collective_bytes_by_op[op] = out.collective_bytes_by_op.get(op, 0) + b
            elif op in ("all-gather-start", "all-reduce-start",
                        "collective-permute-start"):
                base = op.replace("-start", "")
                fake = Instr(instr.name, instr.result_type, base, instr.rest)
                b = _collective_bytes(fake, defs, n_devices, logical_bf16)
                out.collective_bytes += b
                out.collective_counts[base] = out.collective_counts.get(base, 0) + 1
                out.collective_bytes_by_op[base] = out.collective_bytes_by_op.get(base, 0) + b

            # HBM traffic: every surviving instruction materializes its
            # result — except sliced loop buffers, which amortize (above)
            if op in ("all-gather-done", "all-reduce-done",
                      "collective-permute-done", "copy-done", "copy-start"):
                pass
            elif _is_slice_op(instr):
                out.hbm_bytes_once += instr.result_bytes
            else:
                out.hbm_bytes += instr.result_bytes
        return out

    total = comp_cost(entry.name)
    # entry-level amortized slices count once; parameters are read once
    total.hbm_bytes += total.hbm_bytes_once
    total.hbm_bytes_once = 0.0
    for instr in comps[entry.name].instrs:
        if instr.op == "parameter":
            total.hbm_bytes += instr.result_bytes
    return total


def analyze_compiled(compiled, n_devices: int = 1) -> HloCosts:
    return analyze_hlo_text(compiled.as_text(), n_devices=n_devices)
