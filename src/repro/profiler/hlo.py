"""While-loop-aware HLO cost analysis.

XLA's `compiled.cost_analysis()` counts a while-loop body ONCE, which makes
it useless for scan-over-layers programs (verified in this container: a
scan of 8 matmuls reports the flops of 1). This module re-derives the three
roofline inputs from the optimized per-device HLO text:

  * flops            — dot ops: 2 * prod(out_dims) * prod(contracted dims);
                       elementwise/fusion internals approximated by output
                       element counts (second-order, dominated by dots)
  * hbm_bytes        — fusion-boundary traffic: every top-level op reads its
                       operands and writes its outputs once; fusion internals
                       are free (that is what fusion means)
  * collective wire bytes — per collective op, ring-model bytes on the wire
                       per device (all-gather: (g-1)/g * out, all-reduce:
                       2(g-1)/g * in, reduce-scatter: (g-1)/g * in,
                       all-to-all: (g-1)/g * in, permute: in)

Each while op's body cost is multiplied by its trip count, parsed from the
`constant(N)` in its condition computation (jax lax.scan lowers to exactly
this form). Nested whiles compose. If a trip count cannot be parsed, 1 is
used and the op is recorded in `warnings`.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_NAME_RE = re.compile(r"%([\w\.\-]+)")


def _shape_bytes_elems(type_str: str):
    """Sum bytes and element count over all shapes in a type string
    (handles tuples)."""
    total_b = 0
    total_e = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in DTYPE_BYTES:
            continue
        elems = 1
        if dims:
            for d in dims.split(","):
                elems *= int(d)
        total_e += elems
        total_b += elems * DTYPE_BYTES[dt]
    return total_b, total_e


@dataclasses.dataclass
class OpInfo:
    name: str
    opcode: str
    out_type: str
    operands: list
    attrs: str
    args_text: str = ""
    out_bytes: int = 0
    out_elems: int = 0
    scope: str = ""          # jax op_name path from HLO metadata


# Ops lowered from these source scopes correspond to the Pallas flash kernels
# on the TPU target: their fp32 score/ds tiles live in VMEM, never HBM. The
# fused-HBM model therefore counts only their bf16 tile reads/writes (q/k/v/o
# blocks), which is exactly the Pallas kernel's HBM traffic.
VMEM_SCOPE_RE = re.compile(r"flash_vmem|ssd_vmem|decode_vmem|lbench_vmem")


@dataclasses.dataclass
class Computation:
    name: str
    ops: list


def _parse_computations(hlo: str) -> dict:
    comps = {}
    cur = None
    for raw in hlo.splitlines():
        line = raw.strip()
        # computation header: `%name (params...) -> type {` or `ENTRY %name ...`
        m = re.match(r"(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$",
                     line)
        if m and "=" not in line.split("(")[0]:
            cur = Computation(m.group(1), [])
            comps[cur.name] = cur
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is None or "=" not in line:
            continue
        sm = re.search(r'op_name="([^"]*)"', line)
        scope = sm.group(1) if sm else ""
        # strip metadata (contains parens/brackets that confuse parsing)
        line_nom = re.sub(r",?\s*metadata=\{.*?\}", "", line)
        line_nom = re.sub(r",?\s*backend_config=.*$", "", line_nom)
        m = re.match(
            r"(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\((.*)$",
            line_nom,
        )
        if not m:
            continue
        name, out_type, opcode, rest = m.groups()
        depth = 1
        args = []
        buf = ""
        i = 0
        while i < len(rest) and depth > 0:
            ch = rest[i]
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    args.append(buf)
                    break
            elif ch == "," and depth == 1:
                args.append(buf)
                buf = ""
                i += 1
                continue
            buf += ch
            i += 1
        attrs = rest[i + 1:] if i + 1 < len(rest) else ""
        operands = []
        for a in args:
            nm = _NAME_RE.search(a)
            if nm:
                operands.append(nm.group(1))
        ob, oe = _shape_bytes_elems(out_type)
        cur.ops.append(
            OpInfo(name, opcode, out_type, operands, attrs,
                   ",".join(args), ob, oe, scope)
        )
    return comps


def _dot_flops(op: OpInfo, shape_of: dict) -> float:
    # contracted dim sizes from lhs shape + lhs_contracting_dims
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.attrs)
    if not m or not op.operands:
        return 2.0 * op.out_elems  # fallback
    cdims = [int(x) for x in m.group(1).split(",") if x]
    lhs_type = shape_of.get(op.operands[0], "")
    sm = _SHAPE_RE.search(lhs_type)
    if not sm:
        return 2.0 * op.out_elems
    dims = [int(x) for x in sm.group(2).split(",") if x]
    k = 1
    for c in cdims:
        if c < len(dims):
            k *= dims[c]
    return 2.0 * op.out_elems * k


def _group_size(op: OpInfo, default: int) -> int:
    m = re.search(r"replica_groups=\{\{([0-9,]+)\}", op.attrs)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", op.attrs)
    if m:  # iota format [groups,group_size]
        return int(m.group(2))
    return default


def _cond_trip_count(cond: Computation) -> int | None:
    """jax scans compare the loop counter with a s32[] constant."""
    best = None
    for op in cond.ops:
        if op.opcode == "constant" and op.out_type.startswith("s32"):
            m = re.match(r"\s*(\d+)\s*$", op.args_text or "")
            if m:
                v = int(m.group(1))
                best = v if best is None else max(best, v)
    return best


def _fusion_bytes(op: OpInfo, callee, shape_of: dict) -> float:
    """HBM traffic of a fusion: operands + outputs, EXCEPT
    - an operand whose in-fusion users are all dynamic-slice ops counts as
      the sum of the slice outputs (scan bodies slice the current layer out
      of the stacked params — the fusion reads the slice, not the stack);
    - a fusion whose root is dynamic-update-slice writes the update region,
      not the whole carried buffer (in-place accumulation).
    """
    if callee is None:
        total = sum(
            _shape_bytes_elems(shape_of.get(o, ""))[0] for o in op.operands
        )
        return total + op.out_bytes

    params = {}
    for cop in callee.ops:
        if cop.opcode == "parameter":
            m = re.match(r"\s*(\d+)", cop.args_text or "")
            if m:
                params[int(m.group(1))] = cop.name
    users: dict = defaultdict(list)
    for cop in callee.ops:
        for o in cop.operands:
            users[o].append(cop)

    total = 0.0
    for i, o in enumerate(op.operands):
        full = _shape_bytes_elems(shape_of.get(o, ""))[0]
        pname = params.get(i)
        if pname is not None:
            u = users.get(pname, [])
            if u and all(c.opcode in ("dynamic-slice", "slice") for c in u):
                total += sum(c.out_bytes for c in u)
                continue
            if u and all(
                c.opcode == "dynamic-update-slice" and c.operands
                and c.operands[0] == pname for c in u
            ):
                # buffer updated in place: read side ~ update region
                total += sum(
                    _shape_bytes_elems(shape_of.get(c.operands[1], ""))[0]
                    for c in u if len(c.operands) > 1
                )
                continue
        total += full

    root = callee.ops[-1] if callee.ops else None
    out_b = op.out_bytes
    if root is not None and root.opcode == "dynamic-update-slice" and \
            len(root.operands) > 1:
        out_b = _shape_bytes_elems(shape_of.get(root.operands[1], ""))[0]
    return total + out_b


@dataclasses.dataclass
class HloCostModel:
    flops: float
    hbm_bytes: float           # TPU-fusion model (primary; see below)
    hbm_bytes_raw: float       # CPU-fusion-boundary model (upper bound)
    wire_bytes: float
    collective_by_kind: dict
    warnings: list

    def scaled(self, f: float) -> "HloCostModel":
        return HloCostModel(
            self.flops * f, self.hbm_bytes * f, self.hbm_bytes_raw * f,
            self.wire_bytes * f,
            {k: v * f for k, v in self.collective_by_kind.items()},
            list(self.warnings),
        )


def analyze_hlo(hlo: str, default_group: int = 1) -> HloCostModel:
    comps = _parse_computations(hlo)

    # global shape table (op name -> out type string)
    shape_of = {}
    for c in comps.values():
        for op in c.ops:
            shape_of[op.name] = op.out_type

    warnings: list = []
    memo: dict = {}

    # Some XLA passes (e.g. the "wide" while-loop transform) clone regions
    # without metadata; ops with an empty scope inherit their computation's
    # majority scope so VMEM-kernel regions stay recognized.
    comp_vmem: dict = {}
    for cname, c in comps.items():
        scoped = [op.scope for op in c.ops if op.scope]
        hits = sum(1 for s in scoped if VMEM_SCOPE_RE.search(s))
        comp_vmem[cname] = bool(scoped) and hits * 2 > len(scoped)

    def op_in_vmem_scope(op, comp_name):
        if op.scope:
            return bool(VMEM_SCOPE_RE.search(op.scope))
        return comp_vmem.get(comp_name, False)

    def in_bytes(op):
        return sum(
            _shape_bytes_elems(shape_of.get(o, ""))[0] for o in op.operands
        )

    def cost_of(comp_name: str) -> tuple:
        """Returns (flops, hbm_raw, hbm_fused, wire, coll_dict)."""
        if comp_name in memo:
            return memo[comp_name]
        comp = comps.get(comp_name)
        if comp is None:
            return (0.0, 0.0, 0.0, 0.0, {})
        memo[comp_name] = (0.0, 0.0, 0.0, 0.0, {})  # cycle guard
        flops = 0.0
        raw = 0.0
        fused = 0.0
        wire = 0.0
        coll: dict = defaultdict(float)
        for op in comp.ops:
            oc = op.opcode
            if oc in ("parameter", "constant", "get-tuple-element", "tuple",
                      "bitcast", "copy", "copy-start", "copy-done",
                      "after-all", "partition-id", "replica-id", "iota"):
                continue
            if oc == "fusion":
                m = re.search(r"calls=%?([\w\.\-]+)", op.attrs)
                callee = comps.get(m.group(1)) if m else None
                if m:
                    f2, _r, fu2, w2, c2 = cost_of(m.group(1))
                    flops += f2
                    fused += fu2
                    wire += w2
                    for k, v in c2.items():
                        coll[k] += v
                raw += _fusion_bytes(op, callee, shape_of)
                continue
            if oc == "while":
                mb = re.search(r"body=%?([\w\.\-]+)", op.attrs)
                mc = re.search(r"condition=%?([\w\.\-]+)", op.attrs)
                trip = None
                if mc and mc.group(1) in comps:
                    trip = _cond_trip_count(comps[mc.group(1)])
                if trip is None:
                    trip = 1
                    warnings.append(f"while {op.name}: trip count unknown")
                if mb:
                    f2, r2, fu2, w2, c2 = cost_of(mb.group(1))
                    flops += trip * f2
                    raw += trip * r2
                    fused += trip * fu2
                    wire += trip * w2
                    for k, v in c2.items():
                        coll[k] += trip * v
                continue
            if oc in ("call", "custom-call"):
                m = re.search(
                    r"(?:to_apply|called_computations)=\{?%?([\w\.\-]+)",
                    op.attrs,
                )
                if m:
                    f2, r2, fu2, w2, c2 = cost_of(m.group(1))
                    flops += f2
                    raw += r2
                    fused += fu2
                    wire += w2
                    for k, v in c2.items():
                        coll[k] += v
                b = in_bytes(op) + op.out_bytes
                raw += b
                continue
            if oc == "conditional":
                for m in re.finditer(
                    r"(?:true_computation|false_computation|branch_computations=\{)%?([\w\.\-]+)",
                    op.attrs,
                ):
                    f2, r2, fu2, w2, c2 = cost_of(m.group(1))
                    flops += f2
                    raw += r2
                    fused += fu2
                    wire += w2
                    for k, v in c2.items():
                        coll[k] += v
                continue

            # ---- leaf ops ----
            in_vmem_scope = op_in_vmem_scope(op, comp_name)
            if oc in ("dynamic-slice", "slice", "gather"):
                raw += 2 * op.out_bytes
                if not in_vmem_scope:   # in-kernel tile reads counted at dots
                    fused += 2 * op.out_bytes
                continue
            if oc == "dynamic-update-slice":
                upd = (
                    _shape_bytes_elems(shape_of.get(op.operands[1], ""))[0]
                    if len(op.operands) > 1 else op.out_bytes
                )
                raw += 2 * upd
                # in-kernel accumulator flushes stay in VMEM; the final
                # output write is counted at the consumer's dot operand
                if not in_vmem_scope:
                    fused += 2 * upd
                continue
            if oc == "scatter":
                upd = (
                    _shape_bytes_elems(shape_of.get(op.operands[-1], ""))[0]
                    if op.operands else op.out_bytes
                )
                raw += 2 * upd
                fused += 2 * upd
                continue

            is_coll = None
            for ck in COLLECTIVES:
                if oc.startswith(ck):
                    is_coll = ck
                    break
            if is_coll:
                b_in = in_bytes(op)
                g = _group_size(op, default_group)
                if g <= 1:
                    w = 0.0
                elif is_coll == "all-gather":
                    w = op.out_bytes * (g - 1) / g
                elif is_coll == "all-reduce":
                    w = 2.0 * b_in * (g - 1) / g
                elif is_coll == "reduce-scatter":
                    w = b_in * (g - 1) / g
                elif is_coll == "all-to-all":
                    w = b_in * (g - 1) / g
                else:  # collective-permute
                    w = b_in
                wire += w
                coll[is_coll] += w
                raw += b_in + op.out_bytes
                fused += b_in + op.out_bytes
                continue

            b_in = in_bytes(op)
            if oc == "dot":
                flops += _dot_flops(op, shape_of)
                raw += b_in + op.out_bytes
                if in_vmem_scope:
                    # Pallas-kernel region: only 2-byte tile traffic is HBM;
                    # fp32 score/ds tiles live in VMEM
                    small = 0
                    for o in op.operands:
                        t = shape_of.get(o, "")
                        if t.startswith(("bf16", "f16", "s8", "u8")):
                            small += _shape_bytes_elems(t)[0]
                    if op.out_type.startswith(("bf16", "f16")):
                        small += op.out_bytes
                    fused += small
                else:
                    fused += b_in + op.out_bytes
                continue
            if oc == "convolution":
                flops += 2.0 * op.out_elems
                raw += b_in + op.out_bytes
                fused += b_in + op.out_bytes
                continue
            if oc in ("reduce", "reduce-window", "sort"):
                flops += 1.0 * op.out_elems
                raw += b_in + op.out_bytes
                if not in_vmem_scope:
                    fused += op.out_bytes  # input side fuses with producer
                continue
            # pure elementwise / shape ops: free under TPU fusion model
            if oc in ("exponential", "log", "rsqrt", "sqrt", "tanh",
                      "power", "divide", "logistic", "exponential-minus-one"):
                flops += 4.0 * op.out_elems
            elif oc in ("add", "subtract", "multiply", "negate", "abs",
                        "maximum", "minimum", "compare", "select",
                        "clamp", "and", "or", "xor"):
                flops += 1.0 * op.out_elems
            raw += b_in + op.out_bytes
        result = (flops, raw, fused, wire, dict(coll))
        memo[comp_name] = result
        return result

    entry = _entry(comps)
    if entry is None:
        return HloCostModel(0, 0, 0, 0, {}, ["no entry computation found"])
    flops, raw, fused, wire, coll = cost_of(entry)
    return HloCostModel(flops, fused, raw, wire, coll, warnings)


def _entry(comps: dict):
    """The entry computation: the largest one no op calls."""
    called = set()
    for c in comps.values():
        for op in c.ops:
            for m in re.finditer(
                r"(?:calls|body|condition|to_apply|true_computation|false_computation)=%?([\w\.\-]+)",
                op.attrs,
            ):
                called.add(m.group(1))
            m = re.search(r"branch_computations=\{([^}]*)\}", op.attrs)
            if m:
                for nm in _NAME_RE.finditer(m.group(1)):
                    called.add(nm.group(1))
    entries = [c for c in comps if c not in called]
    return max(entries, key=lambda c: len(comps[c].ops), default=None)


def collective_count(hlo: str) -> int:
    """Cross-device collective ops one execution of the program runs:
    each while body's count times its trip count, an async pair
    (`-start` ... `-done`) once."""
    comps = _parse_computations(hlo)
    memo: dict = {}

    def count(name: str) -> int:
        if name in memo or name not in comps:
            return memo.get(name, 0)
        n = 0
        for op in comps[name].ops:
            oc = op.opcode
            if oc.startswith(COLLECTIVES):
                n += not oc.endswith("-done")
            elif oc == "while":
                mb = re.search(r"body=%?([\w\.\-]+)", op.attrs)
                mc = re.search(r"condition=%?([\w\.\-]+)", op.attrs)
                trip = (_cond_trip_count(comps[mc.group(1)])
                        if mc and mc.group(1) in comps else None)
                n += (trip or 1) * (count(mb.group(1)) if mb else 0)
            else:
                n += sum(count(c) for c in re.findall(
                    r"(?:calls|to_apply|called_computations)=\{?%?([\w\.\-]+)",
                    op.attrs))
        memo[name] = n
        return n

    entry = _entry(comps)
    return count(entry) if entry is not None else 0
