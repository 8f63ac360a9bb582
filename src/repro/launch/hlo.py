"""Post-SPMD HLO inspection: collective bytes + roofline terms.

``cost_analysis`` does not expose collective traffic, so we parse the
compiled HLO text and sum operand bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute op.  XLA's cost analysis
also under-counts while-loop (lax.scan) bodies on some backends, so we
independently count per-iteration FLOPs inside while bodies and scale by the
trip count parsed from the loop condition.
"""
from __future__ import annotations

import dataclasses
import re

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict
    count_by_kind: dict

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def collective_bytes(hlo_text: str) -> CollectiveStats:
    """Sum operand bytes of every collective op in the HLO module text."""
    bytes_by = {k: 0 for k in _COLLECTIVES}
    count_by = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.lstrip()
        if "fusion" in stripped.split("(")[0]:
            continue
        for kind in _COLLECTIVES:
            # match "= <ty> kind(" — an op definition, not a reference
            marker = f" {kind}("
            if marker not in stripped:
                continue
            if f" {kind}-start(" in stripped and marker not in stripped:
                continue
            head, _, args = stripped.partition(marker)
            if "=" not in head:
                continue
            # operand shapes are inside the argument list
            arg_str = args.split(")")[0]
            total = 0
            for dtype, dims in _SHAPE_RE.findall(arg_str):
                total += _shape_bytes(dtype, dims)
            if total == 0:
                # some printers omit operand types: fall back to result shape
                m = _SHAPE_RE.search(head)
                if m:
                    total = _shape_bytes(*m.groups())
            bytes_by[kind] += total
            count_by[kind] += 1
            break
    return CollectiveStats(bytes_by, count_by)


def while_trip_counts(hlo_text: str) -> list[int]:
    """Best-effort trip counts of while loops (scan) from known-trip-count
    annotations or constant comparisons in loop conditions."""
    counts = []
    for m in re.finditer(r'known_trip_count=\{?"?n"?[:=](\d+)', hlo_text):
        counts.append(int(m.group(1)))
    if counts:
        return counts
    # fallback: "%constant.N = s32[] constant(K)" referenced by compare in cond
    return counts


def summarize(hlo_text: str) -> dict:
    stats = collective_bytes(hlo_text)
    return {
        "collective_bytes": stats.total_bytes,
        "collective_bytes_by_kind": stats.bytes_by_kind,
        "collective_counts": stats.count_by_kind,
        "while_trip_counts": while_trip_counts(hlo_text),
    }


# ---------------------------------------------------------------------------------
# Full module walk: loop-trip-scaled FLOPs and collective bytes.
#
# XLA's cost_analysis counts while (lax.scan) bodies ONCE (verified
# empirically — see EXPERIMENTS.md §Roofline methodology).  Here we parse the
# module per-computation, attribute dot FLOPs / collective operand bytes to
# their computation, wire up the call graph (fusion/call/while/conditional),
# and evaluate from ENTRY with while bodies multiplied by their trip count
# (read from the loop-condition constant).
# ---------------------------------------------------------------------------------

_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\([^)]*\))?.*\{")
_DEF_RE = re.compile(r"^%?([\w\.\-]+)\s+=\s+([a-z0-9]+)\[([0-9,]*)\]")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_CALL_RE = re.compile(
    r"(?:calls=|to_apply=|body=|condition=|branch_computations=\{)"
    r"(%?[\w\.\-]+(?:,\s*%?[\w\.\-]+)*)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _prod(dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


def _args_of(stripped: str, op: str) -> list[str]:
    """Operand %names of `... op(...)` (first level of parens)."""
    args = stripped.split(f" {op}(", 1)[1]
    depth = 1
    out = []
    for i, ch in enumerate(args):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                out = args[:i]
                break
    return _OPERAND_RE.findall(out)


def parse_computations(hlo_text: str) -> dict:
    """comp name -> {"flops", "coll_bytes", "whiles": [(body, cond)],
    "calls": [names], "max_const": int, "entry": bool}.

    Two passes: (1) collect every op's result shape so untyped operand
    references can be resolved; (2) attribute dot FLOPs, collective operand
    bytes, and call-graph edges per computation.
    """
    lines = hlo_text.splitlines()
    shapes: dict[str, tuple[str, str]] = {}
    for raw in lines:
        m = _DEF_RE.match(raw.strip())
        if m:
            name, dtype, dims = m.groups()
            shapes[name] = (dtype, dims)

    def op_bytes(name: str) -> int:
        if name in shapes:
            return _shape_bytes(*shapes[name])
        return 0

    comps: dict[str, dict] = {}
    cur = None
    for raw in lines:
        line = raw.rstrip()
        if not line.startswith(" ") and line.endswith("{"):
            m = _COMP_RE.match(line.strip())
            if m and ("(" in line or "ENTRY" in line):
                cur = m.group(1)
                comps[cur] = {"flops": 0, "coll_bytes": 0, "whiles": [],
                              "calls": [], "max_const": 0,
                              "entry": line.startswith("ENTRY")}
                continue
        if cur is None:
            continue
        stripped = line.strip()
        if stripped == "}":
            continue
        # dot flops: 2 * prod(result) * prod(lhs contracting dims)
        if " dot(" in stripped and "=" in stripped.split(" dot(")[0]:
            dm = _DEF_RE.match(stripped)
            ops = _args_of(stripped, "dot")
            if dm and ops and ops[0] in shapes:
                _, _, result_dims = dm.groups()
                lhs_dims_s = shapes[ops[0]][1]
                lhs = ([int(x) for x in lhs_dims_s.split(",")]
                       if lhs_dims_s else [])
                cm = _LHS_CONTRACT_RE.search(stripped)
                contract = 1
                if cm and cm.group(1):
                    for d in cm.group(1).split(","):
                        di = int(d)
                        if di < len(lhs):
                            contract *= lhs[di]
                comps[cur]["flops"] += 2 * _prod(result_dims) * contract
        # collectives: sum operand bytes (resolved via the shape table)
        for kind in _COLLECTIVES:
            if f" {kind}(" in stripped and "=" in stripped.split(
                    f" {kind}(")[0]:
                total = sum(op_bytes(n) for n in _args_of(stripped, kind))
                if total == 0:  # fallback: result shape
                    dm = _DEF_RE.match(stripped)
                    if dm:
                        total = _shape_bytes(dm.group(2), dm.group(3))
                comps[cur]["coll_bytes"] += total
                break
        # call graph
        if " while(" in stripped:
            body = re.search(r"body=%?([\w\.\-]+)", stripped)
            cond = re.search(r"condition=%?([\w\.\-]+)", stripped)
            if body and cond:
                comps[cur]["whiles"].append((body.group(1), cond.group(1)))
        else:
            for cm_ in _CALL_RE.finditer(stripped):
                for name in cm_.group(1).split(","):
                    comps[cur]["calls"].append(name.strip().lstrip("%"))
        for c in _CONST_RE.findall(stripped):
            comps[cur]["max_const"] = max(comps[cur]["max_const"], int(c))
    return comps


def walk_stats(hlo_text: str) -> dict:
    """Loop-trip-scaled (flops, collective_bytes) for the whole module."""
    comps = parse_computations(hlo_text)
    memo: dict[str, tuple[int, int]] = {}

    def trip_count(cond: str) -> int:
        c = comps.get(cond)
        return max(1, c["max_const"]) if c else 1

    def eval_comp(name: str, seen: frozenset) -> tuple[int, int]:
        if name in memo:
            return memo[name]
        c = comps.get(name)
        if c is None or name in seen:
            return (0, 0)
        seen = seen | {name}
        fl, by = c["flops"], c["coll_bytes"]
        for callee in c["calls"]:
            f2, b2 = eval_comp(callee, seen)
            fl += f2
            by += b2
        for body, cond in c["whiles"]:
            t = trip_count(cond)
            f2, b2 = eval_comp(body, seen)
            fl += t * f2
            by += t * b2
        memo[name] = (fl, by)
        return memo[name]

    entries = [n for n, c in comps.items() if c["entry"]]
    if not entries:
        entries = list(comps)[:1]
    fl, by = eval_comp(entries[-1], frozenset())
    return {"flops_scaled": fl, "collective_bytes_scaled": by,
            "n_computations": len(comps)}
