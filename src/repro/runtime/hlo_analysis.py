"""Collective-traffic analysis of compiled (post-SPMD) HLO text.

``compiled.cost_analysis()`` does not expose collective bytes, so we parse
the optimized HLO: every ``all-reduce`` / ``all-gather`` / ``reduce-scatter``
/ ``all-to-all`` / ``collective-permute`` op's result shape (which is the
*per-device local* shape after partitioning) is costed with a per-type link
factor (ring all-reduce moves ≈2× payload; gather/scatter/permute ≈1×).
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_LINK_FACTOR = {
    "all-reduce": 2.0,        # ring: reduce-scatter + all-gather, ≈2·R
    "all-gather": 1.0,        # result R, link ≈ R·(n−1)/n
    "reduce-scatter": None,   # result R = D/n, link ≈ D ⇒ factor = group size
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

_GROUPS_RE = re.compile(r"replica_groups=(?:\[[\d,]*\]<=\[\d+\]|\{\{[\d,]+\})")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[([\d,]*)\]<=\[(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _group_size(op_line: str) -> int:
    m = _GROUPS_IOTA_RE.search(op_line)
    if m:
        dims = [int(x) for x in m.group(1).split(",") if x]
        return dims[-1] if dims else 1
    m = _GROUPS_LIST_RE.search(op_line)
    if m:
        return len(m.group(1).split(","))
    return 1

# result shapes before the op name, e.g.:
#   %ar = f32[8,128]{1,0} all-reduce(...)
#   %t = (f32[4]{0}, bf16[2,2]{1,0}) all-gather(...)
_OP_RE = re.compile(
    r"=\s*(?P<result>\([^)]*\)|\S+)\s+(?P<op>" + "|".join(_COLLECTIVES) + r")\(",
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


@dataclass
class CollectiveStats:
    count: int = 0
    result_bytes: int = 0
    link_bytes: float = 0.0


@dataclass
class HloCollectives:
    by_type: dict = field(default_factory=lambda: defaultdict(CollectiveStats))

    @property
    def total_result_bytes(self) -> int:
        return sum(s.result_bytes for s in self.by_type.values())

    @property
    def total_link_bytes(self) -> float:
        return sum(s.link_bytes for s in self.by_type.values())

    def to_dict(self) -> dict:
        return {
            "total_result_bytes": self.total_result_bytes,
            "total_link_bytes": self.total_link_bytes,
            "by_type": {
                k: {"count": v.count, "result_bytes": v.result_bytes,
                    "link_bytes": v.link_bytes}
                for k, v in self.by_type.items()
            },
        }


def _shape_bytes(result: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(result):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str, scale: float = 1.0) -> HloCollectives:
    out = HloCollectives()
    for m in _OP_RE.finditer(hlo_text):
        op = m.group("op")
        nbytes = _shape_bytes(m.group("result"))
        factor = _LINK_FACTOR[op]
        if factor is None:  # reduce-scatter: link bytes ≈ result × group size
            line_end = hlo_text.find("\n", m.end())
            factor = float(_group_size(hlo_text[m.start(): line_end]))
        st = out.by_type[op]
        st.count += 1
        st.result_bytes += int(nbytes * scale)
        st.link_bytes += nbytes * factor * scale
    return out


def count_op(hlo_text: str, opname: str) -> int:
    return len(re.findall(rf"\b{re.escape(opname)}\(", hlo_text))


# ---------------------------------------------------------------------------
# while-loop-aware accounting
#
# XLA's cost_analysis (and a naive text scan) counts a while body's ops ONCE,
# regardless of trip count — with lax.scan over layers that undercounts
# per-layer collectives by ~L×.  We split the HLO into computations, find
# each while's (condition, body, trip_count), and scale body computations by
# their trip counts (nested whiles multiply).
# ---------------------------------------------------------------------------

_COMP_HEAD_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(", re.M)
_WHILE_RE = re.compile(
    r"while\([^)]*\)\s*,?\s*condition=\s*%?([\w\.\-]+)\s*,\s*body=\s*%?([\w\.\-]+)"
)
_TRIP_RE = re.compile(r"constant\((\d+)\)")


def split_computations(hlo_text: str) -> dict[str, str]:
    """Map computation name → its body text (brace-balanced sections).

    Headers look like ``%name (args...) -> result {`` (possibly with nested
    parens/layout braces in the signature), so the opening brace is the last
    ``{`` on the header line; bodies are brace-balanced from there.
    """
    sections: dict[str, str] = {}
    for m in _COMP_HEAD_RE.finditer(hlo_text):
        # only top-level headers: column 0 (op lines inside bodies are indented)
        if m.start() > 0 and hlo_text[m.start() - 1] != "\n":
            continue
        name = m.group(1)
        line_end = hlo_text.find("\n", m.end())
        if line_end < 0:
            line_end = len(hlo_text)
        start = hlo_text.rfind("{", m.end(), line_end + 1)
        if start < 0:
            continue
        depth, i = 0, start
        while i < len(hlo_text):
            c = hlo_text[i]
            if c == "{":
                depth += 1
            elif c == "}":
                depth -= 1
                if depth == 0:
                    break
            i += 1
        sections[name] = hlo_text[start : i + 1]
    return sections


def _trip_count(cond_text: str) -> int:
    """Best-effort loop bound from the condition computation's constant."""
    consts = [int(x) for x in _TRIP_RE.findall(cond_text)]
    consts = [c for c in consts if c > 1]
    return max(consts) if consts else 1


def computation_scales(hlo_text: str) -> dict[str, float]:
    """Execution multiplicity per computation (nested whiles multiply)."""
    sections = split_computations(hlo_text)
    # edges: computation -> (callee_body, trip)
    calls: dict[str, list[tuple[str, int]]] = {name: [] for name in sections}
    for name, body in sections.items():
        for wm in _WHILE_RE.finditer(body):
            cond, wbody = wm.group(1), wm.group(2)
            trip = _trip_count(sections.get(cond, ""))
            calls[name].append((wbody, trip))
    scales: dict[str, float] = {name: 1.0 for name in sections}

    # propagate from entry outward (computations are a DAG of calls)
    def visit(name: str, scale: float, depth=0):
        if depth > 16 or name not in sections:
            return
        scales[name] = max(scales.get(name, 1.0), scale)
        for child, trip in calls.get(name, []):
            visit(child, scale * trip, depth + 1)

    # entry = the computation not referenced as a body/cond: approximate by
    # visiting every section from scale of 1 and whiles multiplying downward.
    referenced = {c for lst in calls.values() for c, _ in lst}
    roots = [n for n in sections if n not in referenced]
    for r in roots:
        visit(r, 1.0)
    return scales


def parse_collectives_scaled(hlo_text: str) -> HloCollectives:
    """Collective traffic with while-body ops scaled by their trip counts."""
    sections = split_computations(hlo_text)
    scales = computation_scales(hlo_text)
    out = HloCollectives()
    for name, body in sections.items():
        sub = parse_collectives(body, scale=scales.get(name, 1.0))
        for op, st in sub.by_type.items():
            agg = out.by_type[op]
            agg.count += st.count
            agg.result_bytes += st.result_bytes
            agg.link_bytes += st.link_bytes
    return out


def cost_analysis_dict(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a plain dict (empty when XLA reports
    nothing)."""
    return dict(compiled.cost_analysis() or {})
