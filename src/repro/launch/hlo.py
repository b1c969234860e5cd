"""Compiled-HLO accounting: collective traffic by op kind.

Kept apart from ``launch.dryrun`` so tests and tools can parse HLO text
without importing the dry-run module.
"""
import re
from typing import Dict

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b(f64|f32|f16|bf16|s64|s32|s16|s8|u64|u32|u16|u8|pred)\[([0-9,]*)\]")


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output-shape bytes of every collective op in the compiled HLO.

    Handles scalar results (``%x = bf16[8,128] all-gather(...)``), tuple
    results (``%x = (f32[16,16], f32[16,16]) all-to-all(...)``) and async
    ``-start`` forms (whose ``-done`` twin carries no new traffic)."""
    out: Dict[str, int] = {op: 0 for op in COLLECTIVE_OPS}
    count: Dict[str, int] = {op: 0 for op in COLLECTIVE_OPS}
    op_re = re.compile(
        r"=\s+.*?\b(" + "|".join(COLLECTIVE_OPS) + r")(-start)?\(")
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = op_re.search(stripped)
        if not m:
            continue
        known = m.group(1)
        total = 0
        for dt, dims in _SHAPE_RE.findall(stripped[: m.start(1)]):
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        out[known] += total
        count[known] += 1
    out_nonzero = {k: v for k, v in out.items() if v}
    return {"bytes_by_op": out_nonzero,
            "counts": {k: v for k, v in count.items() if v},
            "total_bytes": sum(out.values())}
