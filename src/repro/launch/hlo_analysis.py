"""Extract roofline inputs from a compiled SPMD module.

- flops / bytes: compiled.cost_analysis() (per-device in SPMD).
- collective bytes: parse the HLO text; for each all-gather / all-reduce /
  reduce-scatter / all-to-all / collective-permute instruction, sum the byte
  sizes of its *operands* (assignment spec).  Operand types are resolved from
  their defining instructions.

Known caveat (DESIGN.md §6): XLA cost analysis counts while-loop (lax.scan)
bodies ONCE.  The dry-run therefore (a) unrolls the layer loop where
feasible, and (b) uses the depth-probe extrapolation: compile the same step
at two reduced depths L1 < L2, fit flops = a + b*L, report a + b*L_full.
"""
from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=\s*(\(?[^)=]*?\)?)\s*([\w\-]+)\(")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective-kind operand bytes, plus 'total'."""
    # map instruction name -> result byte size
    sizes: dict[str, int] = {}
    lines = hlo_text.splitlines()
    for ln in lines:
        m = re.match(r"\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=\s*((?:\([^=]*?\))|(?:[\w\[\],{}\/#:\s]*?))\s[\w\-]+\(", ln)
        if m:
            sizes[m.group(1).lstrip("%")] = _type_bytes(m.group(2))

    out: dict[str, float] = defaultdict(float)
    for ln in lines:
        for kind in COLLECTIVES:
            # match e.g. "%ag = bf16[...] all-gather(%x)", avoid -start/-done fusions duplicates
            if re.search(rf"\s{kind}(?:-start)?\(", ln):
                ops = re.findall(r"\(([^)]*)\)", ln)
                if not ops:
                    continue
                args = ops[0]
                total = 0
                for arg in args.split(","):
                    arg = arg.strip().lstrip("%")
                    # operand may be printed with its own type: "bf16[8,16] %p.1"
                    if " " in arg:
                        ty, _, nm = arg.rpartition(" ")
                        b = _type_bytes(ty) or sizes.get(nm.lstrip("%"), 0)
                    else:
                        b = sizes.get(arg, 0)
                    total += b
                out[kind] += total
                break
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return dict(out)


def collective_counts(hlo_text: str) -> dict:
    out = {}
    for kind in COLLECTIVES:
        out[kind] = len(re.findall(rf"\s{kind}(?:-start)?\(", hlo_text))
    return out


def cost_summary(compiled) -> dict:
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    mem = {}
    if ma is not None:
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            mem[f] = getattr(ma, f, 0)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "memory": mem,
    }
