"""Roofline-term extraction from a compiled (dry-run) executable.

Three terms per (arch × shape × mesh), in seconds (per-device formulation —
equivalent to the global form since both numerator and denominator scale by
the chip count):

  compute    = computed_FLOPs_per_device / peak_FLOP/s      (analytical)
  memory     = HBM_bytes_per_device / HBM_bw                (analytical)
  collective = Σ collective operand bytes per device / link_bw
               (parsed from optimized HLO, ×while-loop trip counts)

Why analytical for compute/memory: XLA's cost_analysis counts while bodies
once, so an 80-layer lax.scan model under-reports ~80× (probe in
EXPERIMENTS.md §Dry-run). Raw cost_analysis numbers are retained in every
artifact as a cross-check. Collectives come from the HLO because the
*schedule* (which ops XLA inserted, over which groups) is exactly what we
want to observe; we correct their execution counts with the parsed trip
multipliers from hlo_struct.py.

Operand sizes: optimized HLO prints operands as %refs without shapes, so
operand bytes derive from the output shape and group size:
  all-reduce: out == operand; all-gather: operand = out/g;
  reduce-scatter: operand = out*g; all-to-all, collective-permute: out.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List

from repro.roofline.hlo_struct import (computation_multipliers,
                                       line_computation_index)
from repro.roofline.hw import HW, V5E

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(
    r"\b([a-z]+[0-9]*(?:e[0-9]+m[0-9]+(?:fn)?)?)\[([0-9,]*)\]")
_COLL_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")
_IOTA_RG_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_LIST_RG_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _match_op(line: str):
    for cand in _COLL_OPS:
        if f" {cand}(" in line or f" {cand}-start(" in line:
            return cand
    return None


def parse_collectives(hlo_text: str) -> List[Dict]:
    """One record per collective op, with while-trip execution multipliers."""
    mult = computation_multipliers(hlo_text)
    out = []
    for comp, line in line_computation_index(hlo_text):
        s = line.strip()
        op = _match_op(s)
        if op is None:
            continue
        if s.startswith("ROOT "):
            s = s[5:]
        idx = s.find(f" {op}")
        lhs = s[:idx]
        rhs = s[idx:]
        out_bytes = sum(_shape_bytes(d, dd)
                        for d, dd in _SHAPE_RE.findall(lhs))
        group_size, num_groups = None, None
        m = _IOTA_RG_RE.search(rhs)
        if m:
            num_groups, group_size = int(m.group(1)), int(m.group(2))
        else:
            m = _LIST_RG_RE.search(rhs)
            if m:
                ids = [x for x in m.group(1).split(",") if x.strip()]
                group_size = len(ids)
        g = group_size or 2
        if op == "all-gather":
            opnd = out_bytes / g
        elif op == "reduce-scatter":
            opnd = out_bytes * g
        else:
            opnd = out_bytes
        # ring-model effective bytes per device
        if op == "all-reduce":
            eff = 2 * (g - 1) / g * opnd
        elif op == "all-gather":
            eff = (g - 1) * opnd
        elif op == "reduce-scatter":
            eff = (g - 1) / g * opnd
        elif op == "all-to-all":
            eff = (g - 1) / g * opnd
        else:
            eff = opnd
        k = mult.get(comp, 1)
        out.append({
            "op": op, "computation": comp, "trip_multiplier": k,
            "operand_bytes": opnd, "output_bytes": out_bytes,
            "group_size": group_size, "num_groups": num_groups,
            "total_operand_bytes": opnd * k,
            "total_effective_bytes": eff * k,
        })
    return out


def summarize_collectives(colls: List[Dict]) -> Dict:
    by_op = defaultdict(lambda: {"sites": 0, "executions": 0,
                                 "operand_bytes": 0.0,
                                 "effective_bytes": 0.0})
    for c in colls:
        rec = by_op[c["op"]]
        rec["sites"] += 1
        rec["executions"] += c["trip_multiplier"]
        rec["operand_bytes"] += c["total_operand_bytes"]
        rec["effective_bytes"] += c["total_effective_bytes"]
    total = {k: sum(r[k] for r in by_op.values())
             for k in ("sites", "executions", "operand_bytes",
                       "effective_bytes")}
    return {"by_op": {k: dict(v) for k, v in by_op.items()}, "total": total}


def analyze_compiled(compiled, *, hw: HW = V5E, model_flops: float = None,
                     hlo_text: str = None, analytic: Dict = None) -> Dict:
    """Roofline terms + bookkeeping. ``analytic``: optional dict with
    ``computed_flops_per_device`` and ``bytes_per_device`` from
    roofline.flops (preferred source for compute/memory terms)."""
    cost = compiled.cost_analysis() or {}
    raw_flops = float(cost.get("flops", 0.0))
    raw_bytes = float(cost.get("bytes accessed", 0.0))
    hlo = hlo_text if hlo_text is not None else compiled.as_text()
    colls = parse_collectives(hlo)
    summary = summarize_collectives(colls)

    mem = compiled.memory_analysis()
    mem_fields = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes", "peak_memory_in_bytes"):
        if hasattr(mem, f):
            mem_fields[f] = int(getattr(mem, f))
    live_bytes = (mem_fields.get("argument_size_in_bytes", 0)
                  + mem_fields.get("output_size_in_bytes", 0)
                  + mem_fields.get("temp_size_in_bytes", 0)
                  - mem_fields.get("alias_size_in_bytes", 0))

    flops_dev = (analytic or {}).get("computed_flops_per_device", raw_flops)
    bytes_dev = (analytic or {}).get("bytes_per_device", raw_bytes)
    t_compute = flops_dev / hw.peak_flops_bf16
    t_memory = bytes_dev / hw.hbm_bw
    t_coll = summary["total"]["operand_bytes"] / hw.ici_link_bw
    t_coll_eff = summary["total"]["effective_bytes"] / hw.ici_link_bw
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll, "collective_eff_s": t_coll_eff}
    dominant = max(("compute_s", "memory_s", "collective_s"),
                   key=lambda k: terms[k])
    bound_s = max(t_compute, t_memory, t_coll)
    result = {
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "hlo_raw": {"flops": raw_flops, "bytes_accessed": raw_bytes,
                    "note": "while bodies counted once (see §Dry-run)"},
        "collectives": summary,
        "memory_analysis": mem_fields,
        "live_bytes_per_device": live_bytes,
        "fits_hbm": live_bytes <= hw.hbm_bytes,
        "terms": terms,
        "dominant": dominant,
        "roofline_bound_s": bound_s,
        "hw": hw.name,
    }
    if analytic:
        result["analytic"] = analytic
    if model_flops:
        result["model_flops_per_device"] = model_flops
        result["useful_flops_ratio"] = (model_flops / flops_dev
                                        if flops_dev else 0.0)
        result["mfu_at_bound"] = (model_flops / hw.peak_flops_bf16 / bound_s
                                  if bound_s else 0.0)
    return result
