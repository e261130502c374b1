"""Post-compile HLO analysis: cost, memory, and collective-byte extraction.

``cost_analysis``/``memory_analysis`` come straight from the compiled executable.
Collective bytes are NOT in cost_analysis — we parse the optimized (post-SPMD,
per-device) HLO text and sum the **output bytes** of every collective op. Notes on
the approximation (documented in EXPERIMENTS.md §Roofline):

  * the partitioned module is the per-device program, so parsed byte counts are
    per-device;
  * output bytes are the transfer proxy: exact for all-gather (output = gathered) and
    collective-permute; all-reduce moves ~2·(N−1)/N ≈ 2x its operand bytes on a ring —
    we report raw output bytes and apply the 2x in the roofline term for all-reduce;
  * '-start'/'-done' async pairs are counted once (on the start op).
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Any, Dict

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.:  %ag = bf16[2,128]{1,0} all-gather(%x), replica_groups=...
#        %ar = (f32[16]{0}, f32[16]{0}) all-reduce-start(...)
_OP_RE = re.compile(
    r"=\s*(?P<type>\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"(?P<op>" + "|".join(_COLLECTIVES) + r")(?P<suffix>-start|-done)?\(")

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


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


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Per-device bytes and op counts by collective kind, from optimized HLO."""
    by_kind_bytes: Dict[str, int] = defaultdict(int)
    by_kind_count: Dict[str, int] = defaultdict(int)
    for m in _OP_RE.finditer(hlo_text):
        if m.group("suffix") == "-done":
            continue
        kind = m.group("op")
        by_kind_bytes[kind] += _type_bytes(m.group("type"))
        by_kind_count[kind] += 1
    total = sum(by_kind_bytes.values())
    # ring-transfer proxy: all-reduce moves ~2x its bytes
    weighted = total + by_kind_bytes.get("all-reduce", 0)
    return {
        "bytes_by_kind": dict(by_kind_bytes),
        "count_by_kind": dict(by_kind_count),
        "total_output_bytes": total,
        "ring_weighted_bytes": weighted,
    }


def cost_summary(compiled) -> Dict[str, float]:
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
    except Exception:
        ca = {}
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
    }


def memory_summary(compiled) -> Dict[str, float]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
        if hasattr(ma, field):
            out[field] = float(getattr(ma, field))
    if out:
        # donation (alias) overlaps args and outputs
        out["live_bytes"] = (out.get("argument_size_in_bytes", 0.0)
                             + out.get("output_size_in_bytes", 0.0)
                             + out.get("temp_size_in_bytes", 0.0)
                             - out.get("alias_size_in_bytes", 0.0))
    return out


# ------------------------------------------------------------------ roofline terms
#: Per-chip peaks keyed by ``jax.Device.device_kind``. Source: Google Cloud
#: documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, and 1,600 Gbit/s
#: of chip-to-chip interconnect, i.e. four links of 50 GB/s (the collective
#: term charges one link).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def device_peaks(device_kind: str) -> Dict[str, float]:
    """Peak FLOP/s and bytes/s of one chip of ``device_kind``.

    Raises:
        ValueError: no published peaks are recorded for ``device_kind``.
    """
    if device_kind not in PEAKS:
        raise ValueError(f"no peak rates recorded for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


def roofline_terms(flops_per_device: float, bytes_per_device: float,
                   collective_bytes_per_device: float, *,
                   device_kind: str) -> Dict[str, Any]:
    peak = device_peaks(device_kind)
    compute_s = flops_per_device / peak["flops"]
    memory_s = bytes_per_device / peak["hbm_bw"]
    collective_s = collective_bytes_per_device / peak["ici_bw"]
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    bottleneck = max(terms, key=terms.get)
    total = max(sum(terms.values()), 1e-30)
    return {
        **terms,
        "bottleneck": bottleneck.replace("_s", ""),
        "bound_fraction": terms[bottleneck] / total,
        "step_lower_bound_s": max(terms.values()),   # perfect-overlap model
    }
