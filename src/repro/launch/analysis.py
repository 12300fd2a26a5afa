"""Compiled-artifact analysis: cost, memory, and collective bytes.

``collective_bytes`` parses the optimized HLO text and sums the operand
sizes of every cross-device collective (all-gather, all-reduce,
reduce-scatter, all-to-all, collective-permute) — the quantity
``cost_analysis()`` does not report, needed for the roofline's collective
term.  Shapes are parsed from the HLO type syntax (``bf16[16,1024]{...}``).
"""

from __future__ import annotations

import re
from typing import Any, Dict

__all__ = [
    "collective_bytes",
    "cost_summary",
    "memory_summary",
    "DTYPE_BYTES",
    "PEAKS",
    "peaks",
]

DTYPE_BYTES = {
    "pred": 1,
    "s4": 0.5, "u4": 0.5,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# Per-chip roofline denominators, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB
# of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect per chip
# (four links, so 50 GB/s each).  ``dcn_bw`` is not in that table: it is
# this repo's assumption of 25 GB/s per host shared by four chips.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,   # FLOP/s
        "hbm_bw": 819e9,             # B/s
        "ici_bw": 50e9,              # B/s per link
        "dcn_bw": 6.25e9,            # B/s per chip across pods (assumed)
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)


def _parse_shape_bytes(type_str: str) -> float:
    """Bytes of one HLO type like ``bf16[16,1024]`` (tuples handled upstream)."""
    total = 0.0
    for m in _SHAPE_RE.finditer(type_str):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum output-shape bytes per collective kind over the optimized HLO.

    Uses the *result* shape of each collective op (for all-gather this is the
    gathered size; for all-reduce the reduced tensor; for reduce-scatter the
    scattered shard) — a consistent, conservative proxy for bytes moved per
    device.  Fusion-internal ops are not collectives, so line-level scanning
    is exact for this purpose.
    """
    out: Dict[str, float] = {k: 0.0 for k in _COLLECTIVE_OPS}
    out["count"] = 0.0
    for line in hlo_text.splitlines():
        s = line.strip()
        # match `%name = TYPE op-name(...)` forms; skip -start/-done pairs'
        # duplicates by counting only the -start (or the sync form)
        for op in _COLLECTIVE_OPS:
            if f" {op}(" in s or f" {op}-start(" in s:
                lhs = s.split("=", 1)
                if len(lhs) != 2:
                    continue
                # result type is between '=' and the op name
                rhs = lhs[1]
                idx = rhs.find(op)
                type_str = rhs[:idx]
                out[op] += _parse_shape_bytes(type_str)
                out["count"] += 1
                break
    out["total"] = sum(v for k, v in out.items() if k in _COLLECTIVE_OPS)
    return out


def cost_summary(compiled: Any) -> Dict[str, float]:
    """Normalize cost_analysis() across jax versions (dict or list-of-dict)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    flops = float(ca.get("flops", 0.0))
    bytes_accessed = float(ca.get("bytes accessed", 0.0))
    return {"flops": flops, "bytes_accessed": bytes_accessed, "raw_keys": len(ca)}


def memory_summary(compiled: Any) -> Dict[str, float]:
    ma = compiled.memory_analysis()
    out: Dict[str, float] = {}
    for key in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "peak_memory_in_bytes",
        "generated_code_size_in_bytes",
    ):
        out[key] = float(getattr(ma, key, 0.0))
    out["total_hbm_bytes"] = (
        out["argument_size_in_bytes"]
        + out["output_size_in_bytes"]
        + out["temp_size_in_bytes"]
        - out["alias_size_in_bytes"]
    )
    return out
