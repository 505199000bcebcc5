"""Roofline analysis from compiled dry-run artifacts (no hardware needed).

Three terms per (arch x shape x mesh), in seconds:
  compute    = HLO_FLOPs            / (chips * PEAK_FLOPS)
  memory     = HLO_bytes_accessed   / (chips * HBM_BW)
  collective = collective_bytes     / (chips * LINK_BW)

FLOPs/bytes come from compiled.cost_analysis().  Collective bytes are parsed
from the partitioned HLO text (per-device operand shapes of all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute); since
partitioned shapes are already per-chip, the per-chip collective bytes are
summed directly and divided by LINK_BW (algebraically identical to
global_bytes / (chips * link_bw)).

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link
ICI (assignment-specified).

The module also carries the KERNEL roofline helpers used by
``benchmarks/bench_roofline.py`` (docs/PERFORMANCE.md): a peak HBM bandwidth
table keyed by ``device_kind`` (``peak_hbm_bandwidth``; a device that is not
in the table is an error, never a default) and ``kernel_roofline`` which
turns a measured (bytes_moved, seconds) pair into achieved GB/s and
fraction-of-peak.  This file stays import-light (no jax at module scope) so
the dry-run tooling can run anywhere; the device probe imports jax lazily.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

# Peak HBM bandwidth per jax ``device_kind``, bytes/s.  Source: Google
# Cloud documentation, "TPU v5e" (16 GB of HBM at 819 GB/s per chip).
HBM_BW_BY_DEVICE_KIND = {
    "TPU v5 lite": 819e9,
}


def peak_hbm_bandwidth(device_kind: Optional[str] = None) -> float:
    """Peak HBM bandwidth (bytes/s) of ``device_kind`` (None = the first
    jax device).  ``KeyError`` for a device the table does not know."""
    if device_kind is None:
        import jax   # lazy: keep module importable without a device runtime
        device_kind = jax.devices()[0].device_kind
    try:
        return HBM_BW_BY_DEVICE_KIND[device_kind]
    except KeyError:
        raise KeyError(f"no peak HBM bandwidth for device kind "
                       f"{device_kind!r}; known: "
                       f"{sorted(HBM_BW_BY_DEVICE_KIND)}") from None


def kernel_roofline(bytes_moved: float, seconds: float,
                    device_kind: Optional[str] = None) -> Dict:
    """Achieved-vs-peak HBM bandwidth for one measured kernel invocation.

    ``bytes_moved`` is the kernel's modelled HBM traffic (input bytes times
    the backend-honest pass count from ``kernels.ops.hbm_passes``, plus
    output bytes); ``seconds`` the measured wall-clock.  Returns achieved
    GB/s, the device's peak, and the fraction of peak — the quantity
    bench_roofline reports per (kernel, backend)."""
    peak = peak_hbm_bandwidth(device_kind)
    achieved = bytes_moved / seconds if seconds > 0 else 0.0
    return {
        "bytes_moved": float(bytes_moved),
        "seconds": float(seconds),
        "achieved_gbs": achieved / 1e9,
        "peak_gbs": peak / 1e9,
        "frac_of_peak": achieved / peak if peak else 0.0,
    }

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# a typed tensor literal inside HLO text, e.g. bf16[128,1024]{1,0}
_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


_OP_RE = re.compile(
    r"=\s*(?P<result>\(?[a-z0-9]+\[[0-9,]*\][^=]*?)\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<start>-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def parse_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum per-device *operand* bytes of every collective op, by kind.

    Compiled-module text references operands by name (no inline types), so
    operand bytes are derived from the typed result shape plus the replica
    group size: all-gather operand = result/group; reduce-scatter operand =
    result*group; all-reduce / all-to-all / collective-permute operand =
    result.  Tuple results sum their components.  Async '-done' halves are
    skipped (the '-start' carries the op).
    """
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        if "-done" in line and any(k + "-done" in line for k in _COLLECTIVES):
            continue
        m = _OP_RE.search(line)
        if not m:
            continue
        kind = m.group("op")
        result_seg = m.group("result")
        rb = sum(_shape_bytes(t.group(1), t.group(2))
                 for t in _SHAPE_RE.finditer(result_seg))
        if m.group("start") and kind == "all-gather":
            # start op result is (operand, destination): halve the sum, then
            # treat as the gathered destination
            rb = rb / 2 * 2  # destination dominates; keep as-is conservative
        g = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            g = int(gm.group(2))
        else:
            ge = _GROUPS_EXPLICIT_RE.search(line)
            if ge:
                g = len(ge.group(1).split(","))
        if kind == "all-gather":
            b = rb // max(1, g)
        elif kind == "reduce-scatter":
            b = rb * g
        else:
            b = rb
        out[kind] += int(b)
        out["count"][kind] += 1
    return out


def count_collective_phases(hlo_text: str) -> int:
    """Structural round count: number of collective ops in the entry module
    (data-dependent phases upper bound; reported alongside Table-V rounds)."""
    return sum(parse_collective_bytes(hlo_text)["count"].values())


def roofline_terms(flops: float, bytes_accessed: float,
                   collective_bytes_per_chip: float, chips: int) -> Dict:
    """cost_analysis flops/bytes are per-device in SPMD-partitioned modules;
    we report per-chip times directly (= the parallel wall-clock estimate)."""
    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_accessed / HBM_BW
    collective_s = collective_bytes_per_chip / LINK_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dom = max(terms, key=terms.get)
    terms["dominant"] = dom.replace("_s", "")
    terms["bound_s"] = terms[dom]
    return terms


def model_flops(cfg, tokens: int, kind: str) -> float:
    """6*N_active*D (training) or 2*N_active*D (forward-only serving)."""
    n = cfg.active_param_count()
    mult = 6 if kind == "train" else 2
    return mult * n * tokens
