"""Three-term roofline from compiled dry-run artifacts (TPU v5e target).

    compute term    = HLO_FLOPs / (chips × peak FLOP/s bf16)
    memory term     = HLO_bytes / (chips × peak HBM B/s)
    collective term = collective_bytes / (chips × ICI B/s per link)

The peaks come from one table keyed by ``device_kind`` (as JAX reports
it); a device that is not in the table is an error, never a default.

`cost_analysis()` supplies FLOPs/bytes (already per-partition under SPMD);
collective bytes come from parsing the compiled HLO: we sum the *output*
shape bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute op (per-device payloads post-SPMD).
"""

from __future__ import annotations

import re

__all__ = ["PEAKS", "V5E", "peaks", "collective_bytes_from_hlo",
           "roofline_report", "model_flops", "kernel_roofline"]

V5E = "TPU v5 lite"       # jax.devices()[0].device_kind of a v5e chip

# Published per-chip peaks. TPU v5e: Google Cloud documentation, "TPU
# v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# inter-chip interconnect over 4 links (50 GB/s per link).
PEAKS = {
    V5E: {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; raises for a device
    whose published peaks are not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# e.g.  %foo = bf16[16,128,2048]{2,1,0} all-gather(...)
_OP_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9_]+(?:\[[0-9,]*\])?"
    r"(?:\{[^}]*\})?(?:,\s*[a-z0-9_]+\[[0-9,]*\](?:\{[^}]*\})?)*)\)?\s+"
    r"([a-z0-9-]+)\(")


def _shape_bytes(shape_str: str) -> int:
    """'bf16[16,128]{1,0}' (or tuple of) -> total bytes."""
    total = 0
    for m in re.finditer(r"([a-z0-9]+)\[([0-9,]*)\]", shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_from_hlo(hlo_text: str) -> dict:
    """Sum per-device output bytes of every collective op in the HLO."""
    out = {k: {"count": 0, "bytes": 0} for k in _COLL_KINDS}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = _OP_RE.search(stripped)
        if not m:
            continue
        shape_str, opname = m.group(1), m.group(2)
        # normalize fused variants like all-gather-start
        for kind in _COLL_KINDS:
            if opname == kind or opname.startswith(kind + "-"):
                if opname.endswith("-done"):
                    break  # counted at -start
                out[kind]["count"] += 1
                out[kind]["bytes"] += _shape_bytes(shape_str)
                break
    total = sum(v["bytes"] for v in out.values())
    count = sum(v["count"] for v in out.values())
    return {"by_kind": out, "total_bytes": total, "total_count": count}


def model_flops(kind: str, **kw) -> float:
    """Useful-work estimate: 6·N·D for dense LM training (fwd+bwd),
    2·N·D for inference; N = params touched per token (active for MoE)."""
    n_active = kw["n_active_params"]
    tokens = kw["tokens"]
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_active * tokens


def kernel_roofline(direction: str, *, device_kind: str, n: int,
                    d_ell: int = 0, batch: int = 1, itemsize: int = 4,
                    nb: int = 1, cap: int = 0, bin_n: int = 0,
                    measured_us: float = 0.0) -> dict:
    """Analytic roofline bound for one graph-kernel launch.

    Counts the bytes the kernel's tiling *must* move (graph structure +
    payload gathers + destination writes, assuming perfect reuse of
    VMEM-resident blocks) and the combine FLOPs, prices them against
    the HW terms, and reports ``pct_roofline = bound_us /
    measured_us`` — the fraction of the hardware bound actually
    achieved. ``pull`` is the ELL gather (``n × d_ell`` rectangular
    layout); ``pullf`` the frontier-restricted gather over ``rows``
    compacted destinations (pass the padded row capacity as ``n`` —
    only those ELL rows are read and written); ``push`` is the
    two-phase bin reduce (``nb × cap`` padded edge bins + ``nb ×
    bin_n`` accumulators), priced against ``device_kind``'s peaks.
    The ratio is clamped to
    the schema's 1.5 ceiling — anything past ~1.0 means timing noise,
    not physics.
    """
    if direction in ("pull", "pullf"):
        bytes_moved = (n * d_ell * (4 + 4)              # ELL idx + w
                       + n * d_ell * batch * itemsize   # payload gather
                       + n * batch * itemsize)          # dst writes
        flops = n * d_ell * batch
        if direction == "pullf":
            bytes_moved += n * 4                        # compacted row ids
    else:
        bytes_moved = (nb * cap * (4 + 4 + 4)           # src / dst / w
                       + nb * cap * batch * itemsize    # payload gather
                       + nb * bin_n * batch * itemsize)  # accumulators
        flops = nb * cap * batch
    hw = peaks(device_kind)
    bound_us = 1e6 * max(flops / hw["peak_flops"],
                         bytes_moved / hw["hbm_bw"])
    return {"bytes_moved": int(bytes_moved), "flops": int(flops),
            "bound_us": bound_us,
            "pct_roofline": min(bound_us / max(measured_us, 1e-9), 1.5)}


def roofline_report(result: dict, loop_factor: int = 1,
                    device_kind: str = V5E) -> dict:
    """Attach the three terms (seconds) + dominant bottleneck to a dry-run
    result dict (cost analysis is per-partition under SPMD), priced
    against ``device_kind`` — the dry-run's v5e target by default.

    loop_factor: XLA's cost_analysis and the HLO text count a while-loop
    body ONCE, so a scan-over-layers model under-reports loop-resident
    FLOPs/bytes/collectives by ~n_layers. Callers pass the scan trip
    count (transformer cells: n_layers; python-unrolled GNN/recsys: 1).
    Applying the factor to the whole program slightly over-scales the
    loop-external parts (loss/optimizer/embedding, a few % of each term)
    and the layer-internal attention/loss sub-scans remain counted once
    (~10-15% residual undercount on LM compute) — both documented in
    EXPERIMENTS.md §Roofline methodology.
    """
    flops = (result["cost"]["flops"] or 0.0) * loop_factor
    bytes_acc = (result["cost"]["bytes_accessed"] or 0.0) * loop_factor
    coll_bytes = result["collectives"]["total_bytes"] * loop_factor
    hw = peaks(device_kind)
    t_compute = flops / hw["peak_flops"]
    t_memory = bytes_acc / hw["hbm_bw"]
    t_coll = coll_bytes / hw["ici_bw"]
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dominant = max(terms, key=lambda k: terms[k])
    bound = max(terms.values())
    total = max(1e-30, bound)
    return {
        **terms,
        "loop_factor": loop_factor,
        "dominant": dominant.replace("_s", ""),
        "bound_s": bound,
        "compute_fraction_of_bound": t_compute / total,
    }
