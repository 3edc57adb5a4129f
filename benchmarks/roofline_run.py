"""§Roofline table from the dry-run artifact (dryrun_results.json).

Prints per (arch × shape × mesh): the three roofline terms, the dominant
bottleneck, MODEL_FLOPS/HLO_FLOPs usefulness ratio, and a one-line lever.
"""

from __future__ import annotations

import json
import os

from repro.configs import ARCH_FAMILY, full_config, shape_table
from repro.roofline.analysis import model_flops

from .common import emit

RESULTS = os.path.join(os.path.dirname(__file__), "..",
                       "dryrun_results.json")


def _lm_params(cfg, active_only=False):
    D, F, V, L = cfg.d_model, cfg.d_ff, cfg.vocab, cfg.n_layers
    H, Hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    attn = D * (H * hd) + 2 * D * (Hk * hd) + (H * hd) * D
    if cfg.moe is not None:
        e = cfg.moe
        per_exp = 3 * D * e.d_ff_expert
        routed = per_exp * (e.top_k if active_only else e.n_experts)
        ffn = routed + per_exp * e.n_shared + D * e.n_experts
    else:
        ffn = 3 * D * F
    return L * (attn + ffn) + 2 * V * D


def _tokens(arch, shape):
    p = shape_table("lm")[shape].params
    if shape in ("decode_32k", "long_500k"):
        return p["global_batch"]                    # one new token per seq
    return p["global_batch"] * p["seq_len"]


def useful_flops(arch: str, shape: str, n_dev: int) -> float | None:
    if ARCH_FAMILY[arch] != "lm":
        return None
    cfg = full_config(arch)
    kind = "train" if shape == "train_4k" else "serve"
    n = _lm_params(cfg, active_only=True)
    return model_flops(kind, n_active_params=n,
                       tokens=_tokens(arch, shape)) / n_dev


def lever(dominant: str, cell: str) -> str:
    if dominant == "collective":
        return ("reshape TP->DP/ZeRO or sequence-shard activations; "
                "overlap the exchange")
    if dominant == "memory":
        return ("raise arithmetic intensity: fuse/bigger tiles, bf16 "
                "payloads, cut remat rereads")
    return "already MXU-bound: tighten block shapes to keep MXU hot"


def run(path: str = RESULTS):
    with open(path) as f:
        data = json.load(f)
    print(f"{'cell':42s} {'mesh':8s} {'comp_s':>9s} {'mem_s':>9s} "
          f"{'coll_s':>9s} {'dominant':>10s} {'useful/HLO':>10s}")
    for r in sorted(data["results"], key=lambda r: (r["cell"], r["mesh"])):
        rf = r["roofline"]
        arch, shape = r["cell"].split("@")
        uf = useful_flops(arch, shape, r["n_devices"])
        hlo_flops = (r["cost"]["flops"] or 0) * rf.get("loop_factor", 1)
        ratio = uf / hlo_flops if uf and hlo_flops else None
        print(f"{r['cell']:42s} {r['mesh']:8s} {rf['compute_s']:9.2e} "
              f"{rf['memory_s']:9.2e} {rf['collective_s']:9.2e} "
              f"{rf['dominant']:>10s} "
              f"{('%.2f' % ratio) if ratio else '-':>10s}")
    doms = {}
    for r in data["results"]:
        doms[r["roofline"]["dominant"]] = doms.get(
            r["roofline"]["dominant"], 0) + 1
    emit("roofline_cells", 0.0,
         ";".join(f"{k}={v}" for k, v in sorted(doms.items())))


if __name__ == "__main__":
    run()
