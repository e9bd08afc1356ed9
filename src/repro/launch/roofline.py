"""Roofline analysis: compute / memory / collective terms per dry-run cell.

Hardware model: the published per-chip peaks in :data:`PEAKS`, keyed by
``device_kind``; the dry-run cells model :data:`MODEL_DEVICE` (TPU v5e).

    compute_s    = FLOPs / (chips * peak FLOP/s)
    memory_s     = HBM_bytes / (chips * HBM bytes/s)
    collective_s = collective_bytes / (ICI bytes/s per link)

FLOPs and HBM bytes are ANALYTIC, derived from the architecture and cell
shape: ``compiled.cost_analysis()`` counts every ``lax.scan`` body once
(layer stack, microbatch accumulation, attention chunks), so its raw
numbers undercount by the trip counts — we report them alongside for
reference, with the analytic model as the roofline source (the
MODEL_FLOPS ratio makes the bookkeeping auditable).  Collective bytes
come from the compiled HLO (per-shard operand sums x chips).

Conventions (documented per DESIGN.md):
  * attention FLOPs count the chunked implementation as written — full
    S^2 masked blocks (the causal-skip optimization is a §Perf item);
  * training FLOPs = 4x forward under full remat ("nothing" policy:
    1 fwd + 1 recompute-fwd + ~2 bwd) + optimizer QR cost;
  * MODEL_FLOPS = 6 * N_active * D (the napkin number) — the ratio
    MODEL/HLO exposes remat, attention and capacity-factor overheads.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import math
import os
from typing import Optional

import numpy as np

from repro.configs import ARCHS, SHAPES, get_config
from repro.configs.base import ModelConfig, ShapeConfig



@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published peaks of one chip."""

    flops: float     # bf16 FLOP/s
    hbm_bw: float    # HBM bytes/s
    ici_bw: float    # chip-to-chip bytes/s per link
    source: str


#: Per-chip peaks keyed by ``jax.devices()[0].device_kind``.
PEAKS = {
    "TPU v5 lite": DevicePeaks(
        flops=197e12, hbm_bw=819e9,
        # 1,600 Gbit/s of interconnect per chip over its four links
        ici_bw=1600e9 / 8 / 4,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI'),
}

#: The chip the dry-run cells and the tuner's relative pruning model.
MODEL_DEVICE = "TPU v5 lite"


def peaks(device_kind: str = MODEL_DEVICE) -> DevicePeaks:
    """Peaks of ``device_kind``; a kind not in :data:`PEAKS` is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to roofline.PEAKS "
                       f"with their source") from None


__all__ = ["analytic_cell_cost", "roofline_row", "build_table", "main",
           "modeled_seconds", "qr_flops", "DevicePeaks", "PEAKS",
           "MODEL_DEVICE", "peaks"]


# ----------------------------------------------------- generic roofline

def qr_flops(m: int, n: int) -> float:
    """Householder QR flop count: ``2 k^2 (max(m, n) - k/3)`` with
    ``k = min(m, n)`` — the effective-GFLOPs convention the QR benches
    use, here shared with the tuner's candidate pruning."""
    k = min(m, n)
    return 2.0 * k * k * (max(m, n) - k / 3.0)


def modeled_seconds(flops: float, hbm_bytes: float, *, chips: int = 1,
                    device_kind: str = MODEL_DEVICE) -> float:
    """Roofline lower bound on one kernel: the dominant of the compute
    and HBM terms under ``device_kind``'s peaks.  The tuner uses it
    *relatively* (prune candidates whose bound already loses by a wide
    factor), where the asymptotics carry over across backends."""
    pk = peaks(device_kind)
    return max(flops / (chips * pk.flops), hbm_bytes / (chips * pk.hbm_bw))


# ------------------------------------------------------------- flop model

def _attn_flops(cfg: ModelConfig, t: int, s_ctx: int,
                window: Optional[int] = None) -> float:
    """One attention layer on t query tokens against s_ctx keys."""
    d, dq, dkv = cfg.d_model, cfg.d_q, cfg.d_kv
    proj = 2 * t * d * (dq + 2 * dkv) + 2 * t * dq * d
    frac = 1.0
    if getattr(cfg, "attn_causal_skip", False) and t > 1:
        c = max(cfg.seq_chunk, 1024)
        nk = max(1, s_ctx // c)
        if window is not None:
            frac = min(1.0, (window / c + 2) / nk)
        else:
            frac = (nk + 1) / (2.0 * nk)    # lower-triangular blocks only
    scores_av = 4 * t * s_ctx * dq * frac   # QK^T + AV
    return proj + scores_av


def _ffn_flops(cfg: ModelConfig, t: int) -> float:
    mats = 3 if cfg.ffn_act in ("swiglu", "geglu") else 2
    return 2 * mats * t * cfg.d_model * cfg.d_ff


def _moe_flops(cfg: ModelConfig, t: int) -> float:
    moe = cfg.moe
    cap = max(8, min(t, math.ceil(t * moe.top_k / moe.num_experts
                                  * moe.capacity_factor + 7) // 8 * 8))
    mats = 3 if cfg.ffn_act in ("swiglu", "geglu") else 2
    routed = 2 * mats * (moe.num_experts * cap) * cfg.d_model * moe.d_expert
    shared = 2 * mats * t * cfg.d_model * (moe.num_shared * moe.d_expert)
    router = 2 * t * cfg.d_model * moe.num_experts
    return routed + shared + router


def _mamba_flops(cfg: ModelConfig, t: int) -> float:
    d = cfg.d_model
    di = cfg.d_inner or 2 * d
    ds = cfg.d_state
    dtr = cfg.dt_rank or math.ceil(d / 16)
    proj = 2 * t * d * 2 * di + 2 * t * di * d
    conv = 2 * t * di * cfg.conv_kernel
    ssm_in = 2 * t * di * (dtr + 2 * ds) + 2 * t * dtr * di
    scan = 8 * t * di * ds
    return proj + conv + ssm_in + scan


def _mlstm_flops(cfg: ModelConfig, t: int) -> float:
    d = cfg.d_model
    di = int(cfg.mlstm_proj_factor * d)
    h = cfg.n_heads
    dh = di // h
    proj = 2 * t * d * 2 * di + 2 * t * di * d
    qkv = 3 * 2 * t * di * dh                  # block-diagonal per head
    cell = 6 * t * h * dh * dh
    return proj + qkv + cell + 2 * t * di * cfg.conv_kernel


def _slstm_flops(cfg: ModelConfig, t: int) -> float:
    d = cfg.d_model
    h = cfg.n_heads
    dh = d // h
    gates = 2 * t * d * 4 * d
    rec = 2 * t * h * dh * 4 * dh
    ffn_dim = int(round(cfg.slstm_ffn_factor * d / 64) * 64)
    return gates + rec + 2 * t * d * d + 6 * t * d * ffn_dim + \
        2 * t * d * cfg.conv_kernel


def _layer_flops(cfg: ModelConfig, spec, t: int, s_ctx: int) -> float:
    mixer = {
        "attn": lambda: _attn_flops(cfg, t, s_ctx),
        "attn_local": lambda: _attn_flops(cfg, t, s_ctx, window=cfg.window),
        "mamba": lambda: _mamba_flops(cfg, t),
        "mlstm": lambda: _mlstm_flops(cfg, t),
        "slstm": lambda: _slstm_flops(cfg, t),
    }[spec.mixer]()
    ffn = {"dense": lambda: _ffn_flops(cfg, t),
           "moe": lambda: _moe_flops(cfg, t),
           "none": lambda: 0.0}[spec.ffn]()
    return mixer + ffn


def _param_counts(cfg: ModelConfig) -> tuple:
    """(total, active) parameter counts — analytic, no allocation."""
    import jax

    from repro.models import active_param_count, init_params, param_count

    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.ShapeDtypeStruct((2,), np.uint32))
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    active = active_param_count(shapes, cfg)
    return total, active


def _qr_optimizer_flops(cfg: ModelConfig) -> float:
    """QR-Muon orthogonalization cost per step (DESIGN.md §3): blocked MHT
    QR (~4 m n^2 with the masked full-width fori) + thin-Q formation."""
    import jax

    from repro.models import init_params
    from repro.optim.qr_muon import is_muon_param

    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.ShapeDtypeStruct((2,), np.uint32))
    total = 0.0
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        if not is_muon_param(path, leaf):
            continue
        lead = int(np.prod(leaf.shape[:-2], initial=1))
        m, n = sorted(leaf.shape[-2:], reverse=True)
        total += lead * 8.0 * m * n * n
    return total


@dataclasses.dataclass
class CellCost:
    flops: float
    hbm_bytes: float
    model_flops: float
    params_total: int
    params_active: int
    tokens: int


def analytic_cell_cost(cfg: ModelConfig, shape: ShapeConfig,
                       kind: str) -> CellCost:
    n_total, n_active = _param_counts(cfg)
    b, s = shape.global_batch, shape.seq_len

    if kind == "decode":
        t, s_ctx, d_tokens = b, s, b
    else:
        t, s_ctx, d_tokens = b * s, s, b * s

    fwd = 0.0
    per_period = cfg.n_layers // len(cfg.period)
    for spec in cfg.period:
        fwd += per_period * _layer_flops(cfg, spec, t, s_ctx)
    head_tokens = b if kind == "prefill" else t
    fwd += 2 * head_tokens * cfg.d_model * cfg.vocab_size
    if cfg.embedding_input and kind != "decode":
        fwd += 2 * t * cfg.d_model * cfg.d_model  # adapter

    if kind == "train":
        flops = 4.0 * fwd + _qr_optimizer_flops(cfg)
        model_flops = 6.0 * n_active * d_tokens
    else:
        flops = fwd
        model_flops = 2.0 * n_active * d_tokens

    # ----------------------------------------------------- traffic model
    if kind == "train":
        # fp32 params+grads+opt read/write (~28 N) + bf16 weight casts per
        # microbatch + activations ~10 passes of (T, d) per layer
        n_micro = 1
        hbm = 28.0 * n_total + 10.0 * cfg.n_layers * t * cfg.d_model * 2
        hbm += 2.0 * n_total * n_micro
    elif kind == "prefill":
        cache = 2 * sum(1 for sp in cfg.period if "attn" in sp.mixer) \
            * per_period * t * cfg.d_kv * 2
        hbm = 2.0 * n_active_traffic(cfg, n_total) + \
            6.0 * cfg.n_layers * t * cfg.d_model * 2 + cache
    else:  # decode: params + full cache read dominate
        n_attn = sum(1 for sp in cfg.period if "attn" in sp.mixer) * per_period
        cache = 2 * n_attn * b * s * cfg.d_kv * 2
        state = _state_bytes(cfg, b)
        hbm = 2.0 * n_active_traffic(cfg, n_total) + cache + state

    return CellCost(flops=flops, hbm_bytes=hbm, model_flops=model_flops,
                    params_total=n_total, params_active=n_active,
                    tokens=d_tokens)


def n_active_traffic(cfg: ModelConfig, n_total: int) -> float:
    """Weights actually read per step (MoE: top-k of expert weights are
    touched per token, but with E*C dispatch all experts stream once)."""
    return float(n_total)


def _state_bytes(cfg: ModelConfig, b: int) -> float:
    per_period = cfg.n_layers // len(cfg.period)
    total = 0.0
    for sp in cfg.period:
        if sp.mixer == "mamba":
            di = cfg.d_inner or 2 * cfg.d_model
            total += per_period * b * di * cfg.d_state * 4 * 2
        elif sp.mixer == "mlstm":
            di = int(cfg.mlstm_proj_factor * cfg.d_model)
            dh = di // cfg.n_heads
            total += per_period * b * cfg.n_heads * dh * dh * 4 * 2
        elif sp.mixer == "slstm":
            total += per_period * b * cfg.d_model * 4 * 8
    return total


# ------------------------------------------------------------- table

def roofline_row(artifact: dict, *, chips: Optional[int] = None,
                 device_kind: str = MODEL_DEVICE) -> dict:
    arch, shape_name = artifact["arch"], artifact["shape"]
    cfg = get_config(arch)
    if artifact.get("variant") == "optimized":
        cfg = cfg.scaled(attn_causal_skip=True)
    shape = SHAPES[shape_name]
    kind = artifact.get("kind", shape.kind)
    chips = chips or artifact.get("devices", 256)
    cost = analytic_cell_cost(cfg, shape, kind)

    # collective bytes in the HLO are per-shard; execution-weighted counts
    # (x while trip counts) when available, else static
    coll = artifact.get("collectives", {})
    coll_per_shard = coll.get("total_weighted_bytes") or coll.get("total_bytes", 0)
    pk = peaks(device_kind)
    compute_s = cost.flops / (chips * pk.flops)
    memory_s = cost.hbm_bytes / (chips * pk.hbm_bw)
    collective_s = coll_per_shard / pk.ici_bw   # per-chip link time
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    bound_s = max(terms.values())
    # THE score: useful-FLOP utilization achievable under the dominant
    # roofline term (perfect-overlap assumption) — "what MFU could this
    # cell reach".  Raising it means either shrinking the dominant
    # non-compute term or shrinking compute waste (remat, masked attention
    # blocks, MoE capacity padding).
    mfu_bound = (cost.model_flops / (chips * pk.flops * bound_s)
                 if bound_s > 0 else 0.0)
    row = dict(
        arch=arch, shape=shape_name, mesh=artifact["mesh"], kind=kind,
        status=artifact["status"], chips=chips,
        flops=cost.flops, hbm_bytes=cost.hbm_bytes,
        collective_bytes_per_shard=coll_per_shard,
        **{k: v for k, v in terms.items()},
        dominant=dominant.replace("_s", ""),
        roofline_fraction=mfu_bound,
        compute_share=compute_s / bound_s if bound_s > 0 else 0.0,
        model_flops=cost.model_flops,
        model_to_hlo=cost.model_flops / cost.flops if cost.flops else 0.0,
        params_total=cost.params_total, params_active=cost.params_active,
        hlo_flops_reported=artifact.get("cost_analysis", {}).get("flops"),
        temp_bytes=artifact.get("memory_analysis", {}).get("temp_size_in_bytes"),
    )
    return row


def build_table(artifact_dir: str, mesh: str = "pod16x16") -> list:
    rows = []
    for path in sorted(glob.glob(os.path.join(artifact_dir, f"*__{mesh}.json"))):
        with open(path) as f:
            art = json.load(f)
        if art["status"] == "ok":
            rows.append(roofline_row(art))
        else:
            rows.append(dict(arch=art["arch"], shape=art["shape"],
                             mesh=art["mesh"], status=art["status"],
                             reason=art.get("reason", art.get("error", ""))))
    return rows


def format_markdown(rows: list) -> str:
    hdr = ("| arch | shape | status | compute_s | memory_s | collective_s | "
           "dominant | roofline_frac | MODEL/HLO |")
    sep = "|" + "---|" * 9
    lines = [hdr, sep]
    for r in rows:
        if r.get("status") != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | {r['status']}"
                         f" | - | - | - | - | - | - |")
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | ok | {r['compute_s']:.3e} | "
            f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | {r['dominant']} | "
            f"{r['roofline_fraction']:.3f} | {r['model_to_hlo']:.3f} |")
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--artifacts", default="benchmarks/artifacts/dryrun")
    ap.add_argument("--mesh", default="pod16x16")
    ap.add_argument("--out", default="benchmarks/artifacts/roofline.json")
    args = ap.parse_args()
    rows = build_table(args.artifacts, args.mesh)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    print(format_markdown(rows))


if __name__ == "__main__":
    main()
