"""Analytic roofline model per (arch x shape x mesh) cell.

Why analytic: XLA's ``cost_analysis()`` on a scanned (``lax.while``) module
counts each loop body ONCE — an 88-layer stack reports ~1/88th of its FLOPs.
The dry-run still proves compilability, supplies ``memory_analysis()`` (buffer
assignment is loop-aware) and the collective *inventory*; the three roofline
terms are computed here from exact per-layer GEMM/attention/recurrence
counts, multiplied out over layers, and cross-validated in
tests/test_roofline.py against ``cost_analysis`` on an UNROLLED reduced
config (scan_layers=False), where XLA's numbers are trustworthy.

All counts are per training/serving STEP, globally, then divided by chip
count; bytes honor the weight format (bf16 / PSI-INT8 1 B / PSI-INT5
0.625 B per weight — the paper's technique directly moves the memory term).
"""
from __future__ import annotations

import dataclasses
import math

from repro.configs import SHAPES, get_config

# TPU v5e hardware constants (denominators of the ANALYTIC roofline, which
# reports no measurement; measured metrics use ``device_peaks``).
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks, the denominators of measured utilization."""
    bf16_flops: float            # FLOP/s
    int8_ops: float              # OP/s
    hbm_bytes_per_s: float


# Keyed by ``jax.Device.device_kind`` (a v5e reports "TPU v5 lite").
# Source: Google Cloud documentation, "TPU v5e" (system architecture page):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(197e12, 393e12, 819e9),
}


def device_peaks(device) -> "DevicePeaks | None":
    """Peaks of the chip ``device`` is, or None off-TPU (a CPU run measures
    no device metric).  A TPU kind missing from the table is an error, not
    a default."""
    if device.platform != "tpu":
        return None
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device.device_kind!r}; "
            f"add it to DEVICE_PEAKS with its source") from None

def _weight_bytes(quant: str) -> float:
    """Serving bytes/param for a quant mode: bf16 for "none", else the
    registered PsiFormat's packed footprint (psi8 1 B, psi5 0.625 B,
    psi4 0.5 B, ...)."""
    if quant in ("", "none", None):
        return 2.0
    from repro.core.psi import get_format
    try:
        return get_format(quant).bytes_per_weight(packed=True)
    except ValueError:
        return 2.0


# Back-compat view of the paper's three named points (tests/docs reference).
WEIGHT_BYTES = {"none": 2.0, "psi8": 1.0, "psi5": 0.625}
ACT_B = 2            # bf16 activations
TRAIN_GEMM_FACTOR = 4.0    # fwd + remat-fwd + 2x bwd
TRAIN_WEIGHT_IO = 28.0     # bytes/param/step: 3 bf16 reads + grad + adam m,v
SERVE_ACT_RW = 8           # residual-stream reads+writes per layer (fused est)
TRAIN_ACT_RW = 20


@dataclasses.dataclass
class CellModel:
    flops: float                 # global FLOPs / step
    hbm_bytes: float             # global HBM bytes / step
    coll_bytes_per_dev: float    # ICI bytes / device / step
    notes: str = ""


def _attn_kv_len(cfg, S):
    if cfg.attn_type == "swa" and cfg.window:
        return min(cfg.window, S)
    return S


def _layer_gemm_flops(cfg, T):
    """Forward GEMM FLOPs for one block (excl. attention score/value dots)."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    fl = 0.0
    if cfg.family == "ssm":
        di, r, N = cfg.d_inner, cfg.resolved_dt_rank, cfg.ssm_state
        fl += 2 * T * d * 2 * di          # in_proj
        fl += 2 * T * cfg.ssm_conv * di   # depthwise conv
        fl += 2 * T * di * (r + 2 * N)    # x_proj
        fl += 2 * T * r * di              # dt_proj
        fl += 8 * T * di * N              # recurrence + y readout
        fl += 2 * T * di * d              # out_proj
        return fl
    # attention projections (attn / xattn / rec blocks handled by caller)
    return fl


def _attn_flops(cfg, T, S_ctx, causal=True):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    fl = 2 * T * d * (hq + 2 * hkv) * hd      # qkv proj
    fl += 2 * T * hq * hd * d                 # out proj
    eff = (S_ctx + 1) / 2 if (causal and T > 1) else S_ctx
    fl += 2 * 2 * T * hq * hd * eff           # scores + values
    return fl


def _mlp_flops(cfg, T, d_ff=None):
    f = d_ff or cfg.d_ff
    n_mat = 3 if cfg.act in ("swiglu", "geglu") else 2
    return 2 * T * cfg.d_model * f * n_mat


def _moe_flops(cfg, T):
    fl = 2 * T * cfg.d_model * cfg.n_experts           # router
    fl += cfg.top_k * _mlp_flops(cfg, T)               # top-k experts
    return fl


def _rec_flops(cfg, T):
    d, dr = cfg.d_model, cfg.resolved_d_rnn
    fl = 2 * T * d * dr * 2          # in_rec + in_gate
    fl += 2 * T * cfg.ssm_conv * dr  # conv
    fl += 2 * T * dr * dr * 2        # rglru gates (wa, wx)
    fl += 10 * T * dr                # recurrence elementwise
    fl += 2 * T * dr * d             # out
    return fl


def forward_flops(cfg, B, S, kind):
    """Global forward FLOPs for one step of this shape."""
    T = B * S if kind != "decode" else B
    S_ctx = _attn_kv_len(cfg, S)
    total = 0.0
    kinds = _layer_kind_list(cfg)
    for k in kinds:
        if k == "attn":
            total += _attn_flops(cfg, T, S_ctx if kind == "decode" else
                                 min(S_ctx, S))
            total += _mlp_flops(cfg, T) if cfg.family != "moe" else _moe_flops(cfg, T)
        elif k == "xattn":
            total += _attn_flops(cfg, T, S_ctx if kind == "decode" else S)
            # cross attention: kv from enc_frames
            d, hd = cfg.d_model, cfg.resolved_head_dim
            total += 2 * T * d * cfg.n_heads * hd * 2
            total += 2 * 2 * T * cfg.n_heads * hd * cfg.enc_frames
            total += _mlp_flops(cfg, T)
        elif k == "rec":
            total += _rec_flops(cfg, T)
            total += _mlp_flops(cfg, T)
        elif k == "mamba":
            total += _layer_gemm_flops(cfg, T)
    # encoder (whisper): full enc stack on frames, every step
    if cfg.family == "encdec":
        Te = B * cfg.enc_frames
        for _ in range(cfg.n_enc_layers):
            total += _attn_flops(cfg, Te, cfg.enc_frames, causal=False)
            total += _mlp_flops(cfg, Te)
    total += 2 * T * cfg.d_model * cfg.vocab_size      # lm head
    return total


def _layer_kind_list(cfg):
    from repro.models.transformer import layer_kinds
    return layer_kinds(cfg)


def _tp_ars_per_layer(cfg) -> float:
    """Average full-activation TP collectives per layer, fwd+bwd (train).
    Dense/MoE block: attn-out AR + mlp-out AR, x2 for backward = 4.
    Mamba: out_proj AR only, x2 = 2.  Hybrid: weighted by pattern."""
    kinds = _layer_kind_list(cfg)
    per = {"attn": 4.0, "xattn": 6.0, "rec": 4.0, "mamba": 2.0}
    return sum(per[k] for k in kinds) / max(len(kinds), 1)


def weight_bytes_total(cfg, quant: str) -> float:
    """Serving-format parameter bytes (quant applies to GEMM weights only;
    norms/scales stay f32 — a ~0.1 % correction, ignored)."""
    n = cfg.param_count()
    return n * _weight_bytes(quant)


def active_weight_bytes(cfg, quant: str) -> float:
    return cfg.active_param_count() * _weight_bytes(quant)


def kv_cache_bytes(cfg, B, S, kv_quant: str = "") -> float:
    C = _attn_kv_len(cfg, S)
    hd = cfg.resolved_head_dim
    n_attn = sum(1 for k in _layer_kind_list(cfg) if k in ("attn", "xattn"))
    # int8 KV: 1 byte/elem + f32 scale per (slot, head) entry
    kv_b = (1 + 4 / hd) if kv_quant == "int8" else ACT_B
    kv = 2 * B * C * cfg.n_kv_heads * hd * kv_b * n_attn
    if cfg.family == "ssm":
        kv += B * cfg.d_inner * cfg.ssm_state * 4 * cfg.n_layers
    if cfg.family == "hybrid":
        n_rec = sum(1 for k in _layer_kind_list(cfg) if k == "rec")
        kv += B * cfg.resolved_d_rnn * 4 * n_rec
    return kv


def analytic_cell(arch: str, shape_name: str, quant: str = "psi8",
                  chips: int = 256, mesh_model: int = 16,
                  tp_on=None, kv_quant: str = "") -> CellModel:
    from repro.runtime.sharding import tp_enabled
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    B, S, kind = shape.global_batch, shape.seq_len, shape.kind
    tp = tp_enabled(cfg) if tp_on is None else tp_on
    T = B * S if kind != "decode" else B

    fwd = forward_flops(cfg, B, S, kind)
    if kind == "train":
        flops = fwd * TRAIN_GEMM_FACTOR
        hbm = (cfg.param_count() * TRAIN_WEIGHT_IO
               + TRAIN_ACT_RW * T * cfg.d_model * ACT_B * cfg.n_layers
               + 3 * T * cfg.vocab_size * ACT_B)          # logits fwd+bwd
        # FSDP param all-gather + grad reduce-scatter over the data axes
        data_ways = chips // mesh_model
        pbytes = 2.0 * cfg.param_count()                  # bf16
        coll_dev = 2 * pbytes / mesh_model if tp else 2 * pbytes / chips
        # TP collectives per layer on (T/data_ways, d) activations, fwd+bwd.
        # Elementwise-recurrent blocks (mamba, rg-lru) keep the channel dim
        # sharded through the scan: fewer boundary collectives.
        if tp:
            act = (T / data_ways) * cfg.d_model * ACT_B
            coll_dev += _tp_ars_per_layer(cfg) * act * cfg.n_layers
        notes = "train: 4x-fwd GEMMs (remat), FSDP gather+scatter, TP ARs"
    elif kind == "prefill":
        flops = fwd
        hbm = (active_weight_bytes(cfg, quant)
               + SERVE_ACT_RW * T * cfg.d_model * ACT_B * cfg.n_layers
               + kv_cache_bytes(cfg, B, S))               # cache write
        data_ways = chips // mesh_model
        coll_dev = 0.0
        if tp:
            act = (T / data_ways) * cfg.d_model * ACT_B
            coll_dev += (_tp_ars_per_layer(cfg) / 2) * act * cfg.n_layers
        notes = "prefill: weights once + cache write + TP ARs"
    else:  # decode
        flops = fwd
        hbm = (active_weight_bytes(cfg, quant)
               + kv_cache_bytes(cfg, B, S, kv_quant)      # cache read
               + SERVE_ACT_RW * T * cfg.d_model * ACT_B * cfg.n_layers)
        coll_dev = 0.0
        if tp:
            act = max(T / (chips // mesh_model), 1) * cfg.d_model * ACT_B
            coll_dev += (_tp_ars_per_layer(cfg) / 2) * act * cfg.n_layers
        notes = "decode: weights + full KV read per token"
    return CellModel(flops=flops, hbm_bytes=hbm,
                     coll_bytes_per_dev=coll_dev, notes=notes)


def roofline_terms(cell: CellModel, chips: int = 256) -> dict:
    t_c = cell.flops / (chips * PEAK_FLOPS)
    t_m = cell.hbm_bytes / (chips * HBM_BW)
    t_x = cell.coll_bytes_per_dev / ICI_BW
    terms = {"compute_s": t_c, "memory_s": t_m, "collective_s": t_x}
    dom = max(terms, key=terms.get)
    bound = terms[dom]
    return {**terms, "bottleneck": dom.replace("_s", ""),
            "bound_s": bound,
            "roofline_fraction": t_c / bound if bound > 0 else 0.0}
