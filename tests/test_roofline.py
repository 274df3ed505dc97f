"""Analytic roofline model: internal consistency + cross-validation against
XLA cost_analysis on an UNROLLED reduced config (where while-body
undercounting doesn't apply)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config, reduced_config, shape_applicable
from repro.models import build_model
from repro.perf.roofline_model import (analytic_cell, forward_flops,
                                       kv_cache_bytes, roofline_terms,
                                       weight_bytes_total)


def test_terms_positive_all_cells():
    for a in ASSIGNED_ARCHS:
        cfg = get_config(a)
        for s, sh in SHAPES.items():
            if not shape_applicable(cfg, sh)[0]:
                continue
            cell = analytic_cell(a, s)
            assert cell.flops > 0 and cell.hbm_bytes > 0, (a, s)
            rt = roofline_terms(cell)
            assert 0 < rt["roofline_fraction"] <= 1.0, (a, s, rt)


def test_decode_is_memory_bound_for_dense():
    """Single-token decode against a deep cache must be memory-bound —
    the regime the paper's technique targets."""
    for a in ("chatglm3-6b", "qwen3-8b", "phi3-medium-14b"):
        rt = roofline_terms(analytic_cell(a, "decode_32k", quant="psi8"))
        assert rt["bottleneck"] == "memory", (a, rt)


def test_psi_reduces_memory_term():
    """The paper's claim, translated to TPU: PSI weight compression moves
    the decode memory roofline."""
    for a in ("qwen3-8b", "granite-34b"):
        t_bf16 = analytic_cell(a, "decode_32k", quant="none").hbm_bytes
        t_psi8 = analytic_cell(a, "decode_32k", quant="psi8").hbm_bytes
        t_psi5 = analytic_cell(a, "decode_32k", quant="psi5").hbm_bytes
        assert t_psi8 < t_bf16 and t_psi5 < t_psi8
        # weights dominate; the full-weight part shrinks 2x / 3.2x
        w = weight_bytes_total(get_config(a), "none")
        assert (t_bf16 - t_psi8) == pytest.approx(w / 2, rel=0.01)


def test_train_flops_near_6nd():
    """Train FLOPs ~= 4x fwd where fwd ~= 2*N*D + attention."""
    cfg = get_config("qwen3-8b")
    sh = SHAPES["train_4k"]
    fwd = forward_flops(cfg, sh.global_batch, sh.seq_len, "train")
    n = cfg.param_count() - cfg.vocab_size * cfg.d_model
    two_nd = 2 * n * sh.global_batch * sh.seq_len
    assert 0.9 < fwd / two_nd < 1.5   # attention + lm head overhead

    moe = get_config("qwen3-moe-30b-a3b")
    fwd_moe = forward_flops(moe, sh.global_batch, sh.seq_len, "train")
    n_act = moe.active_param_count() - moe.vocab_size * moe.d_model
    assert 0.8 < fwd_moe / (2 * n_act * sh.global_batch * sh.seq_len) < 2.0


def test_kv_cache_bytes_swa_bounded():
    mix = get_config("mixtral-8x22b")
    assert (kv_cache_bytes(mix, 1, 524_288)
            == kv_cache_bytes(mix, 1, mix.window))
    dense = get_config("qwen3-8b")
    assert kv_cache_bytes(dense, 1, 65_536) == 2 * kv_cache_bytes(dense, 1, 32_768)


def test_cross_validate_against_unrolled_hlo():
    """Ground truth check: on an UNROLLED reduced config (scan_layers=False,
    no remat), XLA's cost_analysis flops must match forward_flops within
    35 % (layout/padding slack).  This is what justifies using the analytic
    model instead of cost_analysis on scanned modules (DESIGN.md §7)."""
    cfg = reduced_config(get_config("qwen3-8b"),
                         scan_layers=False, remat=False,
                         d_model=128, d_ff=256, n_layers=2, vocab_size=512,
                         head_dim=32, n_heads=4, n_kv_heads=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 4, 128
    batch = {"tokens": jnp.zeros((B, S), jnp.int32)}

    def fwd(p, b):
        return model.forward(p, b)[0]

    compiled = jax.jit(fwd).lower(params, batch).compile()
    hlo_flops = compiled.cost_analysis()["flops"]
    ours = forward_flops(cfg, B, S, "prefill")
    assert 0.65 < ours / hlo_flops < 1.35, (ours, hlo_flops)


def test_scan_undercount_demonstrated():
    """The reason the analytic model exists: the SAME model scanned reports
    far fewer FLOPs from cost_analysis than unrolled."""
    base = dict(d_model=128, d_ff=256, n_layers=8, vocab_size=512,
                head_dim=32, n_heads=4, n_kv_heads=2, remat=False)
    B, S = 2, 64
    batch = {"tokens": jnp.zeros((B, S), jnp.int32)}
    flops = {}
    for scan in (True, False):
        cfg = reduced_config(get_config("qwen3-8b"), scan_layers=scan, **base)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        compiled = jax.jit(
            lambda p, b: model.forward(p, b)[0]).lower(params, batch).compile()
        flops[scan] = compiled.cost_analysis()["flops"]
    assert flops[True] < 0.55 * flops[False]
