"""Differential fuzz harness for the fused paged-decode attention kernel.

Three implementations of the same read-side contract live in
``repro.kernels.paged_attention``:

  * ``paged_attention_ref``     — the pure-XLA oracle (the token-identity
                                  reference; the exact math of the pre-kernel
                                  gather path);
  * ``paged_attention_gather``  — the dense-gather GPU fast path;
  * ``paged_attention_pallas``  — the flash-decode Pallas kernel (tested in
                                  interpret mode: the kernel body runs on CPU).

The property tests drive randomized block tables (holes / −1 entries,
permuted physical blocks, inactive all-−1 slots, stale garbage in every
unreferenced pool location, positions at block boundaries 0 / bs−1 / bs)
and assert kernel ≡ oracle ≡ gather to fp32 accumulation-order tolerance —
and *exactly* for the masking pattern: rewriting every causally-invisible
pool entry must not change a single output bit.

Also here: the scatter-overflow regression (a position past the block
table's extent must write to the slot's scratch block, never clamp into
the last logical block) and the routed-block-vs-legacy-gather-path
equivalence that pins serving token identity across the PR.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config, reduced_config
from repro.kernels import ops
from repro.kernels import paged_attention as pa
from repro.models import attention

BS = 4            # block size of the synthetic pools
HQ, HKV, HD = 8, 2, 16


# ---------------------------------------------------------------------------
# Randomized case construction.
# ---------------------------------------------------------------------------
def _case(seed, B, n_bt, mode):
    """Pools full of garbage everywhere; tables with permuted physical
    blocks, holes, and (sometimes) an inactive slot; boundary-heavy
    positions.  mode in {"f32", "bf16", "int8"}."""
    rng = np.random.default_rng(seed)
    N = B * n_bt + B                                  # + per-slot scratch
    act = jnp.float32 if mode == "f32" else jnp.bfloat16
    q = jnp.asarray(rng.normal(size=(B, HQ, HD)), act)
    if mode == "int8":
        kp = jnp.asarray(rng.integers(-127, 128, size=(N, BS, HKV, HD)),
                         jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, size=(N, BS, HKV, HD)),
                         jnp.int8)
        ks = jnp.asarray(rng.uniform(1e-3, 0.05, size=(N, BS, HKV, 1)),
                         jnp.float32)
        vs = jnp.asarray(rng.uniform(1e-3, 0.05, size=(N, BS, HKV, 1)),
                         jnp.float32)
    else:
        kp = jnp.asarray(rng.normal(size=(N, BS, HKV, HD)), act)
        vp = jnp.asarray(rng.normal(size=(N, BS, HKV, HD)), act)
        ks = vs = None
    bt = rng.permutation(B * n_bt).astype(np.int32).reshape(B, n_bt)
    bt = np.where(rng.random((B, n_bt)) < 0.3, -1, bt)     # holes
    if B > 1 and rng.random() < 0.5:
        bt[rng.integers(B)] = -1                           # inactive slot
    bounds = np.array([0, BS - 1, BS, n_bt * BS - 1])
    pos = np.where(rng.random(B) < 0.5,
                   rng.choice(bounds, size=B),
                   rng.integers(0, n_bt * BS, size=B)).astype(np.int32)
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(pos), ks, vs


def _visible_rows(bt, pos):
    """Slots with at least one causally visible key (offset 0 of some
    allocated logical block j with j*bs <= pos)."""
    bt, pos = np.asarray(bt), np.asarray(pos)
    j = np.arange(bt.shape[1]) * BS
    return ((bt >= 0) & (j[None, :] <= pos[:, None])).any(axis=1)


def _visible_pool_mask(bt, pos, N):
    """(N, bs) bool: pool entries that are causally visible to any slot."""
    vis = np.zeros((N, BS), bool)
    bt, pos = np.asarray(bt), np.asarray(pos)
    for b in range(bt.shape[0]):
        for j in range(bt.shape[1]):
            pb = int(bt[b, j])
            if pb >= 0:
                upto = min(BS, int(pos[b]) - j * BS + 1)
                if upto > 0:
                    vis[pb, :upto] = True
    return vis


def _all(q, kp, vp, bt, pos, ks, vs):
    ref = np.asarray(pa.paged_attention_ref(q, kp, vp, bt, pos, ks, vs),
                     np.float32)
    gat = np.asarray(pa.paged_attention_gather(q, kp, vp, bt, pos, ks, vs),
                     np.float32)
    ker = np.asarray(pa.paged_attention_pallas(q, kp, vp, bt, pos, ks, vs,
                                               interpret=True), np.float32)
    return ref, gat, ker


# ---------------------------------------------------------------------------
# The differential property.
# ---------------------------------------------------------------------------
@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([1, 2, 4]),
       st.sampled_from([2, 3, 6]), st.sampled_from(["f32", "bf16", "int8"]))
@settings(max_examples=12, deadline=None)
def test_kernel_oracle_gather_agree(seed, B, n_bt, mode):
    q, kp, vp, bt, pos, ks, vs = _case(seed, B, n_bt, mode)
    ref, gat, ker = _all(q, kp, vp, bt, pos, ks, vs)
    rows = _visible_rows(bt, pos)
    tol = (dict(rtol=1e-5, atol=1e-4) if mode == "f32"
           else dict(rtol=4e-2, atol=4e-2))
    np.testing.assert_allclose(ker[rows], ref[rows], **tol)
    np.testing.assert_allclose(gat[rows], ref[rows], **tol)
    # inactive / fully-masked slots: the kernel's contract is exact zero
    assert (ker[~rows] == 0.0).all()


@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([2, 4]),
       st.sampled_from([2, 4]), st.sampled_from(["bf16", "int8"]))
@settings(max_examples=8, deadline=None)
def test_masking_pattern_is_exact(seed, B, n_bt, mode):
    """Rewriting every causally-invisible pool entry (stale rows past pos,
    unreferenced blocks, scratch blocks, holes) changes no output bit in
    either the oracle or the kernel."""
    q, kp, vp, bt, pos, ks, vs = _case(seed, B, n_bt, mode)
    ref0, _, ker0 = _all(q, kp, vp, bt, pos, ks, vs)
    N = kp.shape[0]
    vis = _visible_pool_mask(bt, pos, N)[:, :, None, None]
    rng = np.random.default_rng(seed ^ 0x5EED)
    if mode == "int8":
        garbage = lambda t: jnp.asarray(np.where(
            vis, np.asarray(t), rng.integers(-127, 128, t.shape)), t.dtype)
        s_garbage = lambda t: jnp.asarray(np.where(
            vis, np.asarray(t), rng.uniform(1e-3, 0.05, t.shape)), t.dtype)
        ks2, vs2 = s_garbage(ks), s_garbage(vs)
    else:
        garbage = lambda t: jnp.asarray(np.where(
            vis, np.asarray(t, np.float32), rng.normal(size=t.shape)),
            t.dtype)
        ks2, vs2 = None, None
    ref1, _, ker1 = _all(q, garbage(kp), garbage(vp), bt, pos, ks2, vs2)
    # the oracle's fully-masked rows softmax uniformly over garbage (their
    # output is discarded host-side), so its bit-stability claim covers
    # visible rows; the kernel's contract (exact zero) holds everywhere.
    rows = _visible_rows(bt, pos)
    np.testing.assert_array_equal(ref0[rows], ref1[rows])
    np.testing.assert_array_equal(ker0, ker1)


def test_boundary_positions_exhaustive():
    """pos at exactly 0, bs−1, bs, and the last table entry: every backend
    attends to exactly pos+1 keys (checked against a hand-built dense
    reference)."""
    rng = np.random.default_rng(0)
    n_bt = 3
    N = n_bt + 1
    kp = jnp.asarray(rng.normal(size=(N, BS, HKV, HD)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, BS, HKV, HD)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, HQ, HD)), jnp.float32)
    bt = jnp.asarray([[2, 0, 1]], jnp.int32)          # permuted blocks
    for p in (0, BS - 1, BS, n_bt * BS - 1):
        pos = jnp.asarray([p], jnp.int32)
        ref, gat, ker = _all(q, kp, vp, bt, pos, None, None)
        # dense reference over the logically ordered, truncated KV
        order = np.asarray(bt)[0]
        kd = np.asarray(kp)[order].reshape(n_bt * BS, HKV, HD)[:p + 1]
        vd = np.asarray(vp)[order].reshape(n_bt * BS, HKV, HD)[:p + 1]
        qn = np.asarray(q)[0].reshape(HKV, HQ // HKV, HD)
        s = np.einsum("hgd,khd->hgk", qn, kd) * (HD ** -0.5)
        w = np.exp(s - s.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        want = np.einsum("hgk,khd->hgd", w, vd).reshape(HQ, HD)
        for got in (ref, gat, ker):
            np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Routing (kernels.ops contract).
# ---------------------------------------------------------------------------
class TestRouting:
    def test_cpu_default_is_oracle(self, monkeypatch):
        monkeypatch.delenv("REPRO_PAGED_ATTN", raising=False)
        monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
        monkeypatch.setattr(ops, "_backend", lambda: "cpu")
        assert ops.paged_attn_route() == "ref"

    def test_backend_routing_is_explicit(self, monkeypatch):
        monkeypatch.delenv("REPRO_PAGED_ATTN", raising=False)
        monkeypatch.setattr(ops, "_backend", lambda: "tpu")
        assert ops.paged_attn_route() == "pallas"
        monkeypatch.setattr(ops, "_backend", lambda: "gpu")
        assert ops.paged_attn_route() == "gather"
        monkeypatch.setattr(ops, "_backend", lambda: "cpu")
        monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
        assert ops.paged_attn_route() == "interpret"

    def test_env_override_and_loud_failure(self, monkeypatch):
        monkeypatch.setenv("REPRO_PAGED_ATTN", "gather")
        assert ops.paged_attn_route() == "gather"
        monkeypatch.setenv("REPRO_PAGED_ATTN", "vliw")
        with pytest.raises(ValueError, match="REPRO_PAGED_ATTN"):
            ops.paged_attn_route()

    def test_interpret_on_tpu_is_an_error(self, monkeypatch):
        """On a TPU backend nothing may swap the interpreter in for the
        chip: both interpret switches raise instead of running."""
        monkeypatch.setattr(ops, "_backend", lambda: "tpu")
        monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
        monkeypatch.setenv("REPRO_PAGED_ATTN", "interpret")
        with pytest.raises(RuntimeError, match="REPRO_PAGED_ATTN"):
            ops.paged_attn_route()
        monkeypatch.delenv("REPRO_PAGED_ATTN")
        monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
        for route in (ops.paged_attn_route, ops.matmul_route):
            with pytest.raises(RuntimeError, match="REPRO_FORCE_INTERPRET"):
                route()

    def test_routed_interpret_matches_oracle(self, monkeypatch):
        q, kp, vp, bt, pos, ks, vs = _case(7, 2, 3, "bf16")
        monkeypatch.delenv("REPRO_PAGED_ATTN", raising=False)
        monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
        ref = ops.paged_decode_attention(q, kp, vp, bt, pos, ks, vs)
        monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
        ker = ops.paged_decode_attention(q, kp, vp, bt, pos, ks, vs)
        rows = _visible_rows(bt, pos)
        np.testing.assert_allclose(
            np.asarray(ref, np.float32)[rows],
            np.asarray(ker, np.float32)[rows], rtol=4e-2, atol=4e-2)


# ---------------------------------------------------------------------------
# Block-level: routed read side vs the pre-PR gather path, and the
# scatter-overflow regression.
# ---------------------------------------------------------------------------
def _cfg(**kw):
    return reduced_config(get_config("qwen3-8b"), **kw)


def _legacy_paged_block(p, x, cfg, positions, cache, block_tables,
                        active=None):
    """The pre-kernel paged decode block, verbatim (PR 4): masked scatter
    (with its pos//bs clip) + dense gather + sdpa read.  The routed block
    must stay token-identical to this on in-range positions."""
    q, k_new, v_new = attention._project_qkv(p, x, cfg, positions)
    pos1d = positions[:, 0] if positions.ndim == 3 else positions
    B = x.shape[0]
    N, bs = cache["k"].shape[0], cache["k"].shape[1]
    n_bt = block_tables.shape[1]
    pos = pos1d[:, 0]
    li = jnp.clip(pos // bs, 0, n_bt - 1)
    off = pos % bs
    pb = jnp.take_along_axis(block_tables, li[:, None], axis=1)[:, 0]
    ok = pb >= 0
    if active is not None:
        ok = ok & active
    dest = jnp.where(ok, pb, N - B + jnp.arange(B, dtype=pb.dtype))
    if "k_scale" in cache:
        kq, ks = attention._kv_quantize(k_new[:, 0])
        vq, vs = attention._kv_quantize(v_new[:, 0])
        new_cache = {
            "k": cache["k"].at[dest, off].set(kq),
            "v": cache["v"].at[dest, off].set(vq),
            "k_scale": cache["k_scale"].at[dest, off].set(ks),
            "v_scale": cache["v_scale"].at[dest, off].set(vs),
        }
    else:
        new_cache = {
            "k": cache["k"].at[dest, off].set(
                k_new[:, 0].astype(cache["k"].dtype)),
            "v": cache["v"].at[dest, off].set(
                v_new[:, 0].astype(cache["v"].dtype)),
        }
    safe = jnp.maximum(block_tables, 0)

    def gather(pool):
        g = pool[safe]
        return g.reshape(B, n_bt * bs, *pool.shape[2:])

    if "k_scale" in new_cache:
        k = attention._kv_dequantize(gather(new_cache["k"]),
                                     gather(new_cache["k_scale"]), x.dtype)
        v = attention._kv_dequantize(gather(new_cache["v"]),
                                     gather(new_cache["v_scale"]), x.dtype)
    else:
        k, v = gather(new_cache["k"]), gather(new_cache["v"])
    base = (jnp.arange(n_bt, dtype=jnp.int32)[None, :, None] * bs
            + jnp.arange(bs, dtype=jnp.int32)[None, None, :])
    k_pos = jnp.where(block_tables[:, :, None] >= 0, base,
                      -1).reshape(B, n_bt * bs)
    o = attention.sdpa(q, k, v, pos1d, k_pos, causal=True, window=0)
    from repro.quant import linear
    y = linear(p["wo"], o.reshape(B, 1, -1), cfg.quant_mode)
    return y, new_cache


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_routed_block_matches_legacy_gather_path(kv_quant):
    """End-to-end block output: the routed kernel read side reproduces the
    pre-PR XLA gather path bit-for-bit on the CPU oracle route (this is
    what keeps served tokens identical across the PR)."""
    cfg = _cfg(kv_quant=kv_quant)
    p = attention.init_attention(cfg, jax.random.PRNGKey(0))
    B, n_bt, bs = 2, 4, cfg.cache_block_size
    N = B * n_bt + B
    cache = attention.init_paged_kv_cache(cfg, N, bs)
    rng = np.random.default_rng(3)
    bt = jnp.asarray(rng.permutation(B * n_bt).reshape(B, n_bt), jnp.int32)
    bt = bt.at[0, 3].set(-1)
    x = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)
    positions = jnp.asarray([[bs + 1], [0]], jnp.int32)
    active = jnp.asarray([True, True])
    y_new, c_new = attention.paged_decode_attention_block(
        p, x, cfg, positions, cache, bt, active=active)
    y_old, c_old = _legacy_paged_block(p, x, cfg, positions, cache, bt,
                                       active=active)
    for leaf in c_new:
        np.testing.assert_array_equal(np.asarray(c_new[leaf]),
                                      np.asarray(c_old[leaf]))
    np.testing.assert_array_equal(np.asarray(y_new, np.float32),
                                  np.asarray(y_old, np.float32))


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_scatter_overflow_writes_scratch_not_last_block(kv_quant):
    """Regression: pos//bs >= n_bt used to clip into the LAST logical
    block, scatter-corrupting a physical block owned by another token.
    Overflow must land in the slot's scratch block instead."""
    cfg = _cfg(kv_quant=kv_quant)
    p = attention.init_attention(cfg, jax.random.PRNGKey(1))
    B, n_bt, bs = 2, 2, cfg.cache_block_size
    N = B * n_bt + B
    cache = attention.init_paged_kv_cache(cfg, N, bs)
    # sentinel contents so any corruption is visible
    cache = {k: (v + 1).astype(v.dtype) for k, v in cache.items()}
    bt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)      # fully allocated
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)
    overflow = n_bt * bs                               # first out-of-range pos
    positions = jnp.asarray([[overflow], [overflow + 3]], jnp.int32)
    _, c = attention.paged_decode_attention_block(
        p, x, cfg, positions, cache, bt,
        active=jnp.asarray([True, True]))
    for leaf in c:
        got, before = np.asarray(c[leaf]), np.asarray(cache[leaf])
        # every table-owned block is untouched (the old bug wrote into the
        # last logical block's physical block at offset pos % bs)
        np.testing.assert_array_equal(got[:B * n_bt], before[:B * n_bt])
        # the write landed in each slot's own scratch block
        for b in range(B):
            off = int(np.asarray(positions)[b, 0]) % bs
            assert not np.array_equal(got[N - B + b, off],
                                      before[N - B + b, off]), (leaf, b)


def test_inactive_slots_do_not_write_anywhere_owned():
    """active=False rows route their scatter to scratch even with a valid
    table entry (masked-decode contract, unchanged by the kernel PR)."""
    cfg = _cfg()
    p = attention.init_attention(cfg, jax.random.PRNGKey(2))
    B, n_bt, bs = 2, 2, cfg.cache_block_size
    N = B * n_bt + B
    cache = attention.init_paged_kv_cache(cfg, N, bs)
    cache = {k: (v + 1).astype(v.dtype) for k, v in cache.items()}
    bt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    x = jnp.asarray(np.random.default_rng(8).normal(size=(B, 1, cfg.d_model)),
                    jnp.float32)
    positions = jnp.asarray([[1], [1]], jnp.int32)
    _, c = attention.paged_decode_attention_block(
        p, x, cfg, positions, cache, bt,
        active=jnp.asarray([False, True]))
    np.testing.assert_array_equal(np.asarray(c["k"])[:B * n_bt][[0, 2]][0],
                                  np.asarray(cache["k"])[:B * n_bt][[0, 2]][0])
    # row 0 inactive: its blocks 0/1 untouched; row 1 active: block 2 off 1
    assert np.array_equal(np.asarray(c["k"])[0], np.asarray(cache["k"])[0])
    assert np.array_equal(np.asarray(c["k"])[1], np.asarray(cache["k"])[1])
    assert not np.array_equal(np.asarray(c["k"])[2, 1],
                              np.asarray(cache["k"])[2, 1])
