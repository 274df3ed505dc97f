"""Model stacks for every assigned family: decoder-only (dense/MoE/SSM/
hybrid), encoder-decoder (whisper), VLM backbone (qwen2-vl).

Layers are parameter-stacked and driven by ``jax.lax.scan`` (compile time is
O(1) in depth — granite's 88 layers lower as one loop).  Hybrid stacks scan
over (rec, rec, attn) groups with an unrolled remainder.  Each block is
wrapped in ``jax.checkpoint`` when cfg.remat (activation recomputation keeps
the train_4k cells inside HBM).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention, layers, moe, rglru, ssm
from repro.quant import linear, embed, tied_logits


# ---------------------------------------------------------------------------
# Single blocks.
# ---------------------------------------------------------------------------
def init_block(cfg, key, kind):
    ks = jax.random.split(key, 4)
    p = {"norm1": layers.init_norm(cfg, cfg.d_model)}
    if kind == "attn":
        p["attn"] = attention.init_attention(cfg, ks[0])
    elif kind == "rec":
        p["rec"] = rglru.init_rglru(cfg, ks[0])
    elif kind == "mamba":
        p["mamba"] = ssm.init_mamba(cfg, ks[0])
    elif kind == "xattn":     # decoder block with self + cross attention
        p["attn"] = attention.init_attention(cfg, ks[0])
        p["norm_x"] = layers.init_norm(cfg, cfg.d_model)
        p["xattn"] = attention.init_attention(cfg, ks[2], cross=True)
    # FFN half (mamba blocks have none; MoE blocks carry expert weights).
    if kind != "mamba":
        p["norm2"] = layers.init_norm(cfg, cfg.d_model)
        if cfg.family == "moe":
            p["moe"] = moe.init_moe(cfg, ks[1])
        else:
            p["mlp"] = layers.init_mlp(cfg, ks[1])
    return p


def apply_block(p, x, cfg, kind, positions, enc_kv=None, ctx=None):
    """Full-sequence (train / prefill) block.  Returns (x, state, aux).
    ``ctx`` is this block's prefix-cache context KV (attn blocks only;
    see ``attention.attention_block``)."""
    h = layers.apply_norm(p["norm1"], x, cfg)
    state = None
    if kind in ("attn", "xattn"):
        with jax.named_scope("attn"):
            y, (k, v, k_pos) = attention.attention_block(p["attn"], h, cfg,
                                                         positions, ctx=ctx)
        state = {"k": k, "v": v, "k_pos": k_pos}
        x = x + y
        if kind == "xattn":
            hx = layers.apply_norm(p["norm_x"], x, cfg)
            ekv = attention.project_enc_kv(p["xattn"], enc_kv, cfg)
            x = x + attention.cross_attention_block(p["xattn"], hx, cfg, ekv)
    elif kind == "rec":
        y, state = rglru.rglru_block(p["rec"], h, cfg)
        x = x + y
    elif kind == "mamba":
        y, (h_last, conv_tail) = ssm.mamba_block(p["mamba"], h, cfg)
        state = {"ssm": h_last, "conv": conv_tail}
        x = x + y
    aux = jnp.zeros((), jnp.float32)
    if kind != "mamba":
        with jax.named_scope("mlp"):
            h2 = layers.apply_norm(p["norm2"], x, cfg)
            if cfg.family == "moe":
                y2, aux = moe.moe_ffn(p["moe"], h2, cfg)
            else:
                y2 = layers.apply_mlp(p["mlp"], h2, cfg)
        x = x + y2
    return x, state, aux


def _freeze_inactive_state(new_state, old_state, active):
    """Keep recurrent (rg-lru / mamba) state rows frozen where ``active`` is
    False — the masked-decode contract for continuous batching (DESIGN.md §3).
    State leaves all carry batch on axis 0 at block level."""
    if active is None:
        return new_state

    def sel(n, o):
        mask = active.reshape(active.shape[0], *([1] * (n.ndim - 1)))
        return jnp.where(mask, n, o)

    return jax.tree_util.tree_map(sel, new_state, old_state)


def apply_block_decode(p, x, cfg, kind, positions, cache, enc_kv=None,
                       active=None, constrain=None, block_tables=None):
    """One-token decode block.  Returns (x, new_cache).  ``active`` (B,) bool
    masks cache/state mutation per batch row (None = all rows live).
    ``constrain`` (executor-threaded, DESIGN.md §5) re-pins the block's
    updated cache to its serving sharding after the masked writes.
    ``block_tables`` (B, n_bt) selects the paged attention path — the block
    cache is then a pool dict and the read side goes through the routed
    flash-decode kernel, ``kernels.ops.paged_decode_attention`` (DESIGN.md
    §3 "Paged-decode kernel"); only pure-attention stacks resolve to the
    paged layout (configs.ModelConfig.paged_capable)."""
    h = layers.apply_norm(p["norm1"], x, cfg)
    if kind in ("attn", "xattn"):
        with jax.named_scope("attn"):
            if block_tables is not None:
                y, cache = attention.paged_decode_attention_block(
                    p["attn"], h, cfg, positions, cache, block_tables,
                    active=active, constrain=constrain)
            else:
                y, cache = attention.decode_attention_block(
                    p["attn"], h, cfg, positions, cache, active=active,
                    constrain=constrain)
        x = x + y
        if kind == "xattn":
            hx = layers.apply_norm(p["norm_x"], x, cfg)
            ekv = attention.project_enc_kv(p["xattn"], enc_kv, cfg)
            x = x + attention.cross_attention_block(p["xattn"], hx, cfg, ekv)
    elif kind == "rec":
        y, new_cache = rglru.rglru_decode_step(p["rec"], h, cfg, cache)
        cache = _freeze_inactive_state(new_cache, cache, active)
        if constrain is not None:
            cache = constrain(cache)
        x = x + y
    elif kind == "mamba":
        y, new_cache = ssm.mamba_decode_step(p["mamba"], h, cfg, cache)
        cache = _freeze_inactive_state(new_cache, cache, active)
        if constrain is not None:
            cache = constrain(cache)
        x = x + y
    if kind != "mamba":
        with jax.named_scope("mlp"):
            h2 = layers.apply_norm(p["norm2"], x, cfg)
            if cfg.family == "moe":
                y2, _ = moe.moe_ffn(p["moe"], h2, cfg)
            else:
                y2 = layers.apply_mlp(p["mlp"], h2, cfg)
        x = x + y2
    return x, cache


# ---------------------------------------------------------------------------
# Stack layout helpers.
# ---------------------------------------------------------------------------
def layer_kinds(cfg):
    """Per-layer block kind for the decoder stack."""
    if cfg.family == "ssm":
        return ["mamba"] * cfg.n_layers
    if cfg.family == "hybrid":
        pat = cfg.block_pattern
        return [pat[i % len(pat)] for i in range(cfg.n_layers)]
    if cfg.family == "encdec":
        return ["xattn"] * cfg.n_layers
    return ["attn"] * cfg.n_layers


def _stack_groups(cfg):
    """(group_kinds, n_scanned_groups, tail_kinds): scan unit for the stack."""
    kinds = layer_kinds(cfg)
    if cfg.family == "hybrid":
        pat = list(cfg.block_pattern)
        g = len(pat)
        n_groups = cfg.n_layers // g
        tail = kinds[n_groups * g:]
        return pat, n_groups, tail
    return [kinds[0]], cfg.n_layers, []


def decoder_stack_keys(cfg, key):
    """One key per scanned group, then one per unrolled tail block."""
    _, n_groups, tail_kinds = _stack_groups(cfg)
    return jax.random.split(key, n_groups + len(tail_kinds))


def init_group(cfg, key):
    """Parameters of ONE scanned group (unstacked) — a row of the stack's
    ``groups`` leaves; ``Model.init_serving`` draws them a layer at a
    time."""
    group_kinds, _, _ = _stack_groups(cfg)
    gks = jax.random.split(key, len(group_kinds))
    return {f"b{i}_{kind}": init_block(cfg, gk, kind)
            for i, (kind, gk) in enumerate(zip(group_kinds, gks))}


def init_decoder_stack(cfg, key):
    _, n_groups, tail_kinds = _stack_groups(cfg)
    keys = decoder_stack_keys(cfg, key)
    groups = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[init_group(cfg, keys[i]) for i in range(n_groups)])
    tail = [init_block(cfg, keys[n_groups + i], kind)
            for i, kind in enumerate(tail_kinds)]
    return {"groups": groups, "tail": tail}


def _group_apply(gp, x, cfg, group_kinds, positions, enc_kv=None, ctx=None):
    states, aux = {}, jnp.zeros((), jnp.float32)
    for i, kind in enumerate(group_kinds):
        x, st, a = apply_block(gp[f"b{i}_{kind}"], x, cfg, kind, positions,
                               enc_kv,
                               ctx=None if ctx is None else ctx[f"b{i}"])
        states[f"b{i}"] = st
        aux = aux + a
    return x, states, aux


def _constrain_act(x, cfg):
    """Pin the inter-block residual stream to (batch: dp axes, seq: model,
    d: replicated).  Cuts scan-saved activations 16x and stops the SPMD
    partitioner from replicating the batch dim (DESIGN.md §5)."""
    if not cfg.act_seq_axis or x.ndim != 3 or x.shape[1] <= 1:
        return x
    from jax.sharding import PartitionSpec as P
    bax = cfg.act_batch_axes or None
    return jax.lax.with_sharding_constraint(
        x, P(bax, cfg.act_seq_axis, None))


def apply_decoder_stack(p, x, cfg, positions, enc_kv=None, collect_cache=False,
                        ctx_kv=None):
    """Returns (x, stacked_states_or_None, total_aux).

    ``ctx_kv`` (prefix-cache suffix prefill, DESIGN.md §3): a per-group
    context-KV tree in the same ``{"b{i}": {"k", "v"}}`` stacked layout as
    the decode cache (leading scanned-group axis), holding the shared
    prefix gathered from the paged pool.  Only pure-attention stacks are
    pageable, so the tail must be empty when it is supplied."""
    group_kinds, n_groups, tail_kinds = _stack_groups(cfg)
    if ctx_kv is not None:
        assert not tail_kinds, "prefix context needs a pure scanned stack"

    def body(carry, xs):
        gp, ctx = xs if ctx_kv is not None else (xs, None)
        x, aux = carry
        x = _constrain_act(x, cfg)
        x, states, a = _group_apply(gp, x, cfg, group_kinds, positions,
                                    enc_kv, ctx=ctx)
        x = _constrain_act(x, cfg)
        return (x, aux + a), (states if collect_cache else 0)

    body_fn = jax.checkpoint(body) if cfg.remat else body
    scan_xs = (p["groups"] if ctx_kv is None else (p["groups"], ctx_kv))
    if cfg.scan_layers:
        (x, aux), states = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                                        scan_xs)
    else:
        aux = jnp.zeros((), jnp.float32)
        collected = []
        for i in range(n_groups):
            gxs = jax.tree_util.tree_map(lambda a: a[i], scan_xs)
            (x, aux), st = body_fn((x, aux), gxs)
            collected.append(st)
        states = (jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *collected)
                  if collect_cache else None)
    tail_states = []
    for tp, kind in zip(p["tail"], tail_kinds):
        x, st, a = apply_block(tp, x, cfg, kind, positions, enc_kv)
        aux = aux + a
        tail_states.append(st)
    return x, (states, tail_states) if collect_cache else None, aux


def apply_decoder_stack_decode(p, x, cfg, positions, cache, enc_kv=None,
                               active=None, constrain=None,
                               block_tables=None):
    """cache = (group_cache_stacked, tail_cache_list) as produced by
    ``init_stack_cache`` (dense) or ``init_paged_stack_cache`` (paged —
    selected by passing ``block_tables``; the table is scan-invariant, every
    layer indexes its own pool through the same per-slot block ids).
    ``active`` (B,) bool gates cache writes per row (continuous batching;
    DESIGN.md §3).  ``constrain`` (executor-threaded) pins each block's
    updated cache to its serving sharding inside the scan (DESIGN.md §5).
    Returns (x, new_cache)."""
    group_kinds, n_groups, tail_kinds = _stack_groups(cfg)
    g_cache, t_cache = cache

    def body(x, xs):
        gp, gc = xs
        new_c = {}
        for i, kind in enumerate(group_kinds):
            x, nc = apply_block_decode(gp[f"b{i}_{kind}"], x, cfg, kind,
                                       positions, gc[f"b{i}"], enc_kv,
                                       active=active, constrain=constrain,
                                       block_tables=block_tables)
            new_c[f"b{i}"] = nc
        return x, new_c

    x, new_g_cache = jax.lax.scan(body, x, (p["groups"], g_cache))
    new_t = []
    for tp, kind, tc in zip(p["tail"], tail_kinds, t_cache):
        x, nc = apply_block_decode(tp, x, cfg, kind, positions, tc, enc_kv,
                                   active=active, constrain=constrain,
                                   block_tables=block_tables)
        new_t.append(nc)
    return x, (new_g_cache, new_t)


def apply_block_verify(p, x, cfg, positions, cache, block_tables,
                       active=None, constrain=None):
    """k-token speculative-verify block (paged attention stacks only): the
    attention half goes through ``attention.paged_verify_attention_block``;
    norms and the FFN half are shape-generic over (B, k, d)."""
    h = layers.apply_norm(p["norm1"], x, cfg)
    with jax.named_scope("attn"):
        y, cache = attention.paged_verify_attention_block(
            p["attn"], h, cfg, positions, cache, block_tables,
            active=active, constrain=constrain)
    x = x + y
    with jax.named_scope("mlp"):
        h2 = layers.apply_norm(p["norm2"], x, cfg)
        if cfg.family == "moe":
            y2, _ = moe.moe_ffn(p["moe"], h2, cfg)
        else:
            y2 = layers.apply_mlp(p["mlp"], h2, cfg)
    return x + y2, cache


def apply_decoder_stack_verify(p, x, cfg, positions, cache, block_tables,
                               active=None, constrain=None):
    """Speculative verify over the whole stack: same scan shape as
    ``apply_decoder_stack_decode`` (the block table is scan-invariant), but
    each layer processes k tokens at once.  Paged caches exist only for
    pure full-attention stacks, so there is no tail and no kind dispatch.
    Returns (x (B, k, d), new_cache)."""
    group_kinds, n_groups, tail_kinds = _stack_groups(cfg)
    assert all(k == "attn" for k in group_kinds) and not tail_kinds, (
        f"speculative verify needs a pure attention stack, got "
        f"{group_kinds} + {tail_kinds}")
    g_cache, _ = cache

    def body(x, xs):
        gp, gc = xs
        new_c = {}
        for i, kind in enumerate(group_kinds):
            x, nc = apply_block_verify(gp[f"b{i}_{kind}"], x, cfg, positions,
                                       gc[f"b{i}"], block_tables,
                                       active=active, constrain=constrain)
            new_c[f"b{i}"] = nc
        return x, new_c

    x, new_g = jax.lax.scan(body, x, (p["groups"], g_cache))
    return x, (new_g, [])


def init_stack_cache(cfg, batch, seq_len, dtype=jnp.bfloat16):
    group_kinds, n_groups, tail_kinds = _stack_groups(cfg)

    def one(kind):
        if kind in ("attn", "xattn"):
            return attention.init_kv_cache(cfg, batch, seq_len, dtype)
        if kind == "rec":
            return rglru.init_rglru_state(cfg, batch)
        return ssm.init_mamba_state(cfg, batch)

    g = {f"b{i}": one(kind) for i, kind in enumerate(group_kinds)}
    g = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n_groups,) + a.shape), g)
    t = [one(kind) for kind in tail_kinds]
    return (g, t)


def init_paged_stack_cache(cfg, n_total, block_size, dtype=jnp.bfloat16):
    """Per-layer block pools in the same (grouped, tail) stack structure as
    ``init_stack_cache``.  Only pure full-attention stacks are pageable
    (``cfg.paged_capable`` — enforced at layout resolution), so every group
    slot is an attention pool and the tail is empty."""
    group_kinds, n_groups, tail_kinds = _stack_groups(cfg)
    assert all(k == "attn" for k in group_kinds) and not tail_kinds, (
        f"paged cache needs a pure attention stack, got {group_kinds} + "
        f"{tail_kinds}")
    g = {f"b{i}": attention.init_paged_kv_cache(cfg, n_total, block_size,
                                                dtype)
         for i in range(len(group_kinds))}
    g = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n_groups,) + a.shape), g)
    return (g, [])


def gather_paged_ctx(cache, ctx_ids, dtype):
    """Gather the shared-prefix blocks out of a paged stack cache as dense
    per-group context KV for the suffix prefill (DESIGN.md §3 "Prefix
    cache").

    ``cache`` is the engine's paged ``(g_cache, [])`` stack; ``ctx_ids``
    is ``(nctx,)`` int32 physical block ids covering absolute positions
    ``[0, nctx * block_size)`` in logical order.  Returns a
    ``{"b{i}": {"k", "v"}}`` tree of ``(G, 1, nctx*bs, Hkv, hd)`` arrays
    (batch-1 — the fused single-admission prefill is the only prefix
    path), int8 pools dequantized into ``dtype``.  ``nctx`` is static
    (baked into the compiled shape); ``ctx_ids`` contents are traced."""
    g_cache, tail = cache
    assert not tail, "paged caches have a pure scanned stack"

    def one(pool_dict):
        def gather(pool):
            got = pool[:, ctx_ids]               # (G, nctx, bs, Hkv, ·)
            G, n, bs = got.shape[:3]
            return got.reshape(G, 1, n * bs, *got.shape[3:])

        if "k_scale" in pool_dict:
            k = attention._kv_dequantize(gather(pool_dict["k"]),
                                         gather(pool_dict["k_scale"]), dtype)
            v = attention._kv_dequantize(gather(pool_dict["v"]),
                                         gather(pool_dict["v_scale"]), dtype)
            return {"k": k, "v": v}
        return {"k": gather(pool_dict["k"]), "v": gather(pool_dict["v"])}

    return {name: one(d) for name, d in g_cache.items()}


def insert_paged_stack_cache(cache, seq_cache, block_row, scratch_block):
    """Scatter one prefilled sequence into its allocated pool blocks.

    ``seq_cache`` is the batch-1 DENSE cache returned by ``Model.prefill``
    at ``cache_len == the prefill length`` (rows [0, C) hold the sequence in
    position order — the ring layout is the identity below the extent);
    ``cache`` is the engine's paged stack.  ``block_row`` (n_bt,) int32
    names the physical block for each logical block; entries past the
    request's own allocation are -1 and their (pad-only) rows are routed to
    ``scratch_block`` — that single destination may repeat, which is fine
    because scratch contents are never read.  ``block_row`` and
    ``scratch_block`` may be traced, so one jitted insertion serves every
    slot/table without recompiling.
    """
    g_cache, _ = cache
    sg_cache, _ = seq_cache

    def scatter(pool, seq):
        # pool (G, N, bs, ...), seq (G, 1, C, ...)
        bs = pool.shape[2]
        C = seq.shape[2]
        nb = -(-C // bs)
        rows = seq[:, 0]
        if nb * bs != C:
            pad = [(0, 0), (0, nb * bs - C)] + [(0, 0)] * (rows.ndim - 2)
            rows = jnp.pad(rows, pad)
        rows = rows.reshape(rows.shape[0], nb, bs, *rows.shape[2:])
        ids = jax.lax.dynamic_slice_in_dim(block_row, 0, nb)
        dest = jnp.where(ids >= 0, ids, scratch_block)
        return pool.at[:, dest].set(rows.astype(pool.dtype))

    new_g = {}
    for name, pool_dict in g_cache.items():
        seq_dict = sg_cache[name]
        new_g[name] = {k: scatter(pool, seq_dict[k])
                       for k, pool in pool_dict.items()}
    return (new_g, [])


def slice_stack_cache(cache, row):
    """Extract batch row ``row`` of a batched cache as a batch-1 cache
    (grouped leaves: batch axis 1; tail leaves: axis 0).  The engine uses it
    to split a batched prefill into per-slot insertions; ``row`` may be
    traced."""
    g_cache, t_cache = cache
    new_g = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, row, 1, axis=1), g_cache)
    new_t = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, row, 1, axis=0), t_cache)
    return (new_g, new_t)


def insert_stack_cache(cache, seq_cache, slot):
    """Write a single sequence's cache into row ``slot`` of a batched cache.

    ``seq_cache`` is a batch-1 cache (the output of ``Model.prefill`` on one
    request); ``cache`` is the engine's persistent (max_batch, ...) decode
    cache with identical tree structure.  Grouped leaves carry batch on
    axis 1 (behind the scanned group axis), tail leaves on axis 0 — this is
    the per-slot cache insertion primitive of the continuous-batching engine
    (DESIGN.md §3).  ``slot`` may be a traced int32 scalar, so one jitted
    insertion serves every slot without recompiling.
    """
    g_cache, t_cache = cache
    sg_cache, st_cache = seq_cache
    new_g = jax.tree_util.tree_map(
        lambda big, small: big.at[:, slot].set(small[:, 0].astype(big.dtype)),
        g_cache, sg_cache)
    new_t = jax.tree_util.tree_map(
        lambda big, small: big.at[slot].set(small[0].astype(big.dtype)),
        t_cache, st_cache)
    return (new_g, new_t)


# ---------------------------------------------------------------------------
# Encoder stack (whisper).
# ---------------------------------------------------------------------------
def init_encoder_stack(cfg, key):
    keys = jax.random.split(key, cfg.n_enc_layers)
    blocks = [
        {"norm1": layers.init_norm(cfg, cfg.d_model),
         "attn": attention.init_attention(cfg, k),
         "norm2": layers.init_norm(cfg, cfg.d_model),
         "mlp": layers.init_mlp(cfg, jax.random.fold_in(k, 1))}
        for k in keys
    ]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)


def apply_encoder_stack(p, x, cfg):
    B, S, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def body(x, bp):
        h = layers.apply_norm(bp["norm1"], x, cfg)
        hd, hq, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
        q = linear(bp["attn"]["wq"], h, cfg.quant_mode).reshape(B, S, hq, hd)
        k = linear(bp["attn"]["wk"], h, cfg.quant_mode).reshape(B, S, hkv, hd)
        v = linear(bp["attn"]["wv"], h, cfg.quant_mode).reshape(B, S, hkv, hd)
        o = attention.sdpa(q, k, v, pos, pos, causal=False, window=0)
        x = x + linear(bp["attn"]["wo"], o.reshape(B, S, -1), cfg.quant_mode)
        h2 = layers.apply_norm(bp["norm2"], x, cfg)
        x = x + layers.apply_mlp(bp["mlp"], h2, cfg)
        return x, 0

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body_fn, x, p)
    return x
