"""Pallas TPU kernel: fused paged-decode attention over the block-pool KV.

The serving decode step used to *gather* every slot's pool blocks into a
dense ``(B, n_bt*bs, Hkv, hd)`` temporary (dequantizing int8 pools into a
second temporary first) and only then attend — exactly the HBM round-trip
the TMA thesis says to eliminate (DESIGN.md §2: useful work per byte
moved).  This kernel walks each slot's block table directly instead:

  * Grid ``(B, n_bt)`` with the block-table entry as the *scalar-prefetched*
    HBM index — ``PrefetchScalarGridSpec`` lets the BlockSpec index_map pick
    pool block ``max(table[b, j], 0)`` so each referenced block is streamed
    through VMEM exactly once, straight out of the pool.  No gathered
    temporary ever exists.
  * Online softmax (flash-decode): per-slot running max ``m``, sum ``l`` and
    output accumulator ``acc`` live in VMEM scratch across the ``j`` walk.
  * Key positions are synthesized from the walk itself (logical block j,
    offset o -> ``j*bs + o``; entry −1 -> invalid), so stale pool contents
    past ``pos`` stay causally masked without a stored k_pos — the same
    contract the gather path implemented (DESIGN.md §3).
  * int8 pools (``kv_quant="int8"``) dequantize per-entry inside the same
    VMEM pass: codes * ``k_scale``/``v_scale`` right before the dot, so the
    low-bit representation stays live all the way into the compute unit
    (no dequantized HBM copy).

Rows whose table is entirely −1 (inactive slots) have no valid key and
return exactly zero — the serving engine discards those outputs host-side
(masked-decode contract).  The gather/oracle paths return the unmasked
softmax average there instead; tests only compare rows with >= 1 visible
key.

Routing lives in :mod:`repro.kernels.ops` (tpu -> this kernel, gpu -> the
dense-gather fast path below, cpu -> :func:`paged_attention_ref`, the
bit-level token-identity oracle).  Validated on CPU with ``interpret=True``
against the oracle by ``tests/test_paged_attention.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Shared helpers (gather layout + synthesized positions).
# ---------------------------------------------------------------------------
def _gather(pool, block_tables):
    """pool (N, bs, ...) indexed by (B, n_bt) tables -> (B, n_bt*bs, ...).

    −1 entries clamp to block 0; callers mask them via the synthesized
    positions.  This *is* the dense temporary the Pallas kernel removes —
    kept here as the oracle/fast-path building block.
    """
    B, n_bt = block_tables.shape
    g = pool[jnp.maximum(block_tables, 0)]          # (B, n_bt, bs, ...)
    return g.reshape(B, n_bt * pool.shape[1], *pool.shape[2:])


def synth_positions(block_tables, block_size):
    """(B, n_bt) tables -> (B, n_bt*bs) absolute key positions; −1 entries
    (and everything in them) are invalid (−1)."""
    B, n_bt = block_tables.shape
    base = (jnp.arange(n_bt, dtype=jnp.int32)[None, :, None] * block_size
            + jnp.arange(block_size, dtype=jnp.int32)[None, None, :])
    return jnp.where(block_tables[:, :, None] >= 0, base,
                     -1).reshape(B, n_bt * block_size)


def _out_dtype(q, v_pool, v_scale):
    # quantized pools dequantize into the activation dtype; float pools keep
    # their own dtype (both match the pre-kernel gather path bit-for-bit).
    return q.dtype if v_scale is not None else v_pool.dtype


# ---------------------------------------------------------------------------
# CPU oracle: the token-identity reference.
# ---------------------------------------------------------------------------
@jax.jit
def paged_attention_ref(q, k_pool, v_pool, block_tables, pos,
                        k_scale=None, v_scale=None):
    """Pure-XLA oracle — the exact math of the pre-kernel gather read path.

    q (B, Hq, D); pools (N, bs, Hkv, D); block_tables (B, n_bt) int32
    (−1 = unallocated); pos (B,) absolute query positions; optional
    per-entry scales (N, bs, Hkv, 1) f32 for int8 pools.  Returns
    (B, Hq, D).  This is the token-identity reference: same einsum
    contractions, masking and dtype casts as ``attention.sdpa`` at Sq=1,
    so routing the decode step through it changes no serving token.
    """
    B, Hq, D = q.shape
    bs, Hkv = k_pool.shape[1], k_pool.shape[2]
    G = Hq // Hkv
    k = _gather(k_pool, block_tables)
    v = _gather(v_pool, block_tables)
    if k_scale is not None:
        k = (k.astype(jnp.float32)
             * _gather(k_scale, block_tables)).astype(q.dtype)
        v = (v.astype(jnp.float32)
             * _gather(v_scale, block_tables)).astype(q.dtype)
    k_pos = synth_positions(block_tables, bs)                   # (B, S)
    S = k_pos.shape[1]

    qg = q.reshape(B, 1, Hkv, G, D)                             # Sq = 1
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32)
    s = s.reshape(B, Hq, 1, S) * (D ** -0.5)
    m = (k_pos[:, None, :] >= 0) & (k_pos[:, None, :] <= pos[:, None, None])
    s = jnp.where(m[:, None], s, NEG_INF)                       # (B,Hq,1,S)
    p = jax.nn.softmax(s, axis=-1)
    pg = p.reshape(B, Hkv, G, 1, S)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", pg.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, Hq, D).astype(v.dtype)[:, 0]


# ---------------------------------------------------------------------------
# GPU fast path: dense gather + one-shot softmax in the activation dtype.
# ---------------------------------------------------------------------------
@jax.jit
def paged_attention_gather(q, k_pool, v_pool, block_tables, pos,
                           k_scale=None, v_scale=None):
    """Dense-gather fast path for non-TPU accelerators: materialize the
    gathered (and dequantized) KV once in the activation dtype and run a
    single tensor-core-eligible masked attention.  Same masking semantics
    as the oracle; accumulation order (one dense softmax vs the oracle's
    f32 upcast chain) may differ in the last ulp."""
    B, Hq, D = q.shape
    bs, Hkv = k_pool.shape[1], k_pool.shape[2]
    G = Hq // Hkv
    act = _out_dtype(q, v_pool, v_scale)
    k = _gather(k_pool, block_tables)
    v = _gather(v_pool, block_tables)
    if k_scale is not None:
        k = (k.astype(jnp.float32)
             * _gather(k_scale, block_tables)).astype(act)
        v = (v.astype(jnp.float32)
             * _gather(v_scale, block_tables)).astype(act)
    k_pos = synth_positions(block_tables, bs)
    S = k_pos.shape[1]
    s = jnp.einsum("bhgd,bkhd->bhgk", q.reshape(B, Hkv, G, D), k,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    m = (k_pos >= 0) & (k_pos <= pos[:, None])                  # (B, S)
    s = jnp.where(m[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)                              # (B,Hkv,G,S)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(act), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Hq, D).astype(act)


# ---------------------------------------------------------------------------
# The Pallas kernel.
# ---------------------------------------------------------------------------
def _paged_kernel_body(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref, *, bs, n_bt, n_kv, group,
                       quantized, ks_ref=None, vs_ref=None, stat_refs=()):
    """One (slot b, table entry j) grid step of the VMEM streaming walk."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    entry = bt_ref[b, j]                                 # scalar-prefetched
    q = q_ref[0].astype(jnp.float32)                     # (Hq, D)
    kb = k_ref[0]                                        # (bs, Hkv, D)
    vb = v_ref[0]
    if quantized:
        # fused dequant: codes * per-entry scale, inside VMEM, no HBM copy
        kb = kb.astype(jnp.float32) * ks_ref[0]
        vb = vb.astype(jnp.float32) * vs_ref[0]
    else:
        kb = kb.astype(jnp.float32)
        vb = vb.astype(jnp.float32)
    D = q.shape[-1]

    # grouped scores (Hq, bs): static loop over KV heads keeps every dot a
    # plain (G, D) x (D, bs) MXU contraction (no batched dot_general).
    s = jnp.concatenate(
        [jax.lax.dot_general(q[h * group:(h + 1) * group], kb[:, h, :],
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
         for h in range(n_kv)], axis=0) * (D ** -0.5)

    # synthesized key positions: entry −1 -> whole block invalid; offsets
    # past the query position -> causally masked (covers stale pool rows).
    k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    ok = (entry >= 0) & (k_pos <= pos_ref[b])            # (1, bs)
    s = jnp.where(ok, s, NEG_INF)

    # online-softmax update.  p is re-masked (not just exp'd) so an
    # all-invalid prefix (m still == NEG_INF) contributes exactly zero.
    m_prev = m_ref[...]                                  # (Hq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)           # (Hq, bs)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.concatenate(
        [jax.lax.dot_general(p[h * group:(h + 1) * group], vb[:, h, :],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
         for h in range(n_kv)], axis=0)                  # (Hq, D)
    acc_ref[...] = alpha * acc_ref[...] + pv
    m_ref[...] = m_new

    @pl.when(j == n_bt - 1)
    def _epilogue():
        l = l_ref[...]
        # no visible key at all (inactive slot): exact zero output
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)
        if stat_refs:                        # (m, l) for a cross-shard merge
            stat_refs[0][0] = m_ref[...]
            stat_refs[1][0] = l


@functools.partial(jax.jit, static_argnames=("interpret", "with_stats"))
def paged_attention_pallas(q, k_pool, v_pool, block_tables, pos,
                           k_scale=None, v_scale=None, *, interpret=False,
                           with_stats=False):
    """Flash-decode paged attention: stream pool blocks through VMEM one
    block-table entry at a time.  Same signature/semantics as
    :func:`paged_attention_ref` (to fp32 accumulation-order tolerance;
    exactly for the masking pattern).

    ``with_stats=True`` also returns each row's online-softmax running max
    ``m`` and sum ``l`` ((B, Hq, 1) f32 each), so partial results over
    disjoint key sets (pool shards) can be merged exactly."""
    B, Hq, D = q.shape
    N, bs, Hkv, _ = k_pool.shape
    n_bt = block_tables.shape[1]
    group = Hq // Hkv
    quantized = k_scale is not None
    block_tables = block_tables.astype(jnp.int32)
    pos = pos.astype(jnp.int32)

    def _pool_idx(b, j, bt_ref, pos_ref):
        # −1 (unallocated) clamps to block 0; its contributions are masked
        # in-kernel, so the load is a harmless (already-resident) prefetch.
        return (jnp.maximum(bt_ref[b, j], 0), 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, Hq, D), lambda b, j, bt, pp: (b, 0, 0)),
        pl.BlockSpec((1, bs, Hkv, D), _pool_idx),
        pl.BlockSpec((1, bs, Hkv, D), _pool_idx),
    ]
    operands = [q, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, bs, Hkv, 1), _pool_idx),
                     pl.BlockSpec((1, bs, Hkv, 1), _pool_idx)]
        operands += [k_scale, v_scale]

    body = functools.partial(
        _paged_kernel_body, bs=bs, n_bt=n_bt, n_kv=Hkv, group=group,
        quantized=quantized)
    n_in = len(operands)
    n_out = 3 if with_stats else 1

    def kernel(bt, pp, *refs):
        # positional order: inputs (q, k, v[, k_scale, v_scale]), outputs
        # (o[, m, l]), then the three scratch buffers
        ins, outs = refs[:n_in], refs[n_in:n_in + n_out]
        scales = ins[3:] if quantized else (None, None)
        body(bt, pp, *ins[:3], outs[0], *refs[n_in + n_out:],
             ks_ref=scales[0], vs_ref=scales[1], stat_refs=outs[1:])

    o_shape = jax.ShapeDtypeStruct((B, Hq, D), _out_dtype(q, v_pool, v_scale))
    out_specs = pl.BlockSpec((1, Hq, D), lambda b, j, bt, pp: (b, 0, 0))
    out_shape = o_shape
    if with_stats:
        st_spec = pl.BlockSpec((1, Hq, 1), lambda b, j, bt, pp: (b, 0, 0))
        st_shape = jax.ShapeDtypeStruct((B, Hq, 1), jnp.float32)
        out_specs = (out_specs, st_spec, st_spec)
        out_shape = (o_shape, st_shape, st_shape)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_bt),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((Hq, 1), jnp.float32),            # running max m
            pltpu.VMEM((Hq, 1), jnp.float32),            # running sum l
            pltpu.VMEM((Hq, D), jnp.float32),            # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, pos, *operands)
    return out


# ---------------------------------------------------------------------------
# Traffic model (benchmarks/kernel_bench.py + CI assert on BENCH_kernel.json).
# ---------------------------------------------------------------------------
def gathered_bytes(B, n_bt, bs, n_kv, head_dim, *, quantized,
                   act_bytes=2):
    """Bytes of dense temporaries the *gather* read path materializes per
    decode step per layer — the quantity the Pallas kernel eliminates.

    K and V each gather (B, n_bt*bs, Hkv, hd) in the pool dtype; int8 pools
    additionally gather the per-entry scales and materialize a second,
    dequantized activation-dtype copy of both tensors."""
    entries = B * n_bt * bs * n_kv
    pool_bytes = 1 if quantized else act_bytes
    total = 2 * entries * head_dim * pool_bytes          # gathered K + V
    if quantized:
        total += 2 * entries * 4                         # gathered scales
        total += 2 * entries * head_dim * act_bytes      # dequantized copies
    return total


def streamed_bytes(n_valid_entries, bs, n_kv, head_dim, *, quantized,
                   act_bytes=2):
    """Pool bytes the kernel actually streams through VMEM: each *valid*
    block-table entry's K and V block (plus scales when quantized), read
    once, never re-materialized."""
    per_entry = bs * n_kv * head_dim * (1 if quantized else act_bytes)
    total = 2 * n_valid_entries * per_entry
    if quantized:
        total += 2 * n_valid_entries * bs * n_kv * 4
    return total
