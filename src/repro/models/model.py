"""Model facade: one object per architecture config exposing

  * ``init(key)``                     -> params
  * ``loss(params, batch)``           -> (scalar, metrics)   [train_4k]
  * ``prefill(params, batch)``        -> (logits, cache)     [prefill_32k]
  * ``decode_step(params, batch)``    -> (logits, new cache) [decode_32k/long_500k]
  * ``init_cache(batch, seq_len)``    -> cache pytree
  * ``quantize(params, bits, pack)``  -> PSI serving params (the paper's
                                         technique as a first-class feature)

``batch`` layouts per family are produced by ``input_specs``/``make_batch`` in
repro.launch.dryrun / repro.data.pipeline.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import quantizer
from repro.models import attention, kvcache as kvc, layers, transformer
from repro.models.kvcache import KVCache
from repro.quant import embed, linear, tied_logits
from repro.runtime import sharding as shr


@functools.partial(jax.jit, donate_argnums=0)
def _set_row(stacked, i, row):
    """``stacked[:, ...][i] = row`` leaf by leaf, in place (donated)."""
    return jax.tree_util.tree_map(lambda full, r: full.at[i].set(r),
                                  stacked, row)


def _lm_positions(B, S, offset=0):
    return jnp.broadcast_to(jnp.arange(offset, offset + S)[None], (B, S))


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: object

    # ------------------------------------------------------------------ init
    @staticmethod
    def _init_keys(key):
        """(embed, stack, lm_head, encoder) keys of :meth:`init`."""
        return jax.random.split(key, 4)

    def init(self, key) -> dict:
        params = self._init_outer(key)
        params["stack"] = transformer.init_decoder_stack(
            self.cfg, self._init_keys(key)[1])
        return params

    def _init_outer(self, key) -> dict:
        """Every parameter of :meth:`init` except the decoder stack."""
        return {name: draw() for name, draw in self._outer_draws(key).items()}

    def _outer_draws(self, key) -> dict:
        """name -> thunk drawing that top-level parameter (all but the
        decoder stack), so :meth:`init_serving` can draw one at a time."""
        cfg = self.cfg
        k_e, _, k_h, k_enc = self._init_keys(key)
        d = cfg.d_model
        draws = {
            "embed": lambda: jax.random.normal(
                k_e, (cfg.vocab_size, d), jnp.float32) * d ** -0.5,
            "norm_f": lambda: layers.init_norm(cfg, d),
        }
        if not cfg.tie_embeddings:
            draws["lm_head"] = lambda: jax.random.normal(
                k_h, (d, cfg.vocab_size), jnp.float32) * d ** -0.5
        if cfg.family == "encdec":
            draws["encoder"] = lambda: transformer.init_encoder_stack(
                cfg, k_enc)
            draws["enc_norm_f"] = lambda: layers.init_norm(cfg, d)
        return draws

    def quantize(self, params, bits: int = None, pack: bool = False,
                 policy=None) -> dict:
        """PSI serving format: uniform ``bits`` and/or a per-layer mixed-
        precision ``policy`` ({"embed": 8, "w_down": 4, "default": 5})."""
        return quantizer.quantize_param_tree(params, bits, pack=pack,
                                             policy=policy)

    def init_serving(self, key, bits: int = None, *, pack: bool = False,
                     policy=None, precondition: int = 0) -> dict:
        """Serving parameters without the float tree:
        ``quantize(init(key), bits, pack, policy)`` — after
        ``fake_quant_param_tree(init(key), precondition)`` when
        ``precondition`` is set — built one layer at a time.

        The float leaves are drawn from the same key split and by the same
        eager ops as :meth:`init`, one scanned layer group at a time; each
        is converted and only the result kept, written into its row of the
        stacked leaf by a donated in-place update.  Scales reduce per layer
        along the contraction axis, so the result equals the whole-tree
        path bit for bit, while peak device memory is the serving tree plus
        one layer's float leaves (or one f32 table, the embedding) —
        not the float tree, which at qwen3-8b's widths is 32.8 GB.
        ``bits=None`` with no policy keeps every leaf in float."""
        cfg = self.cfg
        policy = quantizer.parse_policy(policy)
        quantized = bits is not None or bool(policy)

        def convert(path, leaf):
            name = quantizer._path_str(path)
            if precondition:
                leaf = quantizer.fake_quant_leaf(name, leaf, precondition)
            if not quantized:
                return leaf
            return quantizer.quantize_leaf(name, leaf, bits, pack, policy)

        quantizer.check_policy(policy, jax.eval_shape(self.init, key))
        params = {}
        for name, draw in self._outer_draws(key).items():
            # one top-level float leaf alive at a time (the f32 embedding
            # and lm_head are 2.5 GB each at qwen3-8b's widths)
            top = (jax.tree_util.DictKey(name),)
            params[name] = jax.tree_util.tree_map_with_path(
                lambda path, leaf: convert(top + path, leaf), draw())
        _, n_groups, tail_kinds = transformer._stack_groups(cfg)
        keys = transformer.decoder_stack_keys(cfg, self._init_keys(key)[1])
        group_path = (jax.tree_util.DictKey("stack"),
                      jax.tree_util.DictKey("groups"))
        groups = None
        for i in range(n_groups):
            row = jax.tree_util.tree_map_with_path(
                lambda path, leaf: convert(group_path + path, leaf),
                transformer.init_group(cfg, keys[i]))
            if groups is None:
                groups = jax.tree_util.tree_map(
                    lambda a: jnp.zeros((n_groups,) + a.shape, a.dtype), row)
            groups = _set_row(groups, i, row)
        tail = [transformer.init_block(cfg, keys[n_groups + i], kind)
                for i, kind in enumerate(tail_kinds)]
        tail_path = (jax.tree_util.DictKey("stack"),
                     jax.tree_util.DictKey("tail"))
        tail = jax.tree_util.tree_map_with_path(
            lambda path, leaf: convert(tail_path + path, leaf), tail)
        params["stack"] = {"groups": groups, "tail": tail}
        return params

    # -------------------------------------------------------------- embedding
    def _embed_tokens(self, params, tokens, batch):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = embed(params["embed"], tokens, dtype)
        if cfg.family == "vlm" and "vision_embeds" in batch:
            P = batch["vision_embeds"].shape[1]
            x = jnp.concatenate(
                [batch["vision_embeds"].astype(dtype), x[:, P:]], axis=1)
        if cfg.rope == "sinusoidal":
            pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None],
                                   (x.shape[0], x.shape[1]))
            x = x + layers.sinusoidal_from_positions(pos, cfg.d_model, dtype)
        return x

    def _positions(self, batch, B, S, offset=0):
        if "positions" in batch:
            return batch["positions"]
        return _lm_positions(B, S, offset)

    def _encode(self, params, batch):
        """Whisper encoder over precomputed (stub) frame embeddings."""
        cfg = self.cfg
        frames = batch["frames"].astype(jnp.dtype(cfg.dtype))
        x = frames + layers.sinusoidal_embedding(
            frames.shape[1], cfg.d_model, dtype=frames.dtype)[None]
        x = transformer.apply_encoder_stack(params["encoder"], x, cfg)
        return layers.apply_norm(params["enc_norm_f"], x, cfg)

    def _logits(self, params, x):
        cfg = self.cfg
        if cfg.tie_embeddings:
            return tied_logits(params["embed"], x, cfg.quant_mode)
        return linear(params["lm_head"], x, cfg.quant_mode)

    # ----------------------------------------------------------- full forward
    def forward(self, params, batch, collect_cache=False, pos0=0,
                ctx_kv=None, emit_logits=True):
        """``pos0``/``ctx_kv`` (prefix-cache suffix prefill, DESIGN.md §3):
        positions start at ``pos0`` (RoPE and the causal mask are driven by
        absolute positions) and attention additionally sees the shared
        prefix KV in ``ctx_kv`` covering ``[0, pos0)``.

        ``emit_logits=False`` (chunked prefill's intermediate chunks,
        DESIGN.md §3 "SLO scheduling") skips the lm-head entirely and
        returns ``None`` logits — only the KV states matter, and the
        (S, d_model) x (d_model, V) projection is the dominant FLOP of a
        chunk that emits nothing."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        enc_out = self._encode(params, batch) if cfg.family == "encdec" else None
        x = self._embed_tokens(params, tokens, batch)
        positions = self._positions(batch, B, S, offset=pos0)
        x, states, aux = transformer.apply_decoder_stack(
            params["stack"], x, cfg, positions, enc_kv=enc_out,
            collect_cache=collect_cache, ctx_kv=ctx_kv)
        x = layers.apply_norm(params["norm_f"], x, cfg)
        logits = self._logits(params, x) if emit_logits else None
        return logits, states, aux, enc_out

    def loss(self, params, batch):
        """Next-token cross-entropy (shift-inside); returns (loss, metrics).

        Sharding note: the gold logit is extracted with a fused one-hot
        einsum, NOT take_along_axis — a gather along the model-sharded vocab
        dim makes the SPMD partitioner replicate the batch dim of the f32
        logits (observed: 5x 40 GB buffers/device at train_4k scale)."""
        logits, _, aux, _ = self.forward(params, batch)
        tokens = batch["tokens"]
        lg = logits[:, :-1]                      # stay bf16: the f32 cast
        tg = tokens[:, 1:]                       # materializes (B,S,V) f32
        # max-subtracted logsumexp with f32 ACCUMULATION but bf16 storage —
        # the convert/exp chain fuses into the reduction.
        m = jax.lax.stop_gradient(jnp.max(lg, axis=-1, keepdims=True))
        ex = jnp.exp(lg - m)                 # bf16 storage (backward residual
        #                                      is (T, V) — f32 doubles it)
        logz = (jnp.log(jnp.sum(ex, axis=-1, dtype=jnp.float32))
                + m[..., 0].astype(jnp.float32))
        # gold logit via bf16 one-hot product (fuses into the reduction).
        # A/B'd against iota-compare (materializes (B,S,V) s32 buffers) and
        # vmap'd take_along_axis (+4 GB on the 256k-vocab arch): best-or-tied
        # on every architecture.
        oh = jax.nn.one_hot(tg, lg.shape[-1], dtype=lg.dtype)
        gold = jnp.sum((lg * oh).astype(jnp.float32), axis=-1)
        mask = jnp.ones_like(tg, jnp.float32)
        if self.cfg.family == "vlm" and self.cfg.vision_patches:
            # vision positions carry no next-token target
            pos = jnp.arange(tg.shape[1])[None]
            mask = jnp.broadcast_to(pos >= self.cfg.vision_patches - 1,
                                    tg.shape).astype(jnp.float32)
        ce = jnp.sum((logz - gold) * mask) / jnp.maximum(mask.sum(), 1.0)
        total = ce + aux
        return total, {"ce": ce, "aux": aux}

    # ---------------------------------------------------------------- serving
    def init_cache(self, batch, seq_len, dtype=jnp.bfloat16, mesh=None,
                   layout=None, block_size=None, n_blocks=None) -> KVCache:
        """Batched decode cache as a typed :class:`KVCache` (DESIGN.md §3).

        ``layout`` defaults to ``cfg.resolved_cache_layout``: "dense" builds
        the classic per-slot slab; "paged" builds per-layer block pools of
        ``n_blocks`` usable blocks (default: dense-equivalent capacity,
        ``batch * ceil(seq_len / block_size)``) plus ``batch`` per-slot
        scratch blocks.  With ``mesh`` (threaded in by the Executor — its
        only cache-construction path), every leaf is committed to its
        serving sharding — slot/pool dim over the data axes, heads/state
        channels over "model" (DESIGN.md §5).  ``mesh=None`` (direct model
        use, eval_shape) skips placement."""
        cfg = self.cfg
        layout = layout or cfg.resolved_cache_layout
        if layout == kvc.PAGED:
            bs = block_size or cfg.cache_block_size
            nb = (n_blocks if n_blocks is not None
                  else batch * kvc.blocks_for(seq_len, bs))
            kv = transformer.init_paged_stack_cache(cfg, nb + batch, bs,
                                                    dtype)
            cache = KVCache(kv, None, kvc.PAGED, bs, nb)
        else:
            kv = transformer.init_stack_cache(cfg, batch, seq_len, dtype)
            enc_out = (jnp.zeros((batch, cfg.enc_frames, cfg.d_model), dtype)
                       if cfg.family == "encdec" else None)
            cache = KVCache(kv, enc_out)
        if mesh is not None:
            cache = jax.device_put(cache, shr.to_shardings(
                shr.cache_specs(cfg, mesh, cache), mesh))
        return cache

    def prefill(self, params, batch, cache_len=None, true_lens=None,
                pos0=0, ctx_kv=None, emit_logits=True):
        """Forward the prompt, return (last-token logits, decode cache).

        The returned :class:`KVCache` is always DENSE layout — a
        per-sequence cache in position order.  Under the paged engine the
        executor prefills at the bucketed length and ``insert_cache``
        scatters these rows into the allocated pool blocks (DESIGN.md §3).

        ``true_lens`` (B,) int32 supports right-padded prompts (the serving
        engine's bucketed prefill, DESIGN.md §3): last-token logits are
        gathered at ``true_lens - 1`` and KV slots past the true length are
        marked empty (k_pos = -1) so decode attention never sees pad keys.
        Only attention caches can be pad-masked post-hoc — recurrent
        (rg-lru / mamba) state absorbs pad tokens, so the engine prefills
        those families at exact lengths.

        ``pos0``/``ctx_kv`` (prefix-cache SUFFIX prefill, DESIGN.md §3):
        ``batch["tokens"]`` then holds only the uncached prompt suffix,
        positions run ``[pos0, pos0 + S)`` so RoPE and the causal mask see
        true positions, attention additionally reads the shared-prefix KV
        in ``ctx_kv``, and the returned cache covers the suffix rows only
        (``true_lens`` stays suffix-relative — it indexes the suffix
        logits; the pad mask shifts by ``pos0`` internally).
        """
        cfg = self.cfg
        S = batch["tokens"].shape[1]
        cache_len = cache_len or S
        logits, states, _, enc_out = self.forward(params, batch,
                                                  collect_cache=True,
                                                  pos0=pos0, ctx_kv=ctx_kv,
                                                  emit_logits=emit_logits)
        kv = _states_to_cache(cfg, states, S, cache_len)
        enc = enc_out if cfg.family == "encdec" else None
        if true_lens is None:
            return (logits[:, -1] if emit_logits else None), KVCache(kv, enc)
        last = None
        if emit_logits:
            B = logits.shape[0]
            last = logits[jnp.arange(B), true_lens - 1]
        # k_pos entries are ABSOLUTE positions, so the pad threshold is
        # pos0 + suffix true length
        return last, KVCache(_mask_padded_kv(kv, true_lens + pos0), enc)

    def gather_prefix_ctx(self, cache: KVCache, ctx_ids, dtype=jnp.bfloat16):
        """Dense per-group context KV for the shared-prefix blocks
        ``ctx_ids`` of a PAGED engine cache (the ``ctx_kv`` input of
        :meth:`prefill`; DESIGN.md §3 "Prefix cache")."""
        if not cache.paged:
            raise ValueError("prefix context is gathered from a paged "
                             "cache; this cache is dense")
        return transformer.gather_paged_ctx(cache.kv, ctx_ids, dtype)

    def decode_step(self, params, batch, cache: KVCache, mesh=None):
        """batch: {"token": (B,1), "pos": (B,1) or "positions": (B,3,1),
        optional "active": (B,) bool, "block_table": (B, n_bt) int32 when
        ``cache.layout == "paged"``}.  Rows with ``active`` False compute a
        throwaway logit but leave their cache/state rows untouched — the
        masked-decode contract that lets the continuous-batching engine keep
        the jitted step shape-stable over free slots (DESIGN.md §3).  The
        cache layout is dispatched on the typed cache itself, so a dense
        cache (e.g. straight from ``prefill``) decodes dense regardless of
        the config's serving default.

        ``mesh`` (threaded in by the Executor) pins every masked cache write
        to its serving sharding via a block-level constraint inside the
        layer scan (DESIGN.md §5); None / one device is the unsharded path.
        """
        cfg = self.cfg
        token = batch["token"]
        x = embed(params["embed"], token, jnp.dtype(cfg.dtype))
        positions = batch.get("positions", batch.get("pos"))
        if cfg.rope == "sinusoidal":
            x = x + layers.sinusoidal_from_positions(
                positions, cfg.d_model, jnp.dtype(cfg.dtype))
        bt = batch.get("block_table") if cache.paged else None
        if cache.paged and bt is None:
            raise ValueError('paged decode needs batch["block_table"] '
                             "(B, n_bt) int32, -1 = unallocated")
        constrain = None
        if mesh is not None and mesh.size > 1:
            constrain = functools.partial(shr.constrain_block_cache, cfg,
                                          mesh, paged=cache.paged)
        enc_out = cache.enc_out
        x, new_kv = transformer.apply_decoder_stack_decode(
            params["stack"], x, cfg, positions, cache.kv, enc_kv=enc_out,
            active=batch.get("active"), constrain=constrain,
            block_tables=bt)
        x = layers.apply_norm(params["norm_f"], x, cfg)
        logits = self._logits(params, x)
        return logits[:, 0], cache.replace(kv=new_kv)

    def decode_scan(self, params, batch, cache: KVCache, length, mesh=None):
        """Fused M-step greedy decode loop with IN-KERNEL retirement
        (DESIGN.md §3 "Multi-step decode & host overlap").

        batch: {"token": (B, 1), "pos": (B, 1), "active": (B,) bool,
        "remaining": (B,) int32 — per-slot emission budget (max_new minus
        tokens already emitted), "eos_id": () int32 scalar (-1 disables;
        greedy tokens are always >= 0), optional "block_table": (B, n_bt)}.

        Each step runs the standard masked :meth:`decode_step` body, then
        applies the retirement recurrence ON DEVICE::

            remaining -= active            # this step consumed one budget
            active   &= (next != eos_id) & (remaining > 0)

        so a slot that hits EOS or exhausts max_new mid-round rides out the
        rest of the round with ``active`` False — the masked-decode contract
        freezes its cache rows, making the extra steps pure throwaway
        compute.  ``pos`` advances only on entry-active steps and ``token``
        freezes at the last live emission, so the returned carry is exactly
        the state a step-at-a-time host loop would have produced: the host
        replays the same recurrence (``scheduler.replay_round``) over the
        raw (M, B) token block to recover the bit-identical streams.  The
        block table is scan-invariant: the host pre-allocates every block
        the round can touch before dispatch (same contract as the
        speculative draft scan).

        Returns ((M, B) raw per-step greedy tokens, final carry dict with
        the same token/pos/active/remaining keys, cache).
        """
        bt = batch.get("block_table") if cache.paged else None
        if cache.paged and bt is None:
            raise ValueError('paged decode_scan needs batch["block_table"]')
        eos = batch["eos_id"]

        def step(carry, _):
            tok, p, act, rem, kv = carry
            b = {"token": tok, "pos": p, "active": act}
            if self.cfg.rope == "mrope":
                b["positions"] = jnp.broadcast_to(
                    p[:, None, :], (p.shape[0], 3, 1))
            if bt is not None:
                b["block_table"] = bt
            logits, kv = self.decode_step(params, b, kv, mesh=mesh)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)        # (B,)
            rem = rem - act.astype(jnp.int32)
            new_act = act & (nxt != eos) & (rem > 0)
            tok = jnp.where(act[:, None], nxt[:, None], tok)
            p = p + act[:, None].astype(jnp.int32)
            return (tok, p, new_act, rem, kv), nxt

        (tok, p, act, rem, cache), toks = jax.lax.scan(
            step, (batch["token"], batch["pos"], batch["active"],
                   batch["remaining"], cache), None, length=length)
        carry = {"token": tok, "pos": p, "active": act, "remaining": rem}
        return toks, carry, cache

    def verify_step(self, params, batch, cache: KVCache, mesh=None):
        """Speculative VERIFY: score k consecutive tokens per slot in one
        decode-shaped batched pass (DESIGN.md §"Self-speculative decoding").

        batch: {"tokens": (B, k) — the round's feed token then the first
        k-1 drafted tokens, "pos0": (B, 1) — the feed token's absolute
        position, optional "active": (B,) bool, "block_table": (B, n_bt)}.
        Positions run ``pos0 + [0, k)`` per row.  Returns (logits (B, k, V),
        new cache); ``argmax(logits[:, j-1])`` is the target model's greedy
        token after consuming draft j-1 — the verdict the acceptance rule
        compares drafts against.  The pass re-scatters target-computed KV
        over all k positions, replacing what the draft pass wrote (the
        rollback scheme: rejected-tail entries stay stale only until the
        next round's writes reach them, and no earlier-position query can
        ever attend to them).  Paged caches only.
        """
        cfg = self.cfg
        if not cache.paged:
            raise ValueError("speculative verify runs against the paged "
                             "cache layout only")
        tokens = batch["tokens"]
        B, S = tokens.shape
        positions = batch["pos0"] + jnp.arange(S, dtype=jnp.int32)[None]
        x = embed(params["embed"], tokens, jnp.dtype(cfg.dtype))
        if cfg.rope == "sinusoidal":
            x = x + layers.sinusoidal_from_positions(
                positions, cfg.d_model, jnp.dtype(cfg.dtype))
        constrain = None
        if mesh is not None and mesh.size > 1:
            constrain = functools.partial(shr.constrain_block_cache, cfg,
                                          mesh, paged=True)
        x, new_kv = transformer.apply_decoder_stack_verify(
            params["stack"], x, cfg, positions, cache.kv,
            batch["block_table"], active=batch.get("active"),
            constrain=constrain)
        x = layers.apply_norm(params["norm_f"], x, cfg)
        logits = self._logits(params, x)
        return logits, cache.replace(kv=new_kv)

    def slice_cache(self, cache: KVCache, row) -> KVCache:
        """Batch row ``row`` of a batched DENSE cache as a batch-1 cache
        (the counterpart of ``insert_cache`` for splitting batched
        prefills; the burst path slices the dense prefill output even when
        the engine cache is paged)."""
        if cache.paged:
            raise ValueError("slice_cache slices per-slot rows; a paged "
                             "cache has no slot rows to slice")
        kv = transformer.slice_stack_cache(cache.kv, row)
        enc = (None if cache.enc_out is None else
               jax.lax.dynamic_slice_in_dim(cache.enc_out, row, 1, axis=0))
        return cache.replace(kv=kv, enc_out=enc)

    def insert_cache(self, cache: KVCache, seq_cache: KVCache, slot,
                     block_row=None) -> KVCache:
        """Admit one prefilled sequence (batch-1 dense ``seq_cache``) into
        the engine cache (DESIGN.md §3): dense writes row ``slot`` across
        every leaf; paged scatters the sequence's rows into the pool blocks
        named by ``block_row`` (n_bt,) int32 (-1 tail entries route to the
        slot's scratch block).  ``slot`` / ``block_row`` may be traced, so
        one jitted insertion covers all slots/tables."""
        if cache.paged:
            if block_row is None:
                raise ValueError("paged insert_cache needs block_row")
            kv = transformer.insert_paged_stack_cache(
                cache.kv, seq_cache.kv, block_row, cache.n_blocks + slot)
            return cache.replace(kv=kv)
        kv = transformer.insert_stack_cache(cache.kv, seq_cache.kv, slot)
        enc = cache.enc_out
        if enc is not None:
            enc = enc.at[slot].set(seq_cache.enc_out[0].astype(enc.dtype))
        return cache.replace(kv=kv, enc_out=enc)


def _ring_layout(arr, S, C):
    """Training-layout (B, S, ...) sequence -> ring-buffer (B, C, ...) cache
    holding the last min(S, C) entries at slots pos % C.  Positions are the
    contiguous prefill range [0, S), so the layout is a pad (S <= C) or a
    roll of the tail window (S > C) — no scatter needed."""
    if S <= C:
        pad = [(0, 0), (0, C - S)] + [(0, 0)] * (arr.ndim - 2)
        return jnp.pad(arr, pad)
    tail = arr[:, -C:]
    return jnp.roll(tail, shift=(S - C) % C, axis=1)


def _states_to_cache(cfg, states, S, cache_len):
    g_states, t_states = states
    group_kinds, _, tail_kinds = transformer._stack_groups(cfg)

    def conv(kind, st, stacked):
        if st is None:
            return st
        if kind in ("attn", "xattn"):
            C = (min(cache_len, cfg.window)
                 if (cfg.attn_type == "swa" and cfg.window) else cache_len)
            def ring(a):
                return (jax.vmap(lambda x: _ring_layout(x, S, C))(a)
                        if stacked else _ring_layout(a, S, C))
            k_pos = st["k_pos"]
            kp = ring(jnp.where(k_pos >= 0, k_pos, -1)) if S <= C else ring(k_pos)
            if S < C:  # padded slots must read as empty
                if stacked:
                    mask = jnp.arange(C)[None, None] < S
                else:
                    mask = jnp.arange(C)[None] < S
                kp = jnp.where(mask, kp, -1)
            k_ring, v_ring = ring(st["k"]), ring(st["v"])
            if cfg.kv_quant == "int8":
                from repro.models.attention import _kv_quantize
                kq, ks = _kv_quantize(k_ring)
                vq, vs = _kv_quantize(v_ring)
                return {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs,
                        "k_pos": kp}
            return {"k": k_ring, "v": v_ring, "k_pos": kp}
        return st  # rec / mamba states are already final

    new_g = {}
    for i, kind in enumerate(group_kinds):
        new_g[f"b{i}"] = conv(kind, g_states[f"b{i}"], stacked=True)
    new_t = [conv(kind, st, stacked=False)
             for kind, st in zip(tail_kinds, t_states)]
    return (new_g, new_t)


def _mask_padded_kv(kv_cache, true_lens):
    """Mark prefilled KV slots whose absolute position is past the true
    prompt length as empty (k_pos = -1).  Positions are absolute, so this is
    layout-independent (works for padded and SWA-rolled ring caches alike)."""
    g_cache, t_cache = kv_cache

    def fix(st, stacked):
        if not isinstance(st, dict) or "k_pos" not in st:
            return st
        tl = true_lens.reshape((1, -1, 1) if stacked else (-1, 1))
        st = dict(st)
        st["k_pos"] = jnp.where(
            (st["k_pos"] >= 0) & (st["k_pos"] < tl), st["k_pos"], -1)
        return st

    new_g = {k: fix(v, stacked=True) for k, v in g_cache.items()}
    new_t = [fix(v, stacked=False) for v in t_cache]
    return (new_g, new_t)


def build_model(cfg) -> Model:
    return Model(cfg)
