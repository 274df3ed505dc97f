"""Mesh-native execution substrate for the serving engine (DESIGN.md §5).

The Executor is the device half of the serving stack: it owns the mesh
lifecycle, `NamedSharding` placement for every leaf (PSI-quantized params,
the slot-based decode cache, decode-step inputs), and the jit compilation +
donation contract for the serving entry points — prefill, decode_step, and
cache insert/slice.  `repro.launch.serve.Server` is the host half (scheduler
loop, buckets, accounting) and routes ALL device work through one Executor,
so there is exactly one compilation path whether the mesh has 1 device or a
pod.

Placement contract (derived in ``repro.runtime.sharding``):
  * params: tensor-parallel over "model" (quantized codes/planes follow the
    logical weight rule; scale shards only its non-singleton dims);
  * decode cache + decode inputs: slot dim over the data axes — the
    scheduler partitions slots into per-shard pools via ``slot_shard_map``;
  * donation: the engine cache is donated at every entry point that
    consumes it (decode, fused prefill+insert, burst insert) — the caller
    rebinds the returned cache, and XLA aliases the update in place.

Elastic integration (single-device path is a no-op): ``from_devices`` sizes
the mesh with ``elastic.plan_remesh``; ``remesh`` rebuilds the Executor on a
surviving device count, resharding params by device_put (the load path
itself); a ``StragglerMonitor`` is attached only when more than one process
participates.
"""
from __future__ import annotations

import functools
import warnings

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import quantizer
from repro.core.psi import QuantizedTensor
from repro.kernels import ops
from repro.models import kvcache as kvc
from repro.runtime import sharding as shr
from repro.runtime.elastic import make_mesh_from_plan, plan_remesh
from repro.runtime.straggler import StragglerMonitor

# XLA's TPU compiler computes a chain of bf16 elementwise ops in f32 inside a
# fusion and rounds only where the fusion ends, so where it rounds depends
# on how the program fused.  A sharded program fuses differently from the
# one-device program (collectives split fusions): left to that, the same
# model rounds in different places on a mesh and on one device, and greedy
# tokens drift apart.  Every entry point compiles with bf16 results rounded
# after each op, as written (DESIGN.md §5).
STRICT_ROUNDING = {"xla_allow_excess_precision": False}


def single_device_mesh():
    """The degenerate (1, 1) data x model mesh: every spec resolves to
    replicated-on-one-device, so the Executor's single-device behavior is
    bit-identical to unsharded jit."""
    dev = np.asarray(jax.devices()[:1]).reshape(1, 1)
    return jax.sharding.Mesh(dev, ("data", "model"))


class DeviceBlockTable:
    """Host-mirrored, device-resident block table (DESIGN.md §3 "Multi-step
    decode & host overlap").

    The host (max_batch, n_bt) int32 mirror stays authoritative — the
    scheduler reads and writes it exactly like the plain ndarray it
    replaces — but every ``__setitem__`` records which slot rows went dirty,
    and :meth:`device` refreshes the cached device copy INCREMENTALLY: an
    unchanged table returns the same committed device array (zero host->
    device transfer, regression-tested), a few dirty rows go up as one-row
    scatters through a single jitted ``at[slot].set(row)`` executable, and
    only a mostly-rewritten table falls back to a full upload.  ``stats``
    counts each path so the serve loop can report transfer behavior.
    """

    def __init__(self, executor: "Executor"):
        if not executor.paged:
            raise ValueError("DeviceBlockTable mirrors the paged layout's "
                             "block table; this executor is dense")
        self._ex = executor
        self.host = np.full((executor.max_batch, executor.n_bt), -1,
                            np.int32)
        self._device = None
        self._dirty = set()
        self.version = 0                       # host mutation counter
        self.stats = {"reuses": 0, "row_updates": 0, "full_uploads": 0}

    @property
    def shape(self):
        return self.host.shape

    def __getitem__(self, idx):
        return self.host[idx]

    def __setitem__(self, idx, val):
        self.host[idx] = val
        slot = idx[0] if isinstance(idx, tuple) else idx
        for s in np.atleast_1d(np.asarray(slot)).reshape(-1):
            self._dirty.add(int(s))
        self.version += 1

    def device(self):
        """The table as a committed device array in the decode-step input
        sharding, refreshed only where the host mirror changed since the
        last call."""
        sh = self._ex._step_shardings["block_table"]
        if self._device is None or 2 * len(self._dirty) >= self.host.shape[0]:
            self._device = jax.device_put(jnp.asarray(self.host), sh)
            self.stats["full_uploads"] += 1
        elif self._dirty:
            # one (n_bt,) row per dirty slot through the shared scatter
            # executable — NOT donated: an in-flight pipelined round may
            # still be reading the previous table version.
            for s in sorted(self._dirty):
                self._device = self._ex._bt_set_row(
                    self._device, jnp.int32(s), jnp.asarray(self.host[s]))
            self.stats["row_updates"] += len(self._dirty)
        else:
            self.stats["reuses"] += 1
        self._dirty.clear()
        return self._device


class Executor:
    """Owns mesh, placement, and the compiled serving entry points."""

    def __init__(self, cfg, params, *, max_batch: int, max_seq: int,
                 mesh=None, model=None, n_blocks: int = None,
                 speculative=None, decode_horizon: int = 1):
        if model is None:
            from repro.models import build_model   # lazy: models imports us
            model = build_model(cfg)
        self.cfg = cfg
        self.model = model
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.mesh = mesh if mesh is not None else single_device_mesh()
        self.dtype = jnp.dtype(cfg.dtype)

        # ---- cache layout (DESIGN.md §3): dense slot slabs or a paged
        # block pool driven by host-side block tables ----
        self.layout = cfg.resolved_cache_layout
        self.paged = self.layout == kvc.PAGED
        self.block_size = cfg.cache_block_size if self.paged else 0
        if self.paged:
            # logical blocks a slot can address; the pool adds max_batch
            # scratch blocks for masked/inactive writes
            self.n_bt = kvc.table_width(max_seq, self.block_size)
            self.n_blocks = (n_blocks if n_blocks is not None
                             else max_batch * self.n_bt)
            # resolved read-side route for the decode step (kernels.ops:
            # pallas / gather / ref / interpret).  Pinned at construction so
            # serve stats report the route the compiled executable actually
            # traced — the backend cannot change under a live Executor.
            self.paged_attn_route = ops.paged_attn_route()
        else:
            self.n_bt = 0
            self.n_blocks = 0
            self.paged_attn_route = None
            if n_blocks is not None:
                raise ValueError("n_blocks only applies to the paged cache "
                                 "layout (cfg.resolved_cache_layout)")

        # ---- self-speculative decoding (DESIGN.md §"Self-speculative
        # decoding"): the draft model is a narrower PSI view of the SAME
        # checkpoint, derived code-space from the serving leaves ----
        self.speculative = tuple(speculative) if speculative else None
        if self.speculative is not None:
            bits, k = self.speculative
            if not self.paged:
                raise ValueError("speculative decoding needs the paged "
                                 "cache layout (cfg.resolved_cache_layout)")
            if cfg.rope == "mrope":
                raise ValueError("speculative verify does not support "
                                 "mrope position encoding")
            if not 1 <= k <= self.block_size:
                raise ValueError(
                    f"speculative k={k} must be in [1, block_size="
                    f"{self.block_size}]: the k-token verify scatter needs "
                    f"distinct in-block offsets")
            if not any(isinstance(leaf, QuantizedTensor)
                       for leaf in jax.tree_util.tree_leaves(
                           params, is_leaf=lambda x: isinstance(
                               x, QuantizedTensor))):
                raise ValueError("speculative decoding derives its draft "
                                 "from PSI-quantized serving params; "
                                 "quantize first (--quant psiN)")
            self.spec_bits, self.spec_k = bits, k
        else:
            self.spec_bits = self.spec_k = 0

        # ---- multi-step decode (DESIGN.md §3 "Multi-step decode & host
        # overlap"): a horizon-M on-device token loop with in-kernel
        # retirement; M = 1 keeps the classic one-step path untraced ----
        self.decode_horizon = int(decode_horizon) if decode_horizon else 1
        if self.decode_horizon < 1:
            raise ValueError(
                f"decode_horizon={decode_horizon} must be >= 1")
        if self.decode_horizon > 1 and self.speculative is not None:
            raise ValueError(
                "--decode-horizon > 1 does not compose with --speculative: "
                "a speculative round is already a fused multi-token device "
                "unit with its own acceptance loop — pick ONE multi-token "
                "decode strategy (drop --speculative or set the horizon "
                "to 1)")

        # ---- placement: params now, cache/input shardings precomputed ----
        self.param_shardings = shr.to_shardings(
            shr.param_specs(params, cfg, self.mesh, mode="serve"), self.mesh)
        self.params = jax.device_put(params, self.param_shardings)
        if self.speculative is not None:
            draft = quantizer.draft_param_tree(params, self.spec_bits)
            self.draft_shardings = shr.to_shardings(
                shr.param_specs(draft, cfg, self.mesh, mode="serve"),
                self.mesh)
            self.draft_params = jax.device_put(draft, self.draft_shardings)
        else:
            self.draft_params = None

        cache_shape = jax.eval_shape(
            lambda: self._init_cache_fn())
        self.cache_shardings = shr.to_shardings(
            shr.cache_specs(cfg, self.mesh, cache_shape), self.mesh)

        step_inputs = {
            "token": jax.ShapeDtypeStruct((max_batch, 1), jnp.int32),
            "pos": jax.ShapeDtypeStruct((max_batch, 1), jnp.int32),
            "active": jax.ShapeDtypeStruct((max_batch,), jnp.bool_),
        }
        if self.paged:
            step_inputs["block_table"] = jax.ShapeDtypeStruct(
                (max_batch, self.n_bt), jnp.int32)
        if self.speculative is not None:
            # the verify pass feeds k tokens per slot; same slot-over-data
            # rule as every other step input (dim 0 is the slot dim)
            step_inputs["spec_tokens"] = jax.ShapeDtypeStruct(
                (max_batch, self.spec_k), jnp.int32)
        if self.decode_horizon > 1:
            # per-slot emission budget for the in-kernel retirement mask
            step_inputs["remaining"] = jax.ShapeDtypeStruct(
                (max_batch,), jnp.int32)
        self._step_shardings = shr.to_shardings(
            shr.serve_batch_specs(cfg, self.mesh, step_inputs), self.mesh)

        # ---- slot/block partitioning for the mesh-aware scheduler ----
        self.n_slot_shards = shr.batch_shard_count(cfg, self.mesh, max_batch)
        self.slot_shards = shr.slot_shard_map(cfg, self.mesh, max_batch)
        if self.paged:
            n_total = self.n_blocks + max_batch
            self.n_block_shards = shr.batch_shard_count(cfg, self.mesh,
                                                        n_total)
            self.block_shards = shr.block_shard_map(cfg, self.mesh, n_total,
                                                    self.n_blocks)
        else:
            self.n_block_shards = 1
            self.block_shards = None
        dp_extent = int(np.prod([self.mesh.shape[a] for a in shr.DP_AXES
                                 if a in self.mesh.axis_names] or [1]))
        if self.n_slot_shards < dp_extent:
            # an explicitly requested data axis the slots cannot use should
            # be loud, not a silently replicated cache + dead parallelism
            warnings.warn(
                f"max_batch={max_batch} does not divide the mesh's "
                f"{dp_extent}-way data parallelism; decode slots shard only "
                f"{self.n_slot_shards}-way (rest of the data axis idles and "
                f"the cache replicates across it).  Pick max_batch a "
                f"multiple of the data-axis extent.", stacklevel=2)

        # ---- the single set of compiled entry points ----
        # The engine cache cycles through decode / insert endlessly, so its
        # OUTPUT sharding is pinned to the placement contract: every entry
        # point returns the cache exactly as committed at init, which (a)
        # keeps the slot layout stable across the serve lifetime and (b)
        # makes the jit cache key identical call-to-call — the decode step
        # compiles exactly once (the DESIGN.md §3 shape-stability contract
        # now extends to shardings).  Greedy tokens replicate (host-read).
        tok_sh = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec())
        self._prefill = self._jit(self._prefill_fn)
        if self.paged:
            # paged signatures carry the host-managed block-table tensors;
            # the donation + out_shardings contracts are identical, so the
            # decode step still compiles exactly once per mesh
            self._decode = self._jit(
                self._decode_fn_paged, donate_argnums=(5,),
                out_shardings=(tok_sh, self.cache_shardings))
            self._prefill_insert = self._jit(
                self._prefill_insert_fn_paged,
                donate_argnums=(3,),
                out_shardings=(tok_sh, self.cache_shardings))
            # prefix-cache twin: gathers the shared-prefix blocks out of
            # the (donated) pool as dense context KV, prefills only the
            # suffix at positions [pos0, pos0+Sb), and scatters the suffix
            # rows into the table's remaining blocks.  nctx is baked into
            # the ctx_ids shape, so each (bucket, nctx) pair is one
            # compiled executable; the decode step is untouched.
            self._prefill_insert_prefix = self._jit(
                self._prefill_insert_fn_paged_prefix,
                donate_argnums=(3,),
                static_argnums=(7,),       # emit: chunked prefill skips the
                out_shardings=(tok_sh, self.cache_shardings))  # lm-head
            self._insert_burst = self._jit(
                self._insert_burst_fn_paged,
                donate_argnums=(0,),
                out_shardings=self.cache_shardings)
            if self.speculative is not None:
                # the two (and only two) decode-side speculative shapes:
                # the fused k-step draft scan and the k-token verify.  Same
                # donation + pinned-out_shardings contract as _decode, so
                # each compiles exactly once — with speculation on, plain
                # _decode is never traced and the decode-side executable
                # count is exactly 2 (asserted at serve warmup).
                # draft emits its (B, k) tokens directly in the verify
                # pass's spec_tokens sharding, so the host can chain
                # draft -> verify without a device round-trip (the verify
                # builds its token window on device from the draft output)
                self._spec_draft = self._jit(
                    self._draft_fn_paged, donate_argnums=(5,),
                    out_shardings=(self._step_shardings["spec_tokens"],
                                   self.cache_shardings))
                self._spec_verify = self._jit(
                    self._verify_fn_paged, donate_argnums=(6,),
                    out_shardings=(tok_sh, self.cache_shardings))
        else:
            self._decode = self._jit(
                self._decode_fn, donate_argnums=(4,),
                out_shardings=(tok_sh, self.cache_shardings))
            self._prefill_insert = self._jit(
                self._prefill_insert_fn, donate_argnums=(3,),
                out_shardings=(tok_sh, self.cache_shardings))
            self._insert_burst = self._jit(
                self._insert_burst_fn, donate_argnums=(0,),
                out_shardings=self.cache_shardings)

        if self.paged:
            # shared one-row scatter for the device-resident block table
            # (DeviceBlockTable.device): compiles once, moves one (n_bt,)
            # row per dirty slot.  Not donated — a pipelined in-flight
            # round may still hold the previous table array as an input.
            self._bt_set_row = jax.jit(
                lambda t, s, row: t.at[s].set(row),
                out_shardings=self._step_shardings["block_table"])

        if self.decode_horizon > 1:
            # The multi-step round: same donation + pinned-out_shardings
            # contract as _decode, with the carry pinned to the decode-step
            # INPUT shardings (shr.decode_carry_specs) so round N+1 can
            # consume round N's output carry with zero resharding — the
            # round compiles exactly once per mesh and plain _decode is
            # never traced (asserted at serve warmup).
            carry_struct = {
                k: step_inputs[k]
                for k in ("token", "pos", "active", "remaining")}
            carry_sh = shr.to_shardings(
                shr.decode_carry_specs(cfg, self.mesh, carry_struct),
                self.mesh)
            if self.paged:
                self._decode_multi = self._jit(
                    self._decode_multi_fn_paged,
                    donate_argnums=(7,),
                    out_shardings=(tok_sh, carry_sh, self.cache_shardings))
            else:
                self._decode_multi = self._jit(
                    self._decode_multi_fn, donate_argnums=(6,),
                    out_shardings=(tok_sh, carry_sh, self.cache_shardings))
        else:
            self._decode_multi = None

        # ---- elastic / straggler: no-op on a single-process mesh ----
        self.monitor = (StragglerMonitor(n_hosts=jax.process_count())
                        if jax.process_count() > 1 else None)

    def _jit(self, fn, **kw):
        """``jax.jit`` of a model entry point: traced under this executor's
        mesh (:meth:`_in_mesh`) and compiled with strict bf16 rounding
        (:data:`STRICT_ROUNDING`)."""
        return jax.jit(self._in_mesh(fn), compiler_options=STRICT_ROUNDING,
                       **kw)

    def _in_mesh(self, fn):
        """``fn`` traced under this executor's mesh (as an abstract-mesh
        context) when it spans more than one device: the kernel dispatch
        (``kernels.ops``) reads it to wrap Pallas calls in ``shard_map``,
        since XLA cannot partition a Mosaic kernel itself."""
        if self.mesh.size == 1:
            return fn
        abstract = self.mesh.abstract_mesh

        @functools.wraps(fn)
        def traced(*args, **kwargs):
            with jax.sharding.use_abstract_mesh(abstract):
                return fn(*args, **kwargs)
        return traced

    # ------------------------------------------------------------ lifecycle
    @classmethod
    def from_devices(cls, cfg, params, *, max_batch: int, max_seq: int,
                     devices=None, model_parallel: int = 1, pods=None,
                     model=None):
        """Build on the largest valid (data, model) mesh for the available
        devices (``elastic.plan_remesh``).  One device -> the degenerate
        (1, 1) mesh: the single-device no-op path."""
        devices = list(devices if devices is not None else jax.devices())
        plan = plan_remesh(len(devices), model_parallel, pods=pods)
        mesh = make_mesh_from_plan(plan, devices)
        return cls(cfg, params, max_batch=max_batch, max_seq=max_seq,
                   mesh=mesh, model=model)

    def remesh(self, devices=None, model_parallel: int = None):
        """Elastic restart path: rebuild this Executor on the surviving
        device set; params reshard via device_put (resharding IS the load
        path, DESIGN.md §6).  Returns self when the plan already matches
        the current mesh (single-device no-op included).

        devices=None means THIS executor's devices minus any that died —
        not every visible device: an executor deliberately built on a
        submesh must not silently regrab the whole host on restart."""
        if devices is None:
            alive = set(jax.devices())
            devices = [d for d in self.mesh.devices.reshape(-1)
                       if d in alive]
        devices = list(devices)
        mp = (model_parallel if model_parallel is not None
              else self.mesh.shape.get("model", 1))
        plan = plan_remesh(len(devices), mp,
                           pods=self.mesh.shape.get("pod", None))
        if (plan.shape == tuple(self.mesh.devices.shape)
                and plan.axis_names == tuple(self.mesh.axis_names)
                and devices[:plan.n_devices]
                == list(self.mesh.devices.reshape(-1))):
            # same plan AND same physical devices: true no-op.  A same-count
            # survivor set with a swapped device (hot spare replacing a dead
            # chip) must still rebuild — that is the failure this path
            # exists for.
            return self
        mesh = make_mesh_from_plan(plan, devices)
        # Rebuild with the FULL construction config.  Regression (PR 7):
        # dropping n_blocks here silently reset a custom pool size on
        # remesh, shifting the scratch-block base (N - max_batch) under
        # live block tables; every jitted paged entry point — decode,
        # prefill_insert (+ prefix twin), burst insert, and the speculative
        # draft/verify pair — is re-created by __init__, so all of them are
        # re-pinned to the new mesh's shardings.
        return Executor(self.cfg, self.params, max_batch=self.max_batch,
                        max_seq=self.max_seq, mesh=mesh, model=self.model,
                        n_blocks=self.n_blocks if self.paged else None,
                        speculative=self.speculative,
                        decode_horizon=self.decode_horizon)

    def observe_step(self, step_times):
        """Feed per-host step times to the straggler monitor; returns its
        report, or None on the single-process no-op path."""
        if self.monitor is None:
            return None
        return self.monitor.observe(step_times)

    # ------------------------------------------------------------ jitted fns
    def _prefill_fn(self, params, tokens, true_lens, pos0=0, ctx_kv=None,
                    emit=True):
        """(B, Sb) right-padded prompts -> (first greedy token (B,), cache).

        The per-sequence cache is always dense layout; paged executors
        prefill at the bucketed extent (the rows the insert scatters into
        pool blocks), dense executors at ``max_seq`` (the slot extent).
        ``pos0``/``ctx_kv`` select the prefix-cache suffix prefill
        (DESIGN.md §3): tokens are the uncached suffix, positions start at
        ``pos0``, attention reads the shared prefix from ``ctx_kv``.
        ``emit=False`` (a chunked prefill's intermediate chunk) skips the
        lm-head and returns zero tokens — only the KV matters, and the
        output stays (B,) int32 so the jitted out_shardings contract is
        unchanged."""
        B, S = tokens.shape
        batch = {"tokens": tokens}
        if self.cfg.rope == "mrope":
            pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
            batch["positions"] = jnp.broadcast_to(pos[:, None], (B, 3, S))
        if self.cfg.family == "encdec":
            batch["frames"] = jnp.zeros(
                (B, self.cfg.enc_frames, self.cfg.d_model), self.dtype)
        logits, cache = self.model.prefill(
            params, batch, cache_len=(None if self.paged else self.max_seq),
            true_lens=true_lens, pos0=pos0, ctx_kv=ctx_kv, emit_logits=emit)
        if not emit:
            return jnp.zeros((B,), jnp.int32), cache
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    def _decode_fn(self, params, token, pos, active, cache):
        """One masked decode step over all slots; greedy next token (B,)."""
        batch = {"token": token, "pos": pos, "active": active}
        if self.cfg.rope == "mrope":
            batch["positions"] = jnp.broadcast_to(
                pos[:, None, :], (pos.shape[0], 3, 1))
        logits, cache = self.model.decode_step(params, batch, cache,
                                               mesh=self.mesh)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    def _decode_fn_paged(self, params, token, pos, active, block_table,
                         cache):
        """Paged twin of ``_decode_fn``: the (B, n_bt) block table is a
        decode-step INPUT (host-allocated, DESIGN.md §3), not cache state —
        so the donated cache tree and its pinned out_shardings are
        unchanged step-to-step and the step compiles exactly once."""
        batch = {"token": token, "pos": pos, "active": active,
                 "block_table": block_table}
        if self.cfg.rope == "mrope":
            batch["positions"] = jnp.broadcast_to(
                pos[:, None, :], (pos.shape[0], 3, 1))
        logits, cache = self.model.decode_step(params, batch, cache,
                                               mesh=self.mesh)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    def _decode_multi_fn(self, params, token, pos, active, remaining,
                         eos_id, cache):
        """Horizon-M on-device decode round (dense layout): M masked decode
        steps under one dispatch, EOS/max-new retirement applied in-kernel
        (``Model.decode_scan``).  Returns ((M, B) raw step tokens, the
        final carry in decode-input shardings, cache)."""
        batch = {"token": token, "pos": pos, "active": active,
                 "remaining": remaining, "eos_id": eos_id}
        toks, carry, cache = self.model.decode_scan(
            params, batch, cache, self.decode_horizon, mesh=self.mesh)
        return toks, carry, cache

    def _decode_multi_fn_paged(self, params, token, pos, active, remaining,
                               eos_id, block_table, cache):
        """Paged twin of ``_decode_multi_fn``; the block table is
        scan-invariant (the host pre-allocates the round's span — same
        contract as the speculative draft scan)."""
        batch = {"token": token, "pos": pos, "active": active,
                 "remaining": remaining, "eos_id": eos_id,
                 "block_table": block_table}
        toks, carry, cache = self.model.decode_scan(
            params, batch, cache, self.decode_horizon, mesh=self.mesh)
        return toks, carry, cache

    def _draft_fn_paged(self, params, token, pos, active, block_table,
                        cache):
        """Fused k-step DRAFT pass (DESIGN.md §"Self-speculative decoding"):
        ``lax.scan`` over the standard decode body with the low-bit draft
        params — one device dispatch drafts all k tokens, writing
        draft-computed KV at positions [pos, pos+k) (the verify pass
        re-scatters target KV over the same entries).  The block table is
        scan-invariant: the host pre-allocates every block the round can
        touch before calling.  Returns ((B, k) greedy drafts, cache)."""
        def step(carry, _):
            tok, p, kv = carry
            batch = {"token": tok, "pos": p, "active": active,
                     "block_table": block_table}
            logits, kv = self.model.decode_step(params, batch, kv,
                                                mesh=self.mesh)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            return (nxt, p + 1, kv), nxt[:, 0]

        (_, _, cache), toks = jax.lax.scan(
            step, (token, pos, cache), None, length=self.spec_k)
        return jnp.moveaxis(toks, 0, 1), cache          # (B, k)

    def _verify_fn_paged(self, params, token, drafts, pos0, active,
                         block_table, cache):
        """k-token VERIFY at the target width: one decode-shaped batched
        pass (M = B*k rows through the same routed paged-attention kernel)
        over the feed token followed by the first k-1 drafts — the window
        is built ON DEVICE from the draft pass's output, so the host can
        enqueue draft and verify back-to-back without syncing the drafts
        in between.  Returns ((B, k) greedy verdicts, cache) — verdict j is
        the target's next token after consuming tokens[:, :j+1]."""
        tokens = jnp.concatenate([token, drafts[:, :self.spec_k - 1]],
                                 axis=1)
        logits, cache = self.model.verify_step(
            params, {"tokens": tokens, "pos0": pos0, "active": active,
                     "block_table": block_table}, cache, mesh=self.mesh)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    def _prefill_insert_fn(self, params, tokens, true_lens, cache, slot):
        """Fused single-admission path: prefill one sequence and write its
        cache straight into ``slot``."""
        first, seq_cache = self._prefill_fn(params, tokens, true_lens)
        return first, self.model.insert_cache(cache, seq_cache, slot)

    def _prefill_insert_fn_paged(self, params, tokens, true_lens, cache,
                                 slot, block_row):
        first, seq_cache = self._prefill_fn(params, tokens, true_lens)
        return first, self.model.insert_cache(cache, seq_cache, slot,
                                              block_row=block_row)

    def _prefill_insert_fn_paged_prefix(self, params, tokens, true_lens,
                                        cache, slot, block_row, ctx_ids,
                                        emit=True):
        """Prefix-cache suffix prefill (DESIGN.md §3): ``ctx_ids`` (nctx,)
        names the shared-prefix pool blocks (absolute positions
        ``[0, nctx*bs)``), ``tokens`` holds only the uncached suffix, and
        ``block_row`` is the slot's FULL table row — the suffix rows
        scatter into its entries from logical block ``nctx`` on.  Reading
        the context out of ``cache`` before the insert writes it is safe
        under donation (one jitted program).  ``emit`` is static (jit
        static_argnums): an intermediate chunk of a chunked prefill passes
        False and skips the lm-head — the same entry point also serves
        chunk insertion, with ``ctx_ids`` naming the blocks of chunks
        0..N-1 (DESIGN.md §3 "SLO scheduling")."""
        nctx = ctx_ids.shape[0]                     # static, from the shape
        pos0 = nctx * self.block_size
        ctx_kv = (self.model.gather_prefix_ctx(cache, ctx_ids, self.dtype)
                  if nctx else None)
        first, seq_cache = self._prefill_fn(params, tokens, true_lens,
                                            pos0=pos0, ctx_kv=ctx_kv,
                                            emit=emit)
        return first, self.model.insert_cache(cache, seq_cache, slot,
                                              block_row=block_row[nctx:])

    def _insert_burst_fn(self, cache, seq_cache, slots, valid):
        """Insert row i of ``seq_cache`` into slot ``slots[i]`` for every i
        with ``valid[i]`` (both (max_batch,), traced)."""
        for i in range(self.max_batch):
            row = self.model.slice_cache(seq_cache, jnp.int32(i))
            updated = self.model.insert_cache(cache, row, slots[i])
            cache = jax.tree_util.tree_map(
                lambda new, old, i=i: jnp.where(valid[i], new, old),
                updated, cache)
        return cache

    def _insert_burst_fn_paged(self, cache, seq_cache, slots, valid,
                               block_rows):
        """Paged burst: scatter row i of the dense prefill output into the
        blocks of ``block_rows[i]`` ((max_batch, n_bt), traced)."""
        for i in range(self.max_batch):
            row = self.model.slice_cache(seq_cache, jnp.int32(i))
            updated = self.model.insert_cache(cache, row, slots[i],
                                              block_row=block_rows[i])
            cache = jax.tree_util.tree_map(
                lambda new, old, i=i: jnp.where(valid[i], new, old),
                updated, cache)
        return cache

    # ---------------------------------------------------------- entry points
    def _init_cache_fn(self):
        return self.model.init_cache(
            self.max_batch, self.max_seq, dtype=self.dtype,
            layout=self.layout,
            block_size=self.block_size or None,
            n_blocks=self.n_blocks if self.paged else None)

    def init_cache(self):
        """The engine's batched decode cache, committed slot-over-data
        (dense) / block-over-data (paged) at birth (placement happens
        inside ``Model.init_cache(mesh=...)``)."""
        return self.model.init_cache(
            self.max_batch, self.max_seq, dtype=self.dtype, mesh=self.mesh,
            layout=self.layout, block_size=self.block_size or None,
            n_blocks=self.n_blocks if self.paged else None)

    def prefill(self, tokens, true_lens):
        return self._prefill(self.params, jnp.asarray(tokens),
                             jnp.asarray(true_lens))

    def prefill_insert(self, tokens, true_lens, cache, slot: int,
                       block_row=None, ctx_ids=None, emit=True):
        """Fused prefill + slot insert.  ``ctx_ids`` (prefix-cache /
        chunked-prefill mode, paged only) routes to the suffix-prefill
        twin: pass the context block ids — possibly empty, which compiles
        its own nctx=0 shape but computes the identical graph — and
        ``tokens`` holding only the uncached suffix / current chunk.
        ``emit=False`` (intermediate chunks; ctx path only) skips the
        lm-head and returns zero tokens."""
        if self.paged and ctx_ids is not None:
            return self._prefill_insert_prefix(
                self.params, jnp.asarray(tokens), jnp.asarray(true_lens),
                cache, jnp.int32(slot), jnp.asarray(block_row),
                jnp.asarray(ctx_ids, jnp.int32), emit)
        if not emit:
            raise ValueError("emit=False needs the ctx (prefix/chunk) "
                             "prefill path — pass ctx_ids")
        if self.paged:
            return self._prefill_insert(self.params, jnp.asarray(tokens),
                                        jnp.asarray(true_lens), cache,
                                        jnp.int32(slot),
                                        jnp.asarray(block_row))
        return self._prefill_insert(self.params, jnp.asarray(tokens),
                                    jnp.asarray(true_lens), cache,
                                    jnp.int32(slot))

    def insert_burst(self, cache, seq_cache, slots, valid, block_rows=None):
        if self.paged:
            return self._insert_burst(cache, seq_cache, jnp.asarray(slots),
                                      jnp.asarray(valid),
                                      jnp.asarray(block_rows))
        return self._insert_burst(cache, seq_cache, jnp.asarray(slots),
                                  jnp.asarray(valid))

    def make_block_table(self) -> DeviceBlockTable:
        """A host-mirrored device-resident block table for this executor
        (paged only).  The serve loop writes the host mirror like a plain
        ndarray; decode dispatches reuse the committed device copy and pay
        only incremental row scatters for slots that changed."""
        return DeviceBlockTable(self)

    def _device_table(self, block_table):
        """The block table as a committed device array: a
        :class:`DeviceBlockTable` serves its cached copy (zero transfer
        when unchanged); a raw host array takes the legacy full upload."""
        if isinstance(block_table, DeviceBlockTable):
            return block_table.device()
        return jax.device_put(jnp.asarray(block_table),
                              self._step_shardings["block_table"])

    def decode(self, token, pos, active, cache, block_table=None):
        """One decode step; inputs are committed slot-over-data so jit
        compiles the distributed step (computation follows data).  One
        tree-level device_put moves the host step inputs in a single
        transfer — this runs once per generated token; the block table
        rides the :meth:`_device_table` cache."""
        put = {"token": jnp.asarray(token), "pos": jnp.asarray(pos),
               "active": jnp.asarray(active)}
        put = jax.device_put(
            put, {k: self._step_shardings[k] for k in put})
        if self.paged:
            return self._decode(self.params, put["token"], put["pos"],
                                put["active"],
                                self._device_table(block_table), cache)
        return self._decode(self.params, put["token"], put["pos"],
                            put["active"], cache)

    def decode_multi(self, token, pos, active, remaining, cache,
                     block_table=None, eos_id: int = -1):
        """One horizon-M decode ROUND (requires ``decode_horizon > 1``).

        ``token``/``pos``/``active``/``remaining`` may be host arrays (the
        rebuild path after the host mutated its mirrors) or the device
        carry dict returned by the previous round — device_put against the
        identical shardings is a no-op for already-committed leaves, so
        chaining rounds moves zero carry bytes.  ``eos_id`` is a TRACED
        scalar (value changes never recompile); -1 disables EOS retirement.
        Returns ((M, B) raw step tokens — replicated for the host sync,
        carry dict, cache)."""
        if self._decode_multi is None:
            raise ValueError("decode_multi needs decode_horizon > 1 at "
                             "construction")
        put = {"token": jnp.asarray(token), "pos": jnp.asarray(pos),
               "active": jnp.asarray(active),
               "remaining": jnp.asarray(remaining, jnp.int32)}
        put = jax.device_put(
            put, {k: self._step_shardings[k] for k in put})
        eos = jnp.int32(eos_id)
        if self.paged:
            return self._decode_multi(
                self.params, put["token"], put["pos"], put["active"],
                put["remaining"], eos, self._device_table(block_table),
                cache)
        return self._decode_multi(
            self.params, put["token"], put["pos"], put["active"],
            put["remaining"], eos, cache)

    def draft(self, token, pos, active, cache, block_table):
        """One fused k-step draft pass with the low-bit view of the serving
        checkpoint.  Same input contract as :meth:`decode`; returns
        ((B, k) draft tokens, cache)."""
        put = {"token": jnp.asarray(token), "pos": jnp.asarray(pos),
               "active": jnp.asarray(active)}
        put = jax.device_put(
            put, {k: self._step_shardings[k] for k in put})
        return self._spec_draft(self.draft_params, put["token"], put["pos"],
                                put["active"],
                                self._device_table(block_table), cache)

    def verify(self, token, drafts, pos0, active, cache, block_table):
        """One k-token verify pass at the target width.  ``token`` (B, 1)
        is the round's feed token, ``drafts`` (B, k) the draft pass's
        output (device array or host) — the verify window [token,
        drafts[:, :k-1]] is assembled on device, so passing the DeviceArray
        straight from :meth:`draft` chains the two dispatches without a
        host sync.  ``pos0`` (B, 1) is the feed position.  Returns
        ((B, k) target verdicts, cache)."""
        put = {"token": jnp.asarray(token),
               "spec_tokens": jnp.asarray(drafts),
               "pos": jnp.asarray(pos0), "active": jnp.asarray(active)}
        put = jax.device_put(
            put, {k: self._step_shardings[k] for k in put})
        return self._spec_verify(self.params, put["token"],
                                 put["spec_tokens"], put["pos"],
                                 put["active"],
                                 self._device_table(block_table), cache)

    # jit-cache introspection for the shape-stability tests / stats
    def decode_cache_size(self) -> int:
        # _cache_size is a private jax API; degrade to -1 (unknown) rather
        # than fail the stats path if an upgrade removes it.
        return getattr(self._decode, "_cache_size", lambda: -1)()

    def prefill_cache_sizes(self) -> dict:
        """Compiled-shape counts per prefill path (the warmup log / the
        warmup reachability test): burst prefill, fused prefill+insert,
        burst insert."""
        sz = lambda f: getattr(f, "_cache_size", lambda: -1)()
        out = {"prefill": sz(self._prefill),
               "prefill_insert": sz(self._prefill_insert),
               "insert_burst": sz(self._insert_burst)}
        if self.paged:
            out["prefill_insert_prefix"] = sz(self._prefill_insert_prefix)
        return out

    def spec_cache_sizes(self) -> dict:
        """Compiled decode-side executable counts under speculation: the
        compile-once contract becomes compile-exactly-TWICE — one draft
        scan + one verify shape, and the plain decode step never traces
        (``decode == 0``).  Asserted at serve warmup."""
        sz = lambda f: getattr(f, "_cache_size", lambda: -1)()
        return {"draft": sz(self._spec_draft),
                "verify": sz(self._spec_verify),
                "decode": sz(self._decode)}

    def decode_multi_cache_size(self) -> int:
        """Compiled executable count of the horizon-M round (the
        compile-once contract at the round shape)."""
        if self._decode_multi is None:
            return 0
        return getattr(self._decode_multi, "_cache_size", lambda: -1)()

    def multi_cache_sizes(self) -> dict:
        """Decode-side executable counts under a horizon > 1: exactly one
        round shape, and the single-step twin never traces.  Asserted at
        serve warmup (the multi-step entry in the warmup ladder)."""
        return {"decode_multi": self.decode_multi_cache_size(),
                "decode": self.decode_cache_size()}
