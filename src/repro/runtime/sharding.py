"""Partition rules: map every parameter / batch / cache leaf to a
PartitionSpec for the production mesh (DESIGN.md §5).

Axes: ``("data", "model")`` single-pod, ``("pod", "data", "model")`` multi-pod
("pod" joins "data" as an outer data-parallel / FSDP axis).

Serving: weights TP over "model", replicated over data; batch over "data".
Training: FSDP — the non-TP weight dim is additionally sharded over the
data axes (ZeRO-3 semantics under GSPMD: all-gather on use, reduce-scatter
on grad), optimizer moments inherit the param spec.

Rules are *logical*: a rule names the spec of the trailing (weight) dims;
leading layer-stack dims are automatically None.  Quantized serving leaves
(``QuantizedTensor``: codes-or-planes + scale, format as static metadata)
derive their spec from the same logical rule — dispatch is typed, never
dict-key sniffing.
"""
from __future__ import annotations

import re
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.psi import QuantizedTensor
from repro.launch.mesh import DP_AXES, data_axes  # noqa: F401 (re-export)
from repro.launch.mesh import divisible as _divisible
from repro.launch.mesh import pick_batch_axes as _pick_batch_axes
from repro.models.kvcache import KVCache

FSDP_AXIS = "data"

# Models narrower than this gain nothing from 16-way tensor parallelism —
# per-shard GEMMs degenerate (d_ff/16 < MXU tile) and every layer pays two
# all-reduces.  Below the threshold the "model" axis is repurposed as extra
# data/sequence parallelism and weights replicate (whisper-base: 70 MB).
TP_MIN_D_MODEL = 1024


def tp_enabled(cfg) -> bool:
    return cfg.d_model >= TP_MIN_D_MODEL


def _dp(mesh: Mesh, cfg=None) -> Tuple:
    """Data-parallel axes present in this mesh (flattened for batch dim).
    When TP is disabled for this arch, "model" joins the batch axes."""
    axes = data_axes(mesh)
    if cfg is not None and not tp_enabled(cfg) and "model" in mesh.axis_names:
        axes = axes + ("model",)
    return axes


def batch_shard_count(cfg, mesh: Mesh, B: int) -> int:
    """How many ways the slot/batch dim of serving tensors splits on this
    mesh — the number of per-shard slot pools the scheduler partitions over
    (1 on a single-device mesh: the no-op path)."""
    bax = _pick_batch_axes(B, mesh, _dp(mesh, cfg))
    if bax is None:
        return 1
    axes = bax if isinstance(bax, tuple) else (bax,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def slot_shard_map(cfg, mesh: Mesh, n_slots: int) -> np.ndarray:
    """slot -> data-shard index under GSPMD's contiguous-chunk layout for a
    ``(n_slots, ...)`` leaf sharded over the data axes (shard i holds slots
    [i*n/d, (i+1)*n/d)).  The mesh-aware SlotAllocator uses this to admit
    into per-shard free slots (DESIGN.md §5)."""
    d = batch_shard_count(cfg, mesh, n_slots)
    return (np.arange(n_slots) * d) // n_slots


def block_shard_map(cfg, mesh: Mesh, n_total: int,
                    n_usable: int = None) -> np.ndarray:
    """block id -> data-shard index for a paged pool whose leading dim is
    ``n_total`` blocks (``n_blocks`` usable + per-slot scratch — the WHOLE
    dim is what GSPMD chunks over the data axes, so the map must be
    computed against it).  Returns the map truncated to the ``n_usable``
    allocatable ids the scheduler's BlockAllocator partitions over
    (DESIGN.md §5); scratch blocks land on whatever shard the chunking
    gives them and are never allocated."""
    d = batch_shard_count(cfg, mesh, n_total)
    full = (np.arange(n_total) * d) // n_total
    return full[:n_usable if n_usable is not None else n_total]


# ---------------------------------------------------------------------------
# Logical rules: (path regex) -> trailing-dims spec builder.
# Specs use "model" for TP and "fsdp" as a placeholder replaced by the data
# axes in training mode / None in serving mode.
# ---------------------------------------------------------------------------
_RULES = (
    # embeddings: vocab over model (model-parallel logits); d replicated —
    # FSDP-sharding the gather output dim provokes involuntary remat in the
    # SPMD partitioner (resharding a gather across the batch axes).
    (r"(^|/)embed$",        ("model", None)),
    (r"(^|/)lm_head$",      (None, "model")),
    # attention
    (r"(^|/)wq$",           ("fsdp", "model")),
    (r"(^|/)wk$",           ("fsdp", "model")),
    (r"(^|/)wv$",           ("fsdp", "model")),
    (r"(^|/)wo$",           ("model", "fsdp")),
    # dense mlp
    (r"(^|/)w_gate$",       ("fsdp", "model")),   # moe experts override below
    (r"(^|/)w_up$",         ("fsdp", "model")),
    (r"(^|/)w_down$",       ("model", "fsdp")),
    # rg-lru
    (r"(^|/)w_in_rec$",     ("fsdp", "model")),
    (r"(^|/)w_in_gate$",    ("fsdp", "model")),
    (r"(^|/)rglru_wa$",     ("fsdp", "model")),
    (r"(^|/)rglru_wx$",     ("fsdp", "model")),
    (r"(^|/)rglru_(ba|bx|lambda)$", ("model",)),
    (r"(^|/)w_out$",        ("model", "fsdp")),
    # mamba
    (r"(^|/)in_proj$",      ("fsdp", "model")),
    (r"(^|/)x_proj$",       ("model", "fsdp")),
    (r"(^|/)dt_proj_w$",    ("fsdp", "model")),
    (r"(^|/)dt_proj_b$",    ("model",)),
    (r"(^|/)out_proj$",     ("model", "fsdp")),
    (r"(^|/)a_log$",        ("model", None)),
    (r"(^|/)d_skip$",       ("model",)),
    (r"(^|/)conv1d_w$",     (None, "model")),
    (r"(^|/)conv1d_b$",     ("model",)),
    # moe router
    (r"(^|/)router$",       ("fsdp", None)),
)

_MOE_EP = {  # experts divide the model axis: expert parallelism
    r"(^|/)w_gate$": ("model", "fsdp", None),
    r"(^|/)w_up$":   ("model", "fsdp", None),
    r"(^|/)w_down$": ("model", None, "fsdp"),
}
_MOE_TP = {  # tensor parallelism inside each expert
    r"(^|/)w_gate$": (None, "fsdp", "model"),
    r"(^|/)w_up$":   (None, "fsdp", "model"),
    r"(^|/)w_down$": (None, "model", "fsdp"),
}


# Serving splits these row-parallel GEMM weights over their OUTPUT dim
# instead, like every other dense GEMM weight: each output column then sums
# over the whole contraction on one device, in the single-device kernel's
# order.  A split contraction ends in a psum that reorders the f32 sum, and
# under bf16 activations that flips greedy tokens against one device.  The
# price is an activation all-gather per GEMM — small at decode, where M is
# the slot count.
_SERVE_OUTPUT_SPLIT = re.compile(r"(^|/)(wo|w_down|w_out|x_proj|out_proj)$")


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _logical_spec(path: str, cfg, mesh: Mesh, mode: str) -> Optional[Tuple]:
    if cfg.n_experts and re.search(r"moe/", path):
        table = (_MOE_EP if _divisible(cfg.n_experts, mesh, "model")
                 else _MOE_TP)
        for pat, spec in table.items():
            if re.search(pat, path):
                return spec
    if mode == "serve" and _SERVE_OUTPUT_SPLIT.search(path):
        return (None, "model")
    for pat, spec in _RULES:
        if re.search(pat, path):
            return spec
    return None                       # norms, misc small params -> replicate


def _materialize(spec_tail, leaf_shape, mesh: Mesh, mode: str,
                 use_tp: bool = True):
    """Map a logical trailing spec onto a concrete leaf shape; leading dims
    (layer stacks) replicate.  'fsdp' resolves to the data axes in train
    mode, None otherwise.  Axes that don't divide the dim are dropped."""
    fsdp = data_axes(mesh) if mode == "train" else None
    tail = []
    for dim, ax in zip(leaf_shape[-len(spec_tail):], spec_tail):
        if ax == "model" and not use_tp:
            ax = None
        if ax == "fsdp":
            ax = fsdp
        if ax is None:
            tail.append(None)
            continue
        if not _divisible(dim, mesh, ax):
            # fall back: try a single axis out of a tuple, else replicate
            if isinstance(ax, tuple):
                ax = next((a for a in ax if dim % mesh.shape[a] == 0), None)
                tail.append(ax)
            else:
                tail.append(None)
            continue
        tail.append(ax)
    lead = [None] * (len(leaf_shape) - len(spec_tail))
    return P(*(lead + tail))


def _spec_for_qt(leaf: QuantizedTensor, spec_tail, mesh: Mesh, mode: str,
                 use_tp: bool = True) -> QuantizedTensor:
    """QuantizedTensor leaf: unpacked codes keep the weight spec; packed
    planes prepend a replicated bit-plane dim; scale shards only its
    non-singleton dims.  Returns a QuantizedTensor *of specs* (same static
    format metadata), so spec trees and param trees stay structure-equal for
    device_put / out_shardings."""
    data_tail = ((None,) + tuple(spec_tail)) if leaf.packed else spec_tail
    data = _materialize(data_tail, leaf.data.shape, mesh, mode, use_tp)
    sc = leaf.scale.shape
    sc_tail = [ax if sc[-len(spec_tail) + i] > 1 else None
               for i, ax in enumerate(spec_tail)]
    scale = _materialize(tuple(sc_tail), sc, mesh, mode, use_tp)
    return QuantizedTensor(data, scale, leaf.fmt, leaf.packed)


def _is_qt(x):
    return isinstance(x, QuantizedTensor)


def param_specs(params, cfg, mesh: Mesh, mode: str = "serve"):
    """PartitionSpec pytree matching ``params`` (plain or PSI-quantized)."""
    use_tp = tp_enabled(cfg)
    if not use_tp:
        # Small model: replicate everything (whisper-base: 70 MB of weights);
        # the mesh axes all become batch parallelism.  Mixing FSDP shards
        # with >16-way batch sharding provokes involuntary rematerialization
        # in the SPMD partitioner (observed: 217 GB replicated logits).
        def repl(leaf):
            if _is_qt(leaf):
                return QuantizedTensor(P(), P(), leaf.fmt, leaf.packed)
            return P()
        return jax.tree_util.tree_map(repl, params, is_leaf=_is_qt)

    def one(path, leaf):
        p = _path_str(path)
        spec_tail = _logical_spec(p, cfg, mesh, mode)
        if _is_qt(leaf):
            if spec_tail is None:
                return QuantizedTensor(P(), P(), leaf.fmt, leaf.packed)
            return _spec_for_qt(leaf, spec_tail, mesh, mode, use_tp)
        if spec_tail is None or leaf.ndim < len(spec_tail):
            return P()
        return _materialize(spec_tail, leaf.shape, mesh, mode, use_tp)

    return jax.tree_util.tree_map_with_path(one, params, is_leaf=_is_qt)


def batch_specs(cfg, mesh: Mesh, batch_tree, seq_shard: bool = False):
    """Input batch: batch dim over the data axes (replicated if indivisible,
    e.g. long_500k's batch=1).  When TP is off for this arch, "model" joins
    the batch axes; if the batch still can't use it, the token sequence dim
    is sharded over "model" instead (sequence parallelism)."""
    dp = _dp(mesh, cfg)
    free_model = (not tp_enabled(cfg)) and "model" in mesh.axis_names

    def pick_bax(B):
        return _pick_batch_axes(B, mesh, dp)

    def one(path, leaf):
        name = _path_str(path)
        if leaf.ndim == 0:
            return P()
        B = leaf.shape[0]
        bax = pick_bax(B)
        spec = [bax] + [None] * (leaf.ndim - 1)
        used = set()
        for a in (bax if isinstance(bax, tuple) else (bax,) if bax else ()):
            used.add(a)
        if name == "tokens" and leaf.ndim >= 2:
            S = leaf.shape[1]
            if free_model and "model" not in used and S % mesh.shape["model"] == 0:
                spec[1] = "model"           # sequence parallelism
            elif seq_shard and bax is None and _divisible(S, mesh, "data"):
                spec[1] = "data"
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, batch_tree)


def _kv_layout(cfg, mesh: Mesh, B, C, Hkv):
    """(batch_ax, seq_ax, head_ax) for KV cache tensors — one decision
    shared by k, v, and k_pos so masks stay co-sharded with values."""
    bax = _pick_batch_axes(B, mesh, _dp(mesh, cfg))
    used = set(bax if isinstance(bax, tuple) else (bax,) if bax else ())
    use_tp = tp_enabled(cfg)
    head_ax = "model" if (use_tp and Hkv % mesh.shape["model"] == 0) else None
    seq_ax = None
    if head_ax is None:
        # heads unshardable (MQA/GQA < TP degree): shard the KV ring dim
        # over whichever axis is free — "model" first (it is otherwise
        # idle for this tensor), then "data" (long_500k's batch=1).
        cand = ("model", "data") if "model" in mesh.axis_names else ("data",)
        for a in cand:
            if a not in used and C % mesh.shape[a] == 0:
                seq_ax = a
                break
    return bax, seq_ax, head_ax


def _serve_leaf_spec(cfg, mesh: Mesh, name: str, shape, paged=False) -> P:
    """Spec for one BLOCK-LEVEL cache leaf (batch/slot dim on axis 0).
    This is the core rule table; ``cache_specs`` prepends the layer-group
    dim for stacked leaves, and ``block_cache_specs`` applies it verbatim
    inside the decode scan (masked writes stay on-shard).
    Block-level leaf shapes (dense layout):
      attn k/v:   (B, C, Hkv, hd)   k/v_scale: (B, C, Hkv, 1)
      k_pos:      (B, C)
      mamba ssm:  (B, di, N)   conv: (B, cw-1, di)
      rglru h:    (B, dr)      conv: (B, cw-1, dr)
      enc_out:    (B, F, d)
    Paged layout (``paged=True``): pool leaves (N_total, bs, Hkv, hd) /
    scale (N_total, bs, Hkv, 1) — the BLOCK dim shards over the data axes
    (the allocator follows ``block_shard_map``, replacing the contiguous
    slot-chunk assumption), heads over "model" when divisible, and the
    in-block position dim stays replicated (a block is the indivisible
    transfer unit).
    """
    use_tp = tp_enabled(cfg)
    B = shape[0]
    spec = [None] * len(shape)
    spec[0] = _pick_batch_axes(B, mesh, _dp(mesh, cfg))
    if paged:
        if (len(shape) == 4 and use_tp
                and shape[2] % mesh.shape.get("model", 1) == 0):
            spec[2] = "model"
        return P(*spec)
    if name.endswith("enc_out"):
        return P(*spec)
    if re.search(r"(^|/)k$|(^|/)v$|k_scale$|v_scale$", name) and len(shape) == 4:
        spec[0], spec[1], spec[2] = _kv_layout(cfg, mesh, B, shape[1],
                                               max(cfg.n_kv_heads, 1))
        if shape[2] % mesh.shape.get("model", 1) != 0 and spec[2]:
            spec[2] = None
    elif re.search(r"k_pos", name) and len(shape) == 2:
        # same layout decision as k/v (real kv-head count matters)
        spec[0], spec[1], _ = _kv_layout(cfg, mesh, B, shape[1],
                                         max(cfg.n_kv_heads, 1))
    elif re.search(r"ssm$", name) and len(shape) == 3:
        if use_tp and _divisible(shape[1], mesh, "model"):
            spec[1] = "model"
    elif re.search(r"conv$", name) and len(shape) == 3:
        if use_tp and _divisible(shape[2], mesh, "model"):
            spec[2] = "model"
    elif re.search(r"(^|/)h$", name) and len(shape) == 2:
        if use_tp and _divisible(shape[1], mesh, "model"):
            spec[1] = "model"
    return P(*spec)


def cache_specs(cfg, mesh: Mesh, cache_tree, seq_shard: bool = False):
    """Decode cache: batch/slot (or paged block-pool) dim over the data
    axes; KV seq (ring) dim over "data" when the batch can't use it
    (long_500k); mamba/rg-lru channel state over "model"; KV heads over
    "model" only when divisible (MQA/GQA: replicate).  Stack leaves carry
    the layer-group dim first (always replicated); the per-leaf rules live
    in ``_serve_leaf_spec``.

    Accepts either a typed :class:`KVCache` — the layout is read off its
    static metadata and a structure-equal KVCache *of specs* is returned
    (the QuantizedTensor-of-specs pattern, so device_put / out_shardings
    see matching trees) — or a bare kv stack tree (dense rules).
    """
    if isinstance(cache_tree, KVCache):
        kv = _kv_tree_specs(cfg, mesh, cache_tree.kv, cache_tree.paged)
        enc = (None if cache_tree.enc_out is None else _serve_leaf_spec(
            cfg, mesh, "enc_out", cache_tree.enc_out.shape))
        return cache_tree.replace(kv=kv, enc_out=enc)
    return _kv_tree_specs(cfg, mesh, cache_tree, paged=False)


def _kv_tree_specs(cfg, mesh: Mesh, kv_tree, paged: bool):
    def one(path, leaf):
        name = _path_str(path)
        if leaf.ndim == 0:
            return P()
        if re.search(r"(^|/)b\d+/", name):
            # scanned group leaf: replicated layer-group dim leads
            return P(None, *_serve_leaf_spec(cfg, mesh, name, leaf.shape[1:],
                                             paged))
        # enc_out / unrolled tail-block leaves: batch is axis 0 already
        return _serve_leaf_spec(cfg, mesh, name, leaf.shape, paged)

    return jax.tree_util.tree_map_with_path(one, kv_tree)


def block_cache_specs(cfg, mesh: Mesh, block_tree, paged: bool = False):
    """Specs for one block's cache dict as seen INSIDE the decode scan
    (no leading group dim).  Used by the masked-write constraint the
    executor threads through ``Model.decode_step`` (DESIGN.md §5)."""
    def one(path, leaf):
        name = _path_str(path)
        if leaf.ndim == 0:
            return P()
        return _serve_leaf_spec(cfg, mesh, name, leaf.shape, paged)

    return jax.tree_util.tree_map_with_path(one, block_tree)


def constrain_block_cache(cfg, mesh: Mesh, block_tree, paged: bool = False):
    """with_sharding_constraint over one block's cache dict (decode scan
    body): pins the masked scatter writes to the slot-over-data (dense) or
    block-over-data (paged) layout so the SPMD partitioner cannot fall back
    to replicate-and-gather.  The executor threads this through
    ``Model.decode_step`` -> transformer -> attention; it is a no-op on a
    single-device mesh."""
    specs = block_cache_specs(cfg, mesh, block_tree, paged)
    return jax.tree_util.tree_map(
        lambda leaf, s: jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, s)),
        block_tree, specs)


def serve_batch_specs(cfg, mesh: Mesh, batch_tree):
    """Decode-step inputs (token (B, 1), pos (B, 1) / positions (B, 3, 1),
    active (B,)): slot dim over the data axes, everything else replicated —
    co-sharded with the slot dim of the decode cache."""
    def one(leaf):
        if getattr(leaf, "ndim", 0) == 0:
            return P()
        bax = _pick_batch_axes(leaf.shape[0], mesh, _dp(mesh, cfg))
        return P(bax, *([None] * (leaf.ndim - 1)))

    return jax.tree_util.tree_map(one, batch_tree)


def decode_carry_specs(cfg, mesh: Mesh, carry_tree):
    """Specs for the multi-step decode carry (token / pos / active /
    remaining, DESIGN.md §3 "Multi-step decode & host overlap").  The carry
    chains rounds device-side — round N+1 consumes round N's output carry
    directly — so its out_shardings MUST equal the decode-step input
    shardings leaf-for-leaf, or every round boundary would reshard.  The
    rule is therefore exactly :func:`serve_batch_specs` (slot dim over the
    data axes); this wrapper exists to make that invariant a named API
    instead of a coincidence."""
    return serve_batch_specs(cfg, mesh, carry_tree)


def to_shardings(spec_tree, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
