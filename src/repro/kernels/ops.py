"""Jit'd dispatch wrappers around the Pallas kernels.

The serving weight arrives as a :class:`~repro.core.psi.QuantizedTensor`;
dispatch is typed — storage layout (``qt.packed``) picks the kernel body and
``qt.fmt.bits`` parameterizes it — with explicit backend routing (no silent
fall-through):

  * ``tpu``          -> the Pallas kernel (compressed weights in HBM,
                        VMEM dequantization);
  * ``gpu`` / ``cuda`` / ``rocm``
                     -> the dequantize-then-einsum fast path in
                        ``repro.kernels.ref`` (tensor-core-eligible dense
                        dot; the bit-plane loop has no Mosaic pipeline to
                        win on a GPU);
  * anything else (``cpu``) -> the pure-jnp oracle — identical semantics
                        (tests assert allclose between the interpret-mode
                        kernel and the oracle).

Set ``REPRO_FORCE_INTERPRET=1`` to route through
``pallas_call(interpret=True)`` on CPU (used by kernel tests).  On a TPU
backend it is an error: the interpreter would stand in for the chip.

Decode-shaped dispatch (DESIGN.md §2): the tiles follow the call's shape
(``psi_matmul.pick_tiles``).  A decode step (M = active slots, below 128)
gets an M-tile that covers its rows (``pick_bm``) and weight tiles of about
1 MiB, so each grid step streams a slab of codes instead of 16 KiB; prefill
pieces (M >= 128) keep 128 x 128 x 128.  The packed sub-byte body keeps
128 x 128 weight tiles with the M-tile from ``pick_bm``.

:func:`paged_decode_attention` applies the same contract to the paged
decode read side (DESIGN.md §3 "Paged-decode kernel"): tpu -> the fused
flash-decode Pallas kernel in ``repro.kernels.paged_attention`` (no dense
gathered temporary), gpu -> its dense-gather fast path, cpu -> its
pure-XLA oracle; ``REPRO_PAGED_ATTN`` force-overrides the route by name.

Sharded serving (DESIGN.md §5): a Mosaic kernel cannot be partitioned by
XLA, so when a Pallas route (chip or interpret) is traced under a mesh of
more than one device — the Executor traces its steps under its mesh —
each kernel call runs inside ``jax.shard_map``.  ``psi_matmul`` runs
locally on the weight's output-dim shard (serving splits every GEMM weight
over N on "model", ``runtime.sharding``), so no partial sum crosses
devices.  The paged-decode kernel runs per pool shard over the blocks that
shard holds and merges the shards' online-softmax partials exactly.
"""
from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import psi
from repro.kernels import paged_attention as _pa
from repro.kernels import psi_matmul as _pk
from repro.kernels import ref as _ref
from repro.launch.mesh import data_axes, pick_batch_axes

_GPU_BACKENDS = ("gpu", "cuda", "rocm")
_PAGED_ROUTES = ("pallas", "gather", "ref", "interpret")


def _backend() -> str:
    return jax.default_backend()


def matmul_route() -> str:
    """Resolved backend route for :func:`psi_matmul`: ``pallas`` (tpu),
    ``gpu`` (dequantize-then-dot fast path), ``interpret`` (the Pallas
    kernel under the interpreter, ``REPRO_FORCE_INTERPRET=1`` off-TPU) or
    ``ref`` (the pure-jnp oracle)."""
    backend = _backend()
    forced = os.environ.get("REPRO_FORCE_INTERPRET", "0") == "1"
    if backend == "tpu":
        if forced:
            raise RuntimeError(
                "REPRO_FORCE_INTERPRET=1 on a TPU backend would run the "
                "Pallas kernels in the interpreter instead of on the chip; "
                "unset it")
        return "pallas"
    if backend in _GPU_BACKENDS:
        return "gpu"
    return "interpret" if forced else "ref"


def _kernel_mesh(route: str):
    """The (abstract) mesh a Pallas route is being traced under when it
    spans more than one device — the kernel must then be wrapped in
    ``shard_map`` — else None (plain call; XLA routes need no wrapping)."""
    if route not in ("pallas", "interpret"):
        return None
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return None
    return mesh


def _data_axes(n: int, mesh):
    """Data-parallel axes a dim of extent ``n`` shards over — the serving
    placement rule, so kernel specs match the operands' committed layout
    and no resharding is inserted."""
    return pick_batch_axes(n, mesh, data_axes(mesh))


def psi_matmul_2d(x2d: jnp.ndarray, qt: psi.QuantizedTensor) -> jnp.ndarray:
    """(M, K) x QuantizedTensor weight -> (M, N) on one device."""
    route = matmul_route()
    scale = qt.scale.reshape(-1)
    M, K = x2d.shape
    if route in ("pallas", "interpret"):
        interp = route == "interpret"
        if qt.packed:
            return _pk.psi_matmul_packed(x2d, qt.data, scale,
                                         bits=qt.fmt.bits,
                                         bm=_pk.pick_bm(M, x2d.dtype),
                                         interpret=interp)
        bm, bn, bk = _pk.pick_tiles(M, K, qt.data.shape[-1], x2d.dtype)
        return _pk.psi_matmul_codes(x2d, qt.data, scale, bm=bm, bn=bn,
                                    bk=bk, interpret=interp)
    if route == "gpu":
        if qt.packed:
            return _ref.psi_matmul_packed_dequant(x2d, qt.data, scale,
                                                  qt.fmt.bits)
        return _ref.psi_matmul_codes_dequant(x2d, qt.data, scale)
    if qt.packed:
        return _ref.psi_matmul_packed_ref(x2d, qt.data, scale, qt.fmt.bits)
    return _ref.psi_matmul_codes_ref(x2d, qt.data, scale)


def _psi_matmul_sharded(x2d, qt: psi.QuantizedTensor, mesh):
    """:func:`psi_matmul_2d` per device under ``shard_map``: rows over the
    data axes, the weight's output dim N over "model" — the serving
    placement of every GEMM weight (``runtime.sharding``) — so each output
    column is the single-device kernel's own full-K reduction.

    The output is gathered over "model" before it leaves, so activations
    stay replicated over "model" as on one device: left split over d, the
    residual stream turns every RMSNorm into a per-device partial sum plus
    an f32 all-reduce, a summation order one device never uses."""
    m = mesh.shape.get("model", 1)
    rows = _data_axes(x2d.shape[0], mesh)
    n_dim = qt.data.shape[-1]
    cols = "model" if m > 1 and n_dim % m == 0 else None
    lead = (None,) * (qt.data.ndim - 1)        # packed: (bits, K//8, N)
    sc_spec = (P(*(None,) * (qt.scale.ndim - 1), cols)
               if qt.scale.shape[-1] == n_dim else P())

    def body(xl, data, scale):
        y = psi_matmul_2d(xl, psi.QuantizedTensor(data, scale, qt.fmt,
                                                  qt.packed))
        return jax.lax.all_gather(y, cols, axis=1, tiled=True) if cols else y

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(rows, None), P(*lead, cols), sc_spec),
                         out_specs=P(rows, None), check_vma=False)(
        x2d, qt.data, qt.scale)


def psi_matmul(x: jnp.ndarray, qt: psi.QuantizedTensor) -> jnp.ndarray:
    """(..., K) x QuantizedTensor weight -> (..., N); flattens leading dims."""
    lead = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    mesh = _kernel_mesh(matmul_route())
    y = (psi_matmul_2d(x2d, qt) if mesh is None
         else _psi_matmul_sharded(x2d, qt, mesh))
    return y.reshape(*lead, y.shape[-1])


def paged_attn_route() -> str:
    """Resolved backend route for the paged-decode attention read side.

    Same explicit contract as :func:`matmul_route` — tpu -> the Pallas
    flash-decode kernel, gpu -> the dense-gather fast path, cpu -> the
    pure-XLA oracle (the token-identity reference); ``REPRO_FORCE_INTERPRET``
    routes through ``pallas_call(interpret=True)``.  ``REPRO_PAGED_ATTN``
    overrides the route by name (``pallas`` / ``gather`` / ``ref`` /
    ``interpret``); an unknown name fails loudly rather than silently
    falling through, and so does ``interpret`` on a TPU backend."""
    env = os.environ.get("REPRO_PAGED_ATTN", "auto")
    if env != "auto":
        if env not in _PAGED_ROUTES:
            raise ValueError(
                f"REPRO_PAGED_ATTN={env!r}: expected one of "
                f"{('auto',) + _PAGED_ROUTES}")
        if env == "interpret" and _backend() == "tpu":
            raise RuntimeError(
                "REPRO_PAGED_ATTN=interpret on a TPU backend would run the "
                "paged-decode kernel in the interpreter instead of on the "
                "chip; unset it")
        return env
    return {"pallas": "pallas", "gpu": "gather", "interpret": "interpret",
            "ref": "ref"}[matmul_route()]


def _paged_attention_sharded(q, k_pool, v_pool, block_tables, pos, k_scale,
                             v_scale, interpret, mesh):
    """The flash-decode kernel per pool shard under ``shard_map``.

    The pool's block dim is chunked over the data axes (block id b lives on
    shard ``b // n_local`` — ``runtime.sharding.block_shard_map``) and KV
    heads over "model".  Every shard walks all slots' tables with the ids
    it holds mapped into its local range (others masked as unallocated),
    then the shards' partial softmaxes merge: each shard's output is
    weighted by its share ``exp(m_s - m) * l_s / sum`` of the row's total
    mass.  A row whose blocks all live on one shard gets weight exactly 1
    there and 0 elsewhere, so its output is bit-identical to the
    single-device kernel."""
    m = mesh.shape.get("model", 1)
    N, _, Hkv, _ = k_pool.shape
    heads = "model" if m > 1 and Hkv % m == 0 else None
    blocks = _data_axes(N, mesh)
    axes = (() if blocks is None
            else blocks if isinstance(blocks, tuple) else (blocks,))
    n_local = N // math.prod(mesh.shape[a] for a in axes)
    pool_spec = P(blocks, None, heads, None)
    quantized = k_scale is not None

    def body(q, kp, vp, bt, ps, *scales):
        if axes:
            lo = jax.lax.axis_index(axes) * n_local
            bt = jnp.where((bt >= lo) & (bt < lo + n_local), bt - lo, -1)
        ks, vs = scales if quantized else (None, None)
        o, mx, l = _pa.paged_attention_pallas(
            q, kp, vp, bt, ps, ks, vs, interpret=interpret, with_stats=True)
        if not axes:
            return o
        w = jnp.exp(mx - jax.lax.pmax(mx, axes)) * l
        tot = jax.lax.psum(w, axes)
        share = w / jnp.where(tot == 0.0, 1.0, tot)
        return jax.lax.psum(o.astype(jnp.float32) * share,
                            axes).astype(o.dtype)

    operands = [q, k_pool, v_pool, block_tables, pos]
    in_specs = [P(None, heads, None), pool_spec, pool_spec, P(), P()]
    if quantized:
        operands += [k_scale, v_scale]
        in_specs += [pool_spec, pool_spec]
    return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=P(None, heads, None),
                         check_vma=False)(*operands)


def paged_decode_attention(q, k_pool, v_pool, block_tables, pos,
                           k_scale=None, v_scale=None):
    """Routed paged-decode attention read side (no gathered temporary on
    TPU; DESIGN.md §3 "Paged-decode kernel").

    q (B, Hq, D); k/v pools (N, bs, Hkv, D) (int8 codes plus per-entry
    ``k_scale``/``v_scale`` (N, bs, Hkv, 1) f32 under ``kv_quant="int8"``);
    block_tables (B, n_bt) int32 (−1 = unallocated); pos (B,) absolute
    query positions.  Returns (B, Hq, D)."""
    route = paged_attn_route()
    if route in ("pallas", "interpret"):
        interpret = route == "interpret"
        mesh = _kernel_mesh(route)
        if mesh is not None:
            return _paged_attention_sharded(q, k_pool, v_pool, block_tables,
                                            pos, k_scale, v_scale, interpret,
                                            mesh)
        return _pa.paged_attention_pallas(q, k_pool, v_pool, block_tables,
                                          pos, k_scale, v_scale,
                                          interpret=interpret)
    if route == "gather":
        return _pa.paged_attention_gather(q, k_pool, v_pool, block_tables,
                                          pos, k_scale, v_scale)
    return _pa.paged_attention_ref(q, k_pool, v_pool, block_tables, pos,
                                   k_scale, v_scale)
