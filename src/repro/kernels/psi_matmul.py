"""Pallas TPU kernel: matmul with PSI-compressed weights, dequantized in VMEM.

TPU-native adaptation of the paper's multiplier-less SAM array (DESIGN.md §2):
the ASIC removes multiplier *gates*; on TPU the scarce resource in the
memory-bound serving regime is HBM bandwidth, so the PSI code (5 or 8 bits per
weight instead of 16) is kept compressed in HBM and expanded to bf16 *inside
VMEM*, right before the MXU.  Weight HBM traffic drops 2x (INT8) / 3.2x (INT5
bit-planes) versus bf16 weights.

Layout / tiling:
  * Grid (M/bm, N/bn, K/bk); K is the innermost ("arbitrary") dimension and
    accumulates into a VMEM f32 scratch; the per-output-channel scale is
    applied once in the epilogue (k == K/bk - 1).
  * int8 codes: tile (bk, bn) int8 -> bf16 convert -> MXU dot (any registered
    width's codes — the storage is one byte regardless of PsiFormat.bits).
  * packed sub-byte: bit-plane tile (bits, bk//8, bn) uint8; the kernel
    rebuilds the offset-binary value with ``bits`` shift-adds (the SAM
    barrel-shifter mirror), subtracts 2^(bits-1), converts, dots.  One kernel
    body serves every sub-byte width in the PsiFormat registry — ``bits`` is
    a static argument baked per format at trace time.
  * Tiles follow the call's shape (:func:`pick_tiles`).  Prefill pieces
    (M >= 128) keep 128/128/128, MXU-aligned.  Serving reads the whole
    weight once per decode step for a few rows of x, so a decode-shaped
    call (M < 128) takes ``bm`` = M rounded up to the sublane tile and a
    weight tile of about 1 MiB (``bn`` up to 1024 and a ``bk`` that
    divides K; 1024 x 1024 at qwen3-8b's widths): per step ~ 2*bk*bn
    int8 + bk*bn converted + a few x/out/accumulator tiles, about 4.5 MiB
    of VMEM in bf16, within the default scoped limit.  The int8 body pads
    no codes where ``bk`` divides K: N runs over ``cdiv(N, bn)`` blocks
    with a ragged last one.  The packed body keeps 128 x 128 weight tiles.

Validated on CPU with ``interpret=True`` against ``repro.kernels.ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM, DEFAULT_BN, DEFAULT_BK = 128, 128, 128

# Minimum legal sublane (second-to-minor) tile per dtype — the Mosaic
# register-tiling floor.  The lane (minor) dim stays at 128 always.
_MIN_SUBLANE = {jnp.dtype(jnp.bfloat16): 16, jnp.dtype(jnp.float32): 8}


def pick_bm(M: int, dtype=jnp.float32) -> int:
    """Decode-shaped M-tile dispatch (DESIGN.md §2).

    The serving decode step calls the kernel at M = decode slots (32 in
    the chip benchmark); padding M up to the square 128-row tile makes the
    MXU grind 4-128x zero rows per (n, k) grid step.  Pick the smallest
    legal sublane multiple covering M instead (f32: 8, bf16: 16) — same
    kernel body, same numerics (the block-shape-sweep tests assert
    invariance), just a shorter M tile.  Large M keeps the square
    MXU-aligned default.
    """
    if M >= DEFAULT_BM:
        return DEFAULT_BM
    lo = _MIN_SUBLANE.get(jnp.dtype(dtype), 8)
    return max(lo, -(-M // lo) * lo)


# Scoped VMEM a kernel may use without raising its limit (the v5e default).
_SCOPED_VMEM = 16 * 2 ** 20


def _int8_vmem_bytes(bm: int, bn: int, bk: int, itemsize: int) -> int:
    """VMEM one grid step of the int8 body holds: the double-buffered code,
    x, scale and output tiles, the code tile converted to the activation
    dtype, and the f32 accumulator."""
    return (2 * bk * bn + bk * bn * itemsize + 2 * bm * bk * itemsize
            + 2 * bm * bn * itemsize + 2 * 8 * bn * 4 + bm * bn * 4)


# Decode-shaped tiles (M < 128): the widest N block, and the int8 code tile
# (bk * bn bytes) each grid step streams.  On a v5e a grid step costs about
# 0.26 us besides its DMA, so 16 KiB tiles (128 x 128) reach 8% of the HBM
# floor; 1 MiB tiles reach 80-91% at every qwen3-8b decode GEMM, 2 MiB
# ones no more (``benchmarks/kernel_bench.py --tile-sweep``, PERF.md).
_DECODE_BN = 1024
_DECODE_TILE_BYTES = 2 ** 20


def _decode_bn(N: int) -> int:
    """N block for a decode-shaped call: all of N when it fits one block,
    else the widest multiple of 128 up to ``_DECODE_BN`` that divides N, or
    ``_DECODE_BN`` with a ragged last block where only narrow ones divide
    (151936 = 128 * 1187, 75968 = 64 * 1187)."""
    if N <= _DECODE_BN:
        return N
    div = max((b for b in range(128, _DECODE_BN + 1, 128) if N % b == 0),
              default=0)
    return div if 2 * div >= _DECODE_BN else _DECODE_BN


def pick_tiles(M: int, K: int, N: int, dtype=jnp.float32):
    """(bm, bn, bk) of the int8 body for an (M, K) x (K, N) call.

    M >= 128 (prefill pieces) keeps the square 128 x 128 x 128 tiles.  A
    decode-shaped call (M < 128) has little to multiply and a whole weight
    to read, so each grid step streams a slab of codes: ``bm`` covers M
    (:func:`pick_bm`), ``bn`` is :func:`_decode_bn`, and ``bk`` is the
    deepest divisor of K (a multiple of 128, or K itself) whose code tile
    stays within ``_DECODE_TILE_BYTES`` and whose footprint fits scoped
    VMEM.  ``bk`` divides K so the codes are never padded; where K has no
    such divisor it falls back to 128 (K padded).
    """
    bm = pick_bm(M, dtype)
    if M >= DEFAULT_BM:
        return bm, DEFAULT_BN, DEFAULT_BK
    bn = _decode_bn(N)
    itemsize = jnp.dtype(dtype).itemsize
    fits = [d for d in [*range(128, K, 128), K]
            if K % d == 0 and d * bn <= _DECODE_TILE_BYTES
            and _int8_vmem_bytes(bm, bn, d, itemsize) <= _SCOPED_VMEM]
    return bm, bn, max(fits, default=DEFAULT_BK)


def padded_macs(M: int, K: int, N: int, *, bm: int = DEFAULT_BM,
                bn: int = DEFAULT_BN, bk: int = DEFAULT_BK) -> int:
    """MACs the tiled kernel actually issues once every dim is padded up to
    its tile multiple — the quantity the decode-shaped dispatch cuts and
    ``benchmarks/kernel_bench.py`` tracks."""
    mp = -(-M // bm) * bm
    kp = -(-K // bk) * bk
    np_ = -(-N // bn) * bn
    return mp * kp * np_


def _int8_kernel(x_ref, codes_ref, scale_ref, o_ref, acc_ref, *, k_steps):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                  # (bm, bk) bf16/f32
    w = codes_ref[...].astype(x.dtype)              # (bk, bn) int8 -> act dtype
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * scale_ref[...]).astype(o_ref.dtype)


def _packed_kernel(x_ref, planes_ref, scale_ref, o_ref, acc_ref, *, k_steps,
                   bits):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                   # (bm, bk)
    planes = planes_ref[...]                         # (bits, bk//8, bn) uint8
    _, kb, bn = planes.shape
    # SAM-mirror reconstruction: ``bits`` shift-adds rebuild the offset-binary
    # weight; lane index selects the bit within each packed byte.
    lane = jax.lax.broadcasted_iota(jnp.int32, (kb, 8, bn), 1)
    val = jnp.zeros((kb, 8, bn), jnp.int32)
    for b in range(bits):
        plane = planes[b].astype(jnp.int32)[:, None, :]   # (kb, 1, bn)
        bit = (plane >> lane) & 1
        val = val + (bit << b)
    offset = 1 << (bits - 1)
    w = (val.reshape(kb * 8, bn) - offset).astype(x.dtype)  # (bk, bn)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * scale_ref[...]).astype(o_ref.dtype)


def _pad_to(a, mult, axis):
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def psi_matmul_int8(x, codes, scale, *, bm=DEFAULT_BM, bn=DEFAULT_BN,
                    bk=DEFAULT_BK, interpret=False):
    """x (M, K) @ dequant(codes (K, N) int8, scale (N,)) -> (M, N).

    Serves every *unpacked* PsiFormat — sub-byte codes are stored int8, so
    the kernel body is width-independent (``psi_matmul_codes`` is the
    format-neutral alias ``repro.kernels.ops`` dispatches through).

    The codes are not padded on N: it runs over ``cdiv(N, bn)`` blocks,
    the last one ragged (its out-of-range columns are computed from
    whatever the block holds and dropped on write; columns never mix).  K
    is padded only when ``bk`` does not divide it, since padded rows enter
    the sum — :func:`pick_tiles` picks a ``bk`` that divides K."""
    M, K = x.shape
    Kc, N = codes.shape
    assert K == Kc
    bn = min(bn, N)                     # a block as wide as N is always legal
    xp = _pad_to(_pad_to(x, bm, 0), bk, 1)
    cp = _pad_to(codes, bk, 0)
    Mp, Kp = xp.shape
    k_steps = Kp // bk
    grid = (Mp // bm, pl.cdiv(N, bn), k_steps)
    out = pl.pallas_call(
        functools.partial(_int8_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xp, cp, scale.reshape(1, -1))
    return out[:M]


# Format-neutral alias: any registered width's unpacked codes are int8.
psi_matmul_codes = psi_matmul_int8


@functools.partial(jax.jit,
                   static_argnames=("bits", "bm", "bn", "bk", "interpret"))
def psi_matmul_packed(x, planes, scale, *, bits, bm=DEFAULT_BM,
                      bn=DEFAULT_BN, bk=DEFAULT_BK, interpret=False):
    """x (M, K) @ dequant(planes (bits, K//8, N) uint8, scale (N,)) -> (M, N).

    ``bits`` is the PsiFormat width (static) — the same kernel body serves
    every registered sub-byte format.
    """
    assert bk % 8 == 0
    M, K = x.shape
    nb, Kb, N = planes.shape
    assert nb == bits and Kb * 8 == K, (planes.shape, x.shape, bits)
    xp = _pad_to(_pad_to(x, bm, 0), bk, 1)
    pp = _pad_to(_pad_to(planes, bk // 8, 1), bn, 2)
    # padded plane bytes are 0 -> unpack to -2^(bits-1); cancelled because x
    # is zero-padded on K, so the extra columns multiply zeros.  Pad x K first.
    sp = _pad_to(scale.reshape(1, -1), bn, 1)
    Mp, Kp = xp.shape
    Np = pp.shape[2]
    k_steps = Kp // bk
    grid = (Mp // bm, Np // bn, k_steps)
    out = pl.pallas_call(
        functools.partial(_packed_kernel, k_steps=k_steps, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bits, bk // 8, bn), lambda m, n, k: (0, k, n)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xp, pp, sp)
    return out[:M, :N]


def psi_matmul_int5(x, planes, scale, **kw):
    """INT5 instance of :func:`psi_matmul_packed` (kept as the named entry
    point for the paper's Table-I width)."""
    return psi_matmul_packed(x, planes, scale, bits=5, **kw)
