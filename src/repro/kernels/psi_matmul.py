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
  * bm/bn/bk default 128/128/128 — MXU-aligned (multiples of 128 on the
    matmul dims), VMEM footprint per step ~ bm*bk*2 + bk*bn + bm*bn*4
    ≈ 128 KiB, far under the ~16 MiB/core budget, leaving room for
    double-buffered pipelining by the Mosaic compiler.

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

    The serving decode step calls the kernel at M = active slots (1-16);
    padding M up to the square 128-row tile makes the MXU grind 8-16x
    zero rows per (n, k) grid step.  Pick the smallest legal sublane
    multiple covering M instead (f32: 8, bf16: 16) — same kernel body,
    same numerics (the block-shape-sweep tests assert invariance), just a
    shorter M tile.  Large M keeps the square MXU-aligned default.
    """
    if M >= DEFAULT_BM:
        return DEFAULT_BM
    lo = _MIN_SUBLANE.get(jnp.dtype(dtype), 8)
    return max(lo, -(-M // lo) * lo)


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
    format-neutral alias ``repro.kernels.ops`` dispatches through)."""
    M, K = x.shape
    Kc, N = codes.shape
    assert K == Kc
    xp = _pad_to(_pad_to(x, bm, 0), bk, 1)
    cp = _pad_to(_pad_to(codes, bk, 0), bn, 1)
    sp = _pad_to(scale.reshape(1, -1), bn, 1)
    Mp, Kp = xp.shape
    _, Np = cp.shape
    k_steps = Kp // bk
    grid = (Mp // bm, Np // bn, k_steps)
    out = pl.pallas_call(
        functools.partial(_int8_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xp, cp, sp)
    return out[:M, :N]


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
