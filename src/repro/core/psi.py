"""Partial-Sub-Integer (PSI) quantization — the core technique of the TMA paper.

The paper (Eq. 1) decomposes the product of an integer weight ``w`` and input ``X``
into 2N signed powers of two::

    w * X = sum_k (s1_k * 2^{n1_k} * X  +  s2_k * 2^{n2_k} * X),   s in {-1, 0, 1}

* INT5 weights use 2 PSIs (N=1).  Every 5-bit integer is exactly representable
  except w in {+-11, +-13}, where the best two-term approximation errs by ~9 %
  (Table I of the paper).
* INT8 weights use 4 PSIs (N=2) and the decomposition is exact for all of
  [-128, 127].

The paper's headline is "scalable integer weights less than 1-byte", so the
two paper points are instances of a registry: :class:`PsiFormat` describes
any width in [2, 8] bits — term budget, exponent range, derived decomposition
table, exactness + worst-case-error metadata, and sub-byte bit-plane packing.
``get_format(bits)`` / ``get_format("psi4")`` look formats up; serving weights
travel as :class:`QuantizedTensor` pytree leaves that carry their format as
static metadata, so every consumer (kernels, sharding, checkpoints) dispatches
on type + format instead of duck-typed dict keys.

On the TMA ASIC the decomposition removes multipliers.  On TPU (our target) the
same decomposition is used as a *weight-compression format*: the stored code is
``bits`` per weight instead of 16, and the Pallas kernel reconstructs the
weight tile inside VMEM with shifts (see ``repro.kernels.psi_matmul``), cutting
HBM weight traffic — the dominant cost of memory-bound inference.

Everything here is exact-integer bookkeeping; tables are built once in numpy
per registered format (lazily, <= 256 entries each) and the runtime paths are
pure ``jnp``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# PsiFormat: one registered weight width.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PsiFormat:
    """One PSI weight format: INT<bits> codes decomposed into <= n_psi signed
    powers of two with exponents in [0, max_exp].

    Instances are immutable and hashable — a ``QuantizedTensor`` carries its
    format as static pytree metadata (it participates in jit cache keys and
    pytree structure equality).  Error metadata is computed exhaustively at
    registration from the decomposition table, so ``worst_case_rel_error`` is
    a *certified* bound, not a declared one.
    """
    bits: int                    # stored weight width, 2..8
    n_psi: int                   # signed-power term budget (paper: 2 for
    #                              INT5, 4 for INT8)
    max_exp: int                 # exponent range [0, max_exp]
    w_min: int                   # -2^(bits-1)
    w_max: int                   # 2^(bits-1) - 1
    exact: bool                  # every code reconstructs exactly
    worst_case_rel_error: float  # max |w' - w| / max(|w|, 1) over the range

    @property
    def name(self) -> str:
        return f"psi{self.bits}"

    @property
    def qmax(self) -> int:
        return self.w_max

    @property
    def offset(self) -> int:
        """Offset-binary bias for sub-byte packing: code + offset in
        [0, 2^bits)."""
        return 1 << (self.bits - 1)

    @property
    def sub_byte(self) -> bool:
        return self.bits < 8

    def bytes_per_weight(self, packed: bool = True) -> float:
        """HBM bytes per weight in serving format (the roofline 'memory'
        input): bits/8 when bit-plane packed, one int8 byte otherwise."""
        return self.bits / 8.0 if (packed and self.sub_byte) else 1.0

    # -- derived tables (built lazily, cached per (bits, n_psi, max_exp)) --
    def decomposition_table(self) -> np.ndarray:
        return _decomposition_table(self.bits, self.n_psi, self.max_exp)

    def value_table(self) -> np.ndarray:
        return _value_table(self.bits, self.n_psi, self.max_exp)


# Term budgets per width.  The paper pins INT5 -> 2 PSIs (~9 % worst case at
# +-11/+-13) and INT8 -> 4 PSIs (exact); intermediate widths interpolate the
# same bits/2 scaling, except INT3 which needs its second term to stay exact
# (3 = 2 + 1).  Every entry's exactness/error is certified at registration.
DEFAULT_N_PSI = {2: 1, 3: 2, 4: 2, 5: 2, 6: 3, 7: 3, 8: 4}

_REGISTRY: Dict[int, PsiFormat] = {}

FormatLike = Union[int, str, PsiFormat]


def make_format(bits: int, n_psi: Optional[int] = None,
                max_exp: Optional[int] = None) -> PsiFormat:
    """Build (without registering) the PSI format for a weight width.

    Derives the integer range from ``bits``, builds the decomposition table,
    and certifies exactness / worst-case relative error exhaustively.  Used
    by :func:`register_format` and by checkpoint restore, which must rebuild
    a leaf's *exact* format (possibly a non-default ``n_psi``/``max_exp``)
    without touching the registry.
    """
    if not 2 <= bits <= 8:
        raise ValueError(f"PSI weight width must be in [2, 8] bits, got {bits}")
    n_psi = DEFAULT_N_PSI[bits] if n_psi is None else n_psi
    max_exp = bits - 1 if max_exp is None else max_exp
    w_min, w_max = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    vals = _value_table(bits, n_psi, max_exp)
    w = np.arange(w_min, w_max + 1)
    rel = np.abs(vals - w) / np.maximum(np.abs(w), 1)
    return PsiFormat(bits=bits, n_psi=n_psi, max_exp=max_exp,
                     w_min=w_min, w_max=w_max,
                     exact=bool(np.array_equal(vals, w)),
                     worst_case_rel_error=float(rel.max()))


def register_format(bits: int, n_psi: Optional[int] = None,
                    max_exp: Optional[int] = None) -> PsiFormat:
    """Register (or re-register) the PSI format for a weight width."""
    fmt = make_format(bits, n_psi, max_exp)
    _REGISTRY[bits] = fmt
    return fmt


def get_format(spec: FormatLike) -> PsiFormat:
    """Look a format up by bits (5), name ("psi5"), or pass one through."""
    if isinstance(spec, PsiFormat):
        return spec
    if isinstance(spec, str):
        if not spec.startswith("psi"):
            raise ValueError(f"unknown PSI format name {spec!r}")
        spec = int(spec[3:])
    if spec not in _REGISTRY:
        raise ValueError(
            f"no PSI format registered for {spec} bits "
            f"(registered: {sorted(_REGISTRY)})")
    return _REGISTRY[spec]


def registered_bits() -> Tuple[int, ...]:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Decomposition tables (exact integer bookkeeping, numpy, built lazily).
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _decomposition_table(bits: int, n_psi: int, max_exp: int) -> np.ndarray:
    """For every integer in the INT<bits> range, the best <= n_psi-term signed
    power-of-two decomposition (minimum absolute error; ties broken toward the
    smaller reconstructed magnitude, matching a truncating hardware rounder).

    Returns int16 array of shape (range_size, 2 * n_psi): [s_1, n_1, ..., s_N, n_N]
    indexed by (w - w_min).  Unused terms have s=0, n=0.
    """
    w_min, w_max = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    terms = []  # (value, sign, exp) including the zero term
    terms.append((0, 0, 0))
    for n in range(max_exp + 1):
        terms.append((1 << n, 1, n))
        terms.append((-(1 << n), -1, n))

    # Dynamic programming over number of terms: reachable[v] = decomposition
    # of v with <= k terms.  Value space is bounded by n_psi * 2^max_exp.
    vmax = n_psi * (1 << max_exp)
    reachable = {0: ()}
    for _ in range(n_psi):
        new = dict(reachable)
        for v, combo in reachable.items():
            for tv, ts, tn in terms[1:]:
                nv = v + tv
                if -vmax <= nv <= vmax and (nv not in new or len(new[nv]) > len(combo) + 1):
                    new[nv] = combo + ((ts, tn),)
        reachable = new

    table = np.zeros((w_max - w_min + 1, 2 * n_psi), dtype=np.int16)
    for w in range(w_min, w_max + 1):
        # pick reachable value closest to w; tie -> smaller |value|
        best_v, best_err = None, None
        for v in reachable:
            err = abs(v - w)
            if best_err is None or err < best_err or (
                err == best_err and abs(v) < abs(best_v)
            ):
                best_v, best_err = v, err
        combo = reachable[best_v]
        row = []
        for (s, n) in combo:
            row.extend([s, n])
        while len(row) < 2 * n_psi:
            row.extend([0, 0])
        table[w - w_min] = row
    return table


@functools.lru_cache(maxsize=None)
def _value_table(bits: int, n_psi: int, max_exp: int) -> np.ndarray:
    tab = _decomposition_table(bits, n_psi, max_exp)
    signs = tab[:, 0::2].astype(np.int64)
    exps = tab[:, 1::2].astype(np.int64)
    return np.sum(signs * (1 << exps), axis=1).astype(np.int32)


def _best_decomposition_table(bits: int, n_psi: Optional[int] = None) -> np.ndarray:
    """Registered-format decomposition table (``n_psi`` overrides the term
    budget — used by the monotone-error property tests)."""
    fmt = get_format(bits)
    return _decomposition_table(fmt.bits, n_psi or fmt.n_psi, fmt.max_exp)


def psi_value_table(bits: FormatLike, n_psi: Optional[int] = None) -> np.ndarray:
    """Reconstructed integer value for every code in the INT<bits> range.

    ``psi_value_table(5)[w + 16]`` is the integer the hardware actually
    multiplies by when the stored weight is ``w`` — equal to ``w`` everywhere
    except +-11 -> +-10 and +-13 -> +-12 (the paper's ~9 % worst case).
    """
    fmt = get_format(bits)
    return _value_table(fmt.bits, n_psi or fmt.n_psi, fmt.max_exp)


def psi_decompose_int(w: jnp.ndarray, bits: FormatLike) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Decompose integer weights into (signs, exps), each ``(n_psi,) + w.shape``.

    Mirrors the paper's Weight-decomposition block (Fig. 6): the stored integer
    weight is decoded into the per-PSI (s, n) register values fed to the SAMs.
    """
    fmt = get_format(bits)
    tab = jnp.asarray(fmt.decomposition_table())
    rows = tab[w.astype(jnp.int32) - fmt.w_min]
    signs = jnp.moveaxis(rows[..., 0::2], -1, 0).astype(jnp.int32)
    exps = jnp.moveaxis(rows[..., 1::2], -1, 0).astype(jnp.int32)
    return signs, exps


def psi_reconstruct(signs: jnp.ndarray, exps: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`psi_decompose_int` — sum of signed shifts.

    This is exactly what one SAM + the PSI-accumulation block compute.
    """
    return jnp.sum(signs * (1 << exps), axis=0).astype(jnp.int32)


def psi_project_int(w: jnp.ndarray, bits: FormatLike) -> jnp.ndarray:
    """Project integer weights onto the PSI-representable set (what the
    hardware effectively multiplies by)."""
    fmt = get_format(bits)
    if fmt.exact:      # every in-range integer is its own PSI value
        return w.astype(jnp.int32)
    tab = jnp.asarray(fmt.value_table())
    return tab[w.astype(jnp.int32) - fmt.w_min]


def sam_multiply(x: jnp.ndarray, signs: jnp.ndarray, exps: jnp.ndarray) -> jnp.ndarray:
    """Bit-faithful model of one SAM block (Fig. 2): mux(X, -X, 0) then barrel
    shift, one partial sub-integer per (sign, exp) pair; PSIs are then summed
    (the MOA's job).  ``x`` is the INT8 activation."""
    x = x.astype(jnp.int32)
    psis = jnp.where(signs == 0, 0, jnp.where(signs > 0, x, -x)) << exps
    return jnp.sum(psis, axis=0)


def moa_sign_extension_sum(operands: jnp.ndarray, in_bits: int, out_bits: int) -> jnp.ndarray:
    """The Appendix trick: summing sign-extended two's-complement operands is
    equivalent to summing the raw low ``in_bits`` fields and adding
    ``-(num_negative) * 2^{in_bits}``.  Returns the exact sum, computed the
    hardware's way, for validation against ``operands.sum()``.
    """
    operands = operands.astype(jnp.int32)
    num_neg = jnp.sum(operands < 0, axis=0)
    low = jnp.sum(jnp.where(operands < 0, operands + (1 << in_bits), operands), axis=0)
    total = low - (num_neg << in_bits)
    # wrap to out_bits two's complement (MOA output width)
    mod = 1 << out_bits
    wrapped = ((total % mod) + mod) % mod
    return jnp.where(wrapped >= (mod >> 1), wrapped - mod, wrapped)


# ---------------------------------------------------------------------------
# QuantizedTensor: the typed serving-format weight leaf.
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(eq=False)
class QuantizedTensor:
    """A weight tensor in PSI serving format: integer storage + per-channel
    scale + its :class:`PsiFormat` as static pytree metadata.

    Storage is one of two layouts, selected by ``packed``:

    * unpacked — ``data`` is int8 codes ``(..., K, N)``, already *projected*
      onto the PSI-representable set, so dequantization is ``codes * scale``
      — identical to what the SAM array computes (reconstruct-by-shifts,
      DESIGN.md §2);
    * packed — ``data`` is uint8 bit-planes ``(..., bits, K//8, N)``
      (exactly ``bits/8`` bytes per weight in HBM).

    Registered as a pytree node: (data, scale) are children, (fmt, packed) are
    aux — so QuantizedTensor leaves flow through jit, scan (layer stacks slice
    along the leading dim), device_put, and eval_shape unchanged, and every
    consumer dispatches on ``isinstance(leaf, QuantizedTensor)`` + ``leaf.fmt``
    instead of sniffing dict keys.
    """
    data: jnp.ndarray    # int8 codes or uint8 bit-planes (see ``packed``)
    scale: jnp.ndarray   # f32, broadcastable to the code shape
    fmt: PsiFormat
    packed: bool = False

    # ------------------------------------------------------------ properties
    @property
    def bits(self) -> int:
        return self.fmt.bits

    @property
    def codes(self) -> jnp.ndarray:
        """Int8 codes ``(..., K, N)`` — unpacks bit-planes on demand."""
        if self.packed:
            return unpack_codes(self.data, self.fmt)
        return self.data

    @property
    def shape(self) -> Tuple[int, ...]:
        """Logical (dense-weight) shape."""
        if self.packed:
            *lead, _, kb, n = self.data.shape
            return (*lead, kb * 8, n)
        return tuple(self.data.shape)

    @property
    def ndim(self) -> int:
        return len(self.shape)

    # ------------------------------------------------------------ conversions
    def dequantize(self, dtype=jnp.bfloat16) -> jnp.ndarray:
        """The one shared dequantization: codes * scale, cast to ``dtype``."""
        return (self.codes.astype(jnp.float32) * self.scale).astype(dtype)

    def gather_rows(self, ids: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
        """Dequantize only the gathered rows ``(V, D)[ids] -> (..., D)`` —
        the embedding-lookup path.  Packed tables unpack per gathered row
        (bit ``ids % 8`` of byte ``ids // 8`` in each plane) instead of
        expanding the whole table."""
        if self.packed:
            rows = unpack_rows(self.data, ids, self.fmt)
        else:
            rows = self.data[ids]
        return (rows.astype(jnp.float32) * self.scale[ids]).astype(dtype)

    def pack(self) -> "QuantizedTensor":
        """Bit-plane-packed copy (sub-byte formats only; no-op when packed)."""
        if self.packed:
            return self
        return QuantizedTensor(pack_codes(self.data, self.fmt), self.scale,
                               self.fmt, packed=True)

    def draft_view(self, bits: FormatLike) -> "QuantizedTensor":
        """A narrower-width view of the same weights — the self-speculative
        draft model (DESIGN.md §"Self-speculative decoding").

        The view is derived from the stored codes alone (no float checkpoint
        round-trip): codes rescale from the source grid to the draft grid
        (``round(c * qmax_d / qmax_s)``, clipped, PSI-projected) and the
        per-channel scale absorbs the grid ratio (``scale * qmax_s/qmax_d``).
        Because symmetric quantization puts the per-channel max |code| exactly
        at ``qmax_s``, this equals ``quantize_weights(self.dequantize(f32),
        bits)`` code-for-code: the rounding boundaries sit at half-integers of
        the draft grid — never exact ties, since both qmax values are odd —
        with granularity ``1/(2*qmax_s)``, far above f32 rounding error.  The
        invariant is property-tested in tests/test_psi.py.

        Packing is preserved: a packed source yields a packed draft (the
        draft planes are the subset-*sized* artifact the bit-plane layout
        promises — ``bits/8`` bytes per weight, no second checkpoint).
        """
        dfmt = get_format(bits)
        if dfmt.bits > self.fmt.bits:
            raise ValueError(
                f"draft_view narrows only: {self.fmt.name} -> {dfmt.name}")
        if dfmt.bits == self.fmt.bits:
            return self
        ratio = dfmt.qmax / self.fmt.qmax
        c = jnp.clip(jnp.round(self.codes.astype(jnp.float32) * ratio),
                     dfmt.w_min, dfmt.w_max).astype(jnp.int32)
        c = psi_project_int(c, dfmt)
        scale = (self.scale.astype(jnp.float32)
                 * (self.fmt.qmax / dfmt.qmax)).astype(jnp.float32)
        out = QuantizedTensor(c.astype(jnp.int8), scale, dfmt)
        return out.pack() if self.packed else out

    def unpack(self) -> "QuantizedTensor":
        if not self.packed:
            return self
        return QuantizedTensor(self.codes, self.scale, self.fmt, packed=False)

    # ---------------------------------------------------------------- pytree
    def tree_flatten(self):
        return (self.data, self.scale), (self.fmt, self.packed)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0], aux[1])


# ---------------------------------------------------------------------------
# Float-weight quantization (per-channel symmetric) + QAT straight-through.
# ---------------------------------------------------------------------------
def compute_scale(w: jnp.ndarray, bits: FormatLike, axis) -> jnp.ndarray:
    """Symmetric per-channel scale: max|w| along ``axis`` maps to qmax."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    return jnp.maximum(amax, 1e-8) / get_format(bits).qmax


def quantize_weights(w: jnp.ndarray, bits: FormatLike, axis=None) -> QuantizedTensor:
    """Quantize float weights to PSI format.

    ``axis`` is the reduction axis/axes for the per-channel scale (None = per
    tensor).  The integer grid point is projected onto the PSI set, so the
    stored code is bit-identical to what the TMA hardware would compute with.
    """
    fmt = get_format(bits)
    scale = compute_scale(w, fmt, axis)
    q = jnp.clip(jnp.round(w / scale), fmt.w_min, fmt.w_max).astype(jnp.int32)
    q = psi_project_int(q, fmt)
    return QuantizedTensor(q.astype(jnp.int8), scale.astype(jnp.float32), fmt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def fake_quant_ste(w: jnp.ndarray, bits: int, axis=None) -> jnp.ndarray:
    """Quantize-dequantize with a straight-through gradient — the QAT op used
    to reproduce the paper's "trained with the proposed quantization"."""
    return quantize_weights(w, bits, axis).dequantize(w.dtype)


def _fq_fwd(w, bits, axis):
    return fake_quant_ste(w, bits, axis), None


def _fq_bwd(bits, axis, _res, g):
    return (g,)


fake_quant_ste.defvjp(_fq_fwd, _fq_bwd)


def quantize_activations_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-tensor INT8 activation quantization (paper §I: 8-bit
    activations).  Used by the bit-faithful reference path."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8)
    scale = amax / 127.0
    q = jnp.clip(jnp.round(x / scale), -128, 127).astype(jnp.int8)
    return q, scale


# ---------------------------------------------------------------------------
# Sub-byte packing: INT<bits> codes as ``bits`` bit-planes (exactly bits/8
# bytes per weight in HBM), for every sub-byte width in the registry.
# ---------------------------------------------------------------------------
def pack_codes(codes: jnp.ndarray, fmt: FormatLike) -> jnp.ndarray:
    """Pack INT<bits> codes (..., K, N) -> uint8 bit-planes (..., bits, K//8, N).

    Bit ``b`` of weight ``codes[..., i*8 + j, n] + 2^(bits-1)`` (offset-binary)
    is stored at bit ``j`` of ``packed[..., b, i, n]``.  K must be divisible
    by 8.  Exactly bits/8 bytes per weight — the HBM footprint the psi_matmul
    kernel reads.
    """
    fmt = get_format(fmt)
    if not fmt.sub_byte:
        raise ValueError(f"bit-plane packing is for sub-byte widths, "
                         f"got {fmt.bits} bits")
    *lead, K, N = codes.shape
    if K % 8:
        raise ValueError(f"K={K} must be divisible by 8 for bit-plane packing")
    offs = (codes.astype(jnp.int32) + fmt.offset).astype(jnp.uint8)
    offs = offs.reshape(*lead, K // 8, 8, N)
    lane = jnp.arange(8, dtype=jnp.uint8).reshape(8, 1)
    planes = []
    for b in range(fmt.bits):
        bit = (offs >> b) & 1                      # (..., K//8, 8, N)
        plane = jnp.sum(bit.astype(jnp.uint32) << lane.astype(jnp.uint32), axis=-2)
        planes.append(plane.astype(jnp.uint8))     # (..., K//8, N)
    return jnp.stack(planes, axis=-3)              # (..., bits, K//8, N)


def unpack_codes(packed: jnp.ndarray, fmt: FormatLike) -> jnp.ndarray:
    """Inverse of :func:`pack_codes`: (..., bits, K//8, N) uint8 -> (..., K, N)
    int8.

    The reconstruction is a literal sum-of-shifts (``bit << b``) — the software
    mirror of the SAM barrel shifters.
    """
    fmt = get_format(fmt)
    *lead, nbits, Kb, N = packed.shape
    assert nbits == fmt.bits, (packed.shape, fmt)
    lane = jnp.arange(8, dtype=jnp.uint8).reshape(1, 8, 1)
    val = jnp.zeros((*lead, Kb, 8, N), dtype=jnp.int32)
    for b in range(fmt.bits):
        plane = packed[..., b, :, :][..., :, None, :]          # (..., K//8, 1, N)
        bit = (plane >> lane) & jnp.uint8(1)
        val = val + (bit.astype(jnp.int32) << b)
    codes = val.reshape(*lead, Kb * 8, N) - fmt.offset
    return codes.astype(jnp.int8)


def unpack_rows(packed: jnp.ndarray, rows: jnp.ndarray,
                fmt: FormatLike) -> jnp.ndarray:
    """Unpack only the selected logical rows of a packed (bits, V//8, D)
    table: row ``i`` is bit ``i % 8`` of byte ``i // 8`` in each plane.
    Returns int8 codes of shape ``rows.shape + (D,)`` — the gather-shaped
    counterpart of :func:`unpack_codes` used by embedding lookups."""
    fmt = get_format(fmt)
    if packed.ndim != 3:
        raise ValueError(
            f"unpack_rows expects an unstacked (bits, V//8, D) table, got "
            f"shape {packed.shape}; slice leading stack dims first")
    rows = rows.astype(jnp.int32)
    byte, bit = rows // 8, rows % 8
    val = jnp.zeros(rows.shape + (packed.shape[-1],), jnp.int32)
    for b in range(fmt.bits):
        plane = packed[b][byte]                    # rows.shape + (D,)
        val = val + (((plane.astype(jnp.int32) >> bit[..., None]) & 1) << b)
    return (val - fmt.offset).astype(jnp.int8)


def pack_int5(codes: jnp.ndarray) -> jnp.ndarray:
    """INT5 instance of :func:`pack_codes` (0.625 bytes/weight)."""
    return pack_codes(codes, 5)


def unpack_int5(packed: jnp.ndarray) -> jnp.ndarray:
    """INT5 instance of :func:`unpack_codes`."""
    return unpack_codes(packed, 5)


def packed_bytes_per_weight(bits: FormatLike) -> float:
    """HBM bytes per weight in serving format (the roofline 'memory' input)."""
    return get_format(bits).bytes_per_weight(packed=True)


# ---------------------------------------------------------------------------
# Default registry: every width the paper's "scalable integer weights less
# than 1-byte" covers.  INT5/INT8 are the paper's Table-I points; the rest
# open the sub-5-bit HBM-traffic frontier.
# ---------------------------------------------------------------------------
for _bits in sorted(DEFAULT_N_PSI):
    register_format(_bits)
del _bits
