"""Random PSI checkpoints made from the seed.

Every weight is an integer code drawn from random bits by integer
arithmetic alone, with a per-channel f32 scale made by one multiplication
of an integer, so the same seed gives the same numbers bit for bit in any
program that computes them: the served tree is built in one jitted call,
and the reference draws the same layer again, alone, once the served tree
is gone.  Codes lie in the PSI-representable set of the width: integers
that are sums of at most ``psi_terms`` signed powers of two with exponents
up to ``bits - 1``.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.work import Arch

# leaf ids: the key of leaf ``name`` of layer ``l`` is
# fold_in(fold_in(seed_key, LEAF_IDS[name]), l)
LEAF_IDS = {"embed": 1, "lm_head": 2, "final_norm": 3, "wq": 10, "wk": 11,
            "wv": 12, "wo": 13, "w_gate": 14, "w_up": 15, "w_down": 16,
            "attn_norm": 20, "mlp_norm": 21, "q_norm": 22, "k_norm": 23}
# std of the 8-bit code made from the sum of four random bytes
_CODE8_STD = float(np.sqrt(4 * (256 ** 2 - 1) / 12) / 4)


def representable(bits: int, terms: int) -> np.ndarray:
    """Sorted integers of the ``bits``-wide range that are a sum of at most
    ``terms`` signed powers of two with exponents in [0, bits - 1]."""
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    vals = {0}
    powers = [s * (1 << e) for e in range(bits) for s in (1, -1)]
    for _ in range(terms):
        vals |= {v + p for v in vals for p in powers}
    return np.asarray(sorted(v for v in vals if lo <= v <= hi), np.int32)


def projection_table(bits: int, terms: int) -> np.ndarray:
    """code + 2^(bits-1) -> nearest representable code (ties to the
    smaller magnitude)."""
    rep = representable(bits, terms)
    lo = -(1 << (bits - 1))
    out = []
    for c in range(lo, -lo):
        d = np.abs(rep - c)
        best = rep[d == d.min()]
        out.append(best[np.argmin(np.abs(best))])
    return np.asarray(out, np.int32)


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31 and
    beyond: the seed is split into two 32-bit words."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _key(base, name: str, layer):
    return jax.random.fold_in(jax.random.fold_in(base, LEAF_IDS[name]),
                              layer)


def draw_codes(key, shape, bits: int, table) -> jnp.ndarray:
    """int8 PSI codes: the sum of four random bytes, centred and shifted to
    the width (a bell-shaped draw), then projected onto the representable
    set by one select per code that is not representable (elementwise: a
    table gather over billions of codes is slow on the chip)."""
    b = jax.random.bits(key, shape, jnp.uint32)
    s = ((b & 255) + ((b >> 8) & 255) + ((b >> 16) & 255)
         + (b >> 24)).astype(jnp.int32)
    c = ((s - 510) >> 2) >> (8 - bits)
    lo = -(1 << (bits - 1))
    out = c
    for i, v in enumerate(np.asarray(table).tolist()):
        if v != lo + i:
            out = jnp.where(c == lo + i, v, out)
    return out.astype(jnp.int8)


def draw_scale(key, n: int, std: float, bits: int) -> jnp.ndarray:
    """(n,) f32 per-channel scales: the weight std ``std`` over the code
    std, times a factor in [0.75, 1.25) from random bits."""
    code_std = _CODE8_STD / (1 << (8 - bits))
    unit = jnp.float32(std / code_std / 1024.0)
    b = jax.random.bits(key, (n,), jnp.uint32)
    return (768 + (b & 511)).astype(jnp.float32) * unit


def draw_norm(key, n: int) -> jnp.ndarray:
    """(n,) f32 norm weights in [0.75, 1.25)."""
    b = jax.random.bits(key, (n,), jnp.uint32)
    return (768 + (b & 511)).astype(jnp.float32) * jnp.float32(1 / 1024)


def _gemm(base, name, layer, K, N, bits, table):
    kc, ks = jax.random.split(_key(base, name, layer))
    return (draw_codes(kc, (K, N), bits, table),
            draw_scale(ks, N, K ** -0.5, bits))


def layer(base, arch: Arch, conf: dict, l, table) -> Dict:
    """Layer ``l``'s weights as plain arrays: ``{name: (codes (K, N) int8,
    scale (N,) f32)}`` for the GEMMs and ``{name: (n,) f32}`` for norms."""
    out = {name: _gemm(base, name, l, K, N, arch.bits, table)
           for name, K, N in arch.layer_gemms()}
    out["attn_norm"] = draw_norm(_key(base, "attn_norm", l), arch.d_model)
    out["mlp_norm"] = draw_norm(_key(base, "mlp_norm", l), arch.d_model)
    if conf.get("qk_norm"):
        out["q_norm"] = draw_norm(_key(base, "q_norm", l), arch.head_dim)
        out["k_norm"] = draw_norm(_key(base, "k_norm", l), arch.head_dim)
    return out


def outer(base, arch: Arch, table) -> Dict:
    """Embedding (codes (V, D), per-row scale (V,)), ``lm_head`` (codes
    (D, V), per-column scale (V,)) and the final norm."""
    V, D = arch.vocab_size, arch.d_model
    ke, kes = jax.random.split(_key(base, "embed", 0))
    return {"embed": (draw_codes(ke, (V, D), arch.bits, table),
                      draw_scale(kes, V, D ** -0.5, arch.bits)),
            "lm_head": _gemm(base, "lm_head", 0, D, V, arch.bits, table),
            "final_norm": draw_norm(_key(base, "final_norm", 0), D)}


def table_for(arch: Arch, conf: dict) -> np.ndarray:
    return projection_table(arch.bits, conf["psi_terms"])


def served_tree(seed: int, arch: Arch, conf: dict, fmt, qt_cls):
    """The program's serving parameter tree, built on the device in one
    jitted call: ``QuantizedTensor`` leaves (``qt_cls`` with format
    ``fmt``; packed by the type's own ``pack`` where the width is under a
    byte, the embedding excepted) with layers stacked on a leading axis."""
    table = table_for(arch, conf)
    sub_byte = arch.bits < 8

    def qt(codes, scale, scale_shape, pack):
        t = qt_cls(codes, scale.reshape(scale_shape), fmt)
        return t.pack() if pack else t

    def one_layer(base, l):
        w = layer(base, arch, conf, l, table)
        attn = {n: qt(*w[n], (1, -1), sub_byte)
                for n in ("wq", "wk", "wv", "wo")}
        if conf.get("qk_norm"):
            attn["q_norm_scale"] = w["q_norm"]
            attn["k_norm_scale"] = w["k_norm"]
        return {"norm1": {"scale": w["attn_norm"]}, "attn": attn,
                "norm2": {"scale": w["mlp_norm"]},
                "mlp": {n: qt(*w[n], (1, -1), sub_byte)
                        for n in ("w_gate", "w_up", "w_down")}}

    @jax.jit
    def build(base):
        o = outer(base, arch, table)
        stack = jax.lax.map(functools.partial(one_layer, base),
                            jnp.arange(arch.n_layers))
        return {"embed": qt(*o["embed"], (-1, 1), False),
                "norm_f": {"scale": o["final_norm"]},
                "lm_head": qt(*o["lm_head"], (1, -1), sub_byte),
                "stack": {"groups": {"b0_attn": stack}, "tail": []}}

    return build(seed_key(seed))
