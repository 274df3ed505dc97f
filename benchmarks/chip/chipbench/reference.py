"""The plain reference: a dense GQA decoder in float32 ``jax.numpy`` at
``Precision.HIGHEST`` (RMSNorm, RoPE with rotate-half, optional qk-norm,
SwiGLU, untied ``lm_head``), as the published Qwen3 and Phi-3 configs
describe it.  It imports nothing of the program: it draws the seed's
codes again (``chipbench.weights``) and dequantizes them as code * scale.

Runs a layer at a time, each sequence alone at one padded length, so it
fits next to nothing else on the chip and compiles once per configuration.  ``lower`` casts every matmul operand to
a narrower float first: the control, computed a step below the precision
the configuration states.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.work import Arch

HIGHEST = jax.lax.Precision.HIGHEST


def _cast(x, lower):
    """x rounded to the type ``lower``; an 8-bit float is scaled per tensor
    so that its largest magnitude meets the type's largest finite value."""
    if lower is None:
        return x
    top = float(jnp.finfo(lower).max)
    if top > 1e5:
        return x.astype(lower).astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(lower).astype(jnp.float32) * s


def _mm(a, b, lower):
    return jnp.matmul(_cast(a, lower), _cast(b, lower), precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotate-half RoPE over the whole head dim; x (B, S, H, D)."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos[:, :, None].astype(jnp.float32) * inv          # (B, S, D/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None]
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _deq(codes, scale):
    return codes.astype(jnp.float32) * scale[None, :]


@functools.partial(jax.jit, static_argnames=("arch", "qk_norm", "lower",
                                             "eps", "theta"))
def _block(x, w, *, arch: Arch, qk_norm: bool, lower, eps, theta):
    B, S, _ = x.shape
    Hq, Hkv, D = arch.n_heads, arch.n_kv_heads, arch.head_dim
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = _rms(x, w["attn_norm"], eps)
    q = _mm(h, _deq(*w["wq"]), lower).reshape(B, S, Hq, D)
    k = _mm(h, _deq(*w["wk"]), lower).reshape(B, S, Hkv, D)
    v = _mm(h, _deq(*w["wv"]), lower).reshape(B, S, Hkv, D)
    if qk_norm:
        q = _rms(q, w["q_norm"], eps)
        k = _rms(k, w["k_norm"], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    g = Hq // Hkv
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", _cast(q, lower), _cast(k, lower),
                   precision=HIGHEST) * (D ** -0.5)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _cast(p, lower), _cast(v, lower),
                   precision=HIGHEST).reshape(B, S, Hq * D)
    x = x + _mm(o, _deq(*w["wo"]), lower)
    h = _rms(x, w["mlp_norm"], eps)
    a = jax.nn.silu(_mm(h, _deq(*w["w_gate"]), lower)) \
        * _mm(h, _deq(*w["w_up"]), lower)
    return x + _mm(a, _deq(*w["w_down"]), lower)


@functools.partial(jax.jit, static_argnames=("arch", "conf_key"))
def _layer_weights(base, l, *, arch: Arch, conf_key):
    conf = dict(conf_key)
    return weights.layer(base, arch, conf, l,
                         weights.projection_table(arch.bits,
                                                  conf["psi_terms"]))


@functools.partial(jax.jit, static_argnames=("arch", "conf_key"))
def _outer_weights(base, *, arch: Arch, conf_key):
    conf = dict(conf_key)
    return weights.outer(base, arch, weights.projection_table(
        arch.bits, conf["psi_terms"]))


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(h, norm, codes, scale, *, eps, lower):
    """Logits (T, V) of final hidden rows h (T, D)."""
    return _mm(_rms(h, norm, eps), _deq(codes, scale), lower)


def _embed(codes, scale, ids):
    return codes[ids].astype(jnp.float32) * scale[ids][..., None]


def hidden(seed: int, arch: Arch, conf: dict, seqs: Sequence[np.ndarray],
           seq_len: int, lowers=(None,)):
    """Final hidden states of each sequence, each padded to ``seq_len``
    (causal, so right padding changes no real position): a list over
    ``lowers`` of lists over sequences of (1, seq_len, D) f32.  Every
    sequence runs at the one shape, so the compiled layer is reused from
    run to run; each layer's weights are drawn once for all of them."""
    base = weights.seed_key(seed)
    key = _conf_key(conf)
    o = _outer_weights(base, arch=arch, conf_key=key)
    xs = []
    for s in seqs:
        ids = np.zeros((1, seq_len), np.int32)
        ids[0, :len(s)] = s
        xs.append(_embed(*o["embed"], jnp.asarray(ids)))
    del o
    out = [list(xs) for _ in lowers]
    kw = dict(arch=arch, qk_norm=bool(conf.get("qk_norm")),
              eps=float(conf["rms_norm_eps"]),
              theta=float(conf["rope_theta"]))
    for l in range(arch.n_layers):
        w = _layer_weights(base, jnp.int32(l), arch=arch, conf_key=key)
        out = [[_block(x, w, lower=lower, **kw) for x in xs_]
               for lower, xs_ in zip(lowers, out)]
        del w
    return out


def _conf_key(conf: dict):
    return tuple(sorted((k, v) for k, v in conf.items()
                        if k in ("psi_terms", "qk_norm")))


def logit_gaps(seed: int, arch: Arch, conf: dict,
               seqs: Sequence[np.ndarray], targets: Sequence[np.ndarray],
               seq_len: int, lower=None, chunk: int = 256):
    """For each sequence i and each position p listed with its token in
    ``targets[i]`` ((n, 2) int: position, token), the reference's logits at
    p: returns per sequence the gap ``max(logits) - logits[token]`` and,
    with ``lower`` set, the gap of the token that the narrower forward puts
    first (the control) — as a list of (gaps, control_gaps or None)."""
    eps = float(conf["rms_norm_eps"])
    lowers = (None, lower) if lower is not None else (None,)
    hs = hidden(seed, arch, conf, seqs, seq_len, lowers)
    o = _outer_weights(weights.seed_key(seed), arch=arch,
                       conf_key=_conf_key(conf))
    codes, scale = o["lm_head"]
    norm = o["final_norm"]
    out: List = []
    for i, tg in enumerate(targets):
        n = len(tg)
        pad = -(-n // chunk) * chunk
        pos = np.zeros((pad,), np.int32)
        tok = np.zeros((pad,), np.int32)
        pos[:n], tok[:n] = tg[:, 0], tg[:, 1]
        gaps, cgaps = [], []
        for c in range(0, pad, chunk):
            p = jnp.asarray(pos[c:c + chunk])
            lg = _head(hs[0][i][0, p], norm, codes, scale, eps=eps,
                       lower=None)
            best = jnp.max(lg, -1)
            t = jnp.asarray(tok[c:c + chunk])
            gaps.append(np.asarray(best - jnp.take_along_axis(
                lg, t[:, None], 1)[:, 0]))
            if lower is not None:
                lo = _head(hs[1][i][0, p], norm, codes, scale, eps=eps,
                           lower=lower)
                pick = jnp.argmax(lo, -1)
                cgaps.append(np.asarray(best - jnp.take_along_axis(
                    lg, pick[:, None], 1)[:, 0]))
        out.append((np.concatenate(gaps)[:n],
                    np.concatenate(cgaps)[:n] if cgaps else None))
    return out


def control_dtype(conf: dict) -> Optional[object]:
    """The float one step below the configuration's activation type: fp8
    (e4m3) below bfloat16."""
    below = {"bfloat16": jnp.float8_e4m3fn, "float16": jnp.float8_e4m3fn,
             "float32": jnp.bfloat16}
    return below[conf["torch_dtype"]]
