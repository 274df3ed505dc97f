"""Model-level PSI quantization: walk a parameter pytree and convert matmul
weights into PSI serving format (:class:`repro.core.psi.QuantizedTensor`
leaves — integer codes + per-channel scale, optionally packed sub-byte
bit-planes).

This is the software analogue of the paper's flow (Fig. 6): weights live in
DRAM/SRAM in compact integer form and the Weight-decomposition block expands
them on the way into the compute array.  Here the "compute array" is the
psi_matmul Pallas kernel which expands codes inside VMEM.

Mixed precision is a first-class policy: ``quantize_param_tree(params,
policy={"embed": 8, "w_down": 4, "default": 5})`` assigns a registered
:class:`~repro.core.psi.PsiFormat` per terminal leaf name — the lever the
memory-bound regime rewards (per-layer bytes/weight is the HBM-traffic dial).
"""
from __future__ import annotations

import re
import warnings
from typing import Any, Dict, Mapping, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import psi

# Only leaves whose terminal name matches this include-list are quantized:
# GEMM weights and embedding tables.  Everything else (norm scales, biases —
# including biases that become 2-D when layer-stacked for scan — the mamba
# a_log dynamics matrix, depthwise conv mixers, and the MoE router, whose
# quantization flips top-k routing decisions for negligible storage gain)
# passes through in full precision.  See DESIGN.md §2.
WEIGHT_NAMES = (
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "w_out",
    "w_in_rec", "w_in_gate", "rglru_wa", "rglru_wx",
    "in_proj", "x_proj", "dt_proj_w", "out_proj",
    "embed", "lm_head", "convk", "w",
)
_INCLUDE_RE = re.compile(r"(^|/)(%s)$" % "|".join(WEIGHT_NAMES))

DEFAULT_EXCLUDE = (
    r"a_log",        # mamba state matrix (parameterizes dynamics, not a GEMM)
    r"conv1d",       # mamba / rg-lru short conv (depthwise, tiny)
    r"norm",
    r"bias",
    r"router",       # tiny; quantizing it flips top-k routing
)

# A policy maps terminal leaf names (regex alternatives, matched like the
# include-list) to registered bit-widths; "default" covers the rest.  A bits
# value of 0/None leaves those weights in float.
Policy = Mapping[str, Optional[int]]


def parse_quant_mode(mode: str) -> Tuple[Optional[str], Optional[int]]:
    """"none" -> (None, None); "qatN" -> ("qat", N); "psiN" -> ("psi", N).
    N must name a registered :class:`~repro.core.psi.PsiFormat`."""
    if mode in ("", "none", None):
        return None, None
    m = re.fullmatch(r"(qat|psi)(\d+)", mode)
    if not m:
        raise ValueError(f"unknown quant mode {mode!r} "
                         f"(expected none / qatN / psiN)")
    kind, bits = m.group(1), int(m.group(2))
    psi.get_format(bits)      # raises on unregistered widths
    return kind, bits


def quant_mode_choices() -> Tuple[str, ...]:
    """Valid quant-mode strings, derived from the format registry (the
    replacement for the old hard-coded QUANT_MODES tuple)."""
    bits = psi.registered_bits()
    return (("none",) + tuple(f"qat{b}" for b in bits)
            + tuple(f"psi{b}" for b in bits))


def serving_mode_choices() -> Tuple[str, ...]:
    """Registry-derived serving-format choices for the serve/dryrun CLIs
    (QAT modes are a training concern and are excluded)."""
    return ("none",) + tuple(f"psi{b}" for b in psi.registered_bits())


def parse_policy(spec: Union[str, Policy, None]) -> Optional[Dict[str, Optional[int]]]:
    """Normalize a mixed-precision policy.

    Accepts a mapping ({"embed": 8, "default": 5}) or the CLI string form
    "embed=8,w_down=4,default=5".  Every bits value must name a registered
    format (0 means "keep float").
    """
    if spec is None:
        return None
    if isinstance(spec, str):
        out: Dict[str, Optional[int]] = {}
        for item in spec.split(","):
            item = item.strip()
            if not item:
                continue
            name, _, val = item.partition("=")
            if not _:
                raise ValueError(f"policy entry {item!r} is not name=bits")
            out[name.strip()] = int(val)
    else:
        out = dict(spec)
    for name, bits in out.items():
        if bits:
            psi.get_format(bits)
        if name == "default":
            continue
        try:
            re.compile(rf"(^|/)(?:{name})$")
        except re.error as e:
            # fail at the flag, not with a raw re.error deep inside tree_map
            raise ValueError(
                f"policy name {name!r} is not a valid leaf-name pattern "
                f"({e})") from None
    return out


def _policy_bits(path: str, policy: Optional[Dict[str, Optional[int]]],
                 default: Optional[int]) -> Optional[int]:
    """Resolve the bit-width for one leaf: first policy entry whose name
    matches the leaf's terminal path component wins, then the policy's
    "default", then the uniform ``default`` bits."""
    if policy:
        for name, bits in policy.items():
            if name == "default":
                continue
            if re.search(rf"(^|/)(?:{name})$", path):
                return bits
        if "default" in policy:
            return policy["default"]
    return default


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def is_quantizable(path: str, leaf: Any) -> bool:
    if not hasattr(leaf, "ndim") or leaf.ndim < 2:
        return False
    if not jnp.issubdtype(leaf.dtype, jnp.floating):
        return False
    if not _INCLUDE_RE.search(path):
        return False
    return not any(re.search(p, path) for p in DEFAULT_EXCLUDE)


def _scale_axis(path: str, leaf) -> tuple:
    # Embedding tables: per-row scales (quality: each token row independent).
    if re.search(r"embed", path):
        return (leaf.ndim - 1,)
    # CNN kernels (H, W, I, O): per-output-channel over all spatial+input dims.
    if re.search(r"convk", path):
        return tuple(range(leaf.ndim - 1))
    # GEMM weights: reduce ONLY the contraction dim (second-to-last), so
    # layer-stacked (L, K, N) and per-expert (L, E, d, f) tensors keep
    # per-layer / per-expert scales with matching leading axes (scan-safe).
    return (leaf.ndim - 2,)


def quantize_leaf(path: str, leaf: Any, bits: Optional[int] = None,
                  pack: bool = False,
                  policy: Optional[Dict[str, Optional[int]]] = None) -> Any:
    """One leaf of :func:`quantize_param_tree` (``path`` is its
    ``/``-joined tree path; ``policy`` already parsed).  Non-quantizable
    leaves, and leaves the policy keeps in float, pass through."""
    if not is_quantizable(path, leaf):
        return leaf
    leaf_bits = _policy_bits(path, policy, bits)
    if not leaf_bits:
        return leaf
    q = psi.quantize_weights(leaf, leaf_bits, axis=_scale_axis(path, leaf))
    if (pack and q.fmt.sub_byte and leaf.ndim >= 2
            and leaf.shape[-2] % 8 == 0 and not re.search(r"embed", path)):
        return q.pack()
    return q


def check_policy(policy: Optional[Dict[str, Optional[int]]],
                 params: Dict) -> None:
    """Warn about policy entries that silently have no effect on ``params``
    (arrays or shape structs) — exactly the failure mixed precision exists
    to avoid.  Two loud cases: a key matching no leaf at all (typo), and a
    *nonzero*-bits key matching only excluded/non-quantizable leaves
    (contradicted intent — e.g. router=8 when the router is on the exclude
    list).  A deliberate {"router": 0} keep-float entry stays quiet."""
    if not policy:
        return
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    paths = [_path_str(p) for p, _ in leaves]
    qpaths = [_path_str(p) for p, leaf in leaves
              if is_quantizable(_path_str(p), leaf)]

    def hit(key, pool):
        return any(re.search(rf"(^|/)(?:{key})$", p) for p in pool)

    dead = [k for k in policy if k != "default" and not hit(k, paths)]
    ineffective = [k for k in policy
                   if k != "default" and policy[k] and k not in dead
                   and not hit(k, qpaths)]
    if dead:
        warnings.warn(
            f"quantization policy entries matched no parameter leaf: "
            f"{sorted(dead)} (known weight names: {WEIGHT_NAMES})",
            stacklevel=3)
    if ineffective:
        warnings.warn(
            f"quantization policy entries match only excluded/"
            f"non-quantizable leaves and have no effect: "
            f"{sorted(ineffective)} (see DEFAULT_EXCLUDE)", stacklevel=3)


def quantize_param_tree(
    params: Dict,
    bits: Optional[int] = None,
    pack: bool = False,
    exclude: Optional[tuple] = None,
    policy: Union[str, Policy, None] = None,
) -> Dict:
    """Return a new tree where quantizable leaves become
    :class:`~repro.core.psi.QuantizedTensor` serving leaves.

    * ``bits`` — uniform width for every quantizable leaf;
    * ``policy`` — per-layer mixed precision, e.g. ``{"embed": 8,
      "w_down": 4, "default": 5}`` (overrides ``bits`` where it matches);
    * ``pack=True`` — sub-byte leaves additionally bit-plane pack
      (``fmt.bits/8`` bytes per weight in HBM) when the contraction dim is a
      multiple of 8; embeddings stay unpacked (row-gather path).

    Non-quantizable leaves pass through unchanged.
    """
    exclude = DEFAULT_EXCLUDE if exclude is None else exclude
    policy = parse_policy(policy)
    if bits is None and not policy:
        raise ValueError("pass uniform bits= and/or a mixed-precision policy=")
    out = jax.tree_util.tree_map_with_path(
        lambda path, leaf: quantize_leaf(_path_str(path), leaf, bits, pack,
                                         policy), params)
    check_policy(policy, params)
    return out


def draft_param_tree(params: Dict, draft_bits: int) -> Dict:
    """Self-speculative draft parameters: every :class:`QuantizedTensor` leaf
    wider than ``draft_bits`` is replaced by its :meth:`draft_view` (derived
    from the stored codes, no re-quantization from float); float leaves and
    leaves already at or below the draft width pass through unchanged, so the
    draft tree has the *same pytree structure* as the serving tree and reuses
    its sharding specs verbatim."""
    fmt = psi.get_format(draft_bits)

    def convert(leaf):
        if isinstance(leaf, psi.QuantizedTensor) and leaf.fmt.bits > fmt.bits:
            return leaf.draft_view(fmt)
        return leaf

    return jax.tree_util.tree_map(
        convert, params,
        is_leaf=lambda x: isinstance(x, psi.QuantizedTensor))


def dequantize(leaf: Any, dtype=jnp.bfloat16):
    """THE shared dequantize helper: expand one serving-format leaf back to a
    dense float array; non-quantized leaves pass through.  Every inline
    scale-application in the model zoo routes here (DESIGN.md §2)."""
    if isinstance(leaf, psi.QuantizedTensor):
        return leaf.dequantize(dtype)
    return leaf


# Backwards-compatible name (pre-QuantizedTensor API).
dequantize_leaf = dequantize


def fake_quant_param_tree(params: Dict, bits: int, exclude: Optional[tuple] = None) -> Dict:
    """QAT forward transform: quantize-dequantize every quantizable leaf with a
    straight-through gradient.  Apply inside the loss so dLoss/dw flows to the
    latent float weights (paper: networks are *trained with* the quantization).
    """
    exclude = DEFAULT_EXCLUDE if exclude is None else exclude
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: fake_quant_leaf(_path_str(path), leaf, bits),
        params)


def fake_quant_leaf(path: str, leaf: Any, bits: int) -> Any:
    """One leaf of :func:`fake_quant_param_tree`."""
    if not is_quantizable(path, leaf):
        return leaf
    return psi.fake_quant_ste(leaf, bits, _scale_axis(path, leaf))


def quantized_bytes(params: Dict) -> int:
    """Total serving-format bytes (for EXPERIMENTS.md compression reporting).
    QuantizedTensor leaves flatten to their storage (codes or packed planes)
    plus scales, so packed sub-byte formats report their true footprint."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        total += leaf.size * leaf.dtype.itemsize
    return total
