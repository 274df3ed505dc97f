"""The yardstick's arithmetic: the chip's published peaks, and the operations
and bytes that serving a configuration requires.

Counts follow the algorithm, not the tiles a kernel happens to run: a
``psi_matmul`` call reads every weight once at its format width plus its
activations and does 2*M*K*N operations over the M rows that are really
there; a paged-attention decode read takes each active slot's real keys and
values once.  Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses

# Keyed by ``jax.Device.device_kind``; a v5e reports "TPU v5 lite".
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9},
}

ACT_BYTES = 2          # bf16 activations
SCALE_BYTES = 4        # one f32 scale per output channel


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; a kind missing from
    the table is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}: add it to PEAKS with its "
                         f"source") from None


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes of a dense GQA decoder, from a configuration file."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    bits: int
    qk_norm: bool = False

    @classmethod
    def from_config(cls, conf: dict) -> "Arch":
        return cls(conf["num_hidden_layers"], conf["hidden_size"],
                   conf["num_attention_heads"], conf["num_key_value_heads"],
                   conf["head_dim"], conf["intermediate_size"],
                   conf["vocab_size"], conf["psi_bits"],
                   bool(conf.get("qk_norm", False)))

    def layer_gemms(self):
        """(name, K, N) of each weight matrix of one layer."""
        d, q, kv = self.d_model, self.n_heads * self.head_dim, \
            self.n_kv_heads * self.head_dim
        return [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
                ("w_gate", d, self.d_ff), ("w_up", d, self.d_ff),
                ("w_down", self.d_ff, d)]

    def gemm_params_per_token(self) -> int:
        """Weights one token multiplies through: every layer's GEMMs."""
        return self.n_layers * sum(k * n for _, k, n in self.layer_gemms())

    def served_weight_bytes(self) -> int:
        """Bytes of the served weights: GEMM codes at format width (the
        ``lm_head`` too), the embedding as one int8 code per weight (it is
        gathered by rows, never bit-plane packed), one f32 scale per output
        channel or embedding row, and f32 norm scales."""
        d, V, L = self.d_model, self.vocab_size, self.n_layers
        per_layer = sum(k * n for _, k, n in self.layer_gemms())
        codes = (L * per_layer + d * V) * self.bits // 8 + V * d
        scales = SCALE_BYTES * (L * sum(n for _, _, n in self.layer_gemms())
                                + 2 * V)
        norms = 4 * (L * (2 * d + 2 * self.head_dim * self.qk_norm) + d)
        return int(codes + scales + norms)


def psi_matmul_call(M: int, K: int, N: int, bits: int):
    """(operations, bytes) of one ``psi_matmul`` call over M real rows."""
    ops = 2.0 * M * K * N
    nbytes = (K * N * bits / 8.0 + N * SCALE_BYTES
              + M * K * ACT_BYTES + M * N * ACT_BYTES)
    return ops, nbytes


def paged_attention_step(arch: Arch, contexts):
    """(FLOPs, bytes) of one decode step's paged-attention reads over all
    layers: each active slot reads its ``ctx`` keys and values once."""
    Hq, Hkv, D, L = arch.n_heads, arch.n_kv_heads, arch.head_dim, \
        arch.n_layers
    flops = nbytes = 0.0
    for ctx in contexts:
        flops += 4.0 * ctx * Hq * D
        nbytes += 2.0 * ctx * Hkv * D * ACT_BYTES + 2.0 * Hq * D * ACT_BYTES
    return L * flops, L * nbytes


def least_time(ops: float, nbytes: float, compute_peak: float,
               bw: float) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(ops / compute_peak, nbytes / bw)


def psi_matmul_least_time(arch: Arch, steps, peak: dict) -> float:
    """Least time of every ``psi_matmul`` call that the served ``steps``
    required.  A step is ``("decode", M)`` — one call per layer GEMM at M
    active rows plus the ``lm_head`` — or ``("prefill", rows, seqs)`` —
    the layer GEMMs over ``rows`` prompt tokens and the ``lm_head`` over
    the last position of each of ``seqs`` prompts.  The compute roof is the
    int8 peak: the codes are integers of 8 bits or fewer."""
    total = 0.0
    comp, bw = peak["int8_ops"], peak["hbm_bytes_per_s"]
    for step in steps:
        rows, head_rows = ((step[1], step[1]) if step[0] == "decode"
                           else (step[1], step[2]))
        for _, K, N in arch.layer_gemms():
            total += arch.n_layers * least_time(
                *psi_matmul_call(rows, K, N, arch.bits), comp, bw)
        total += least_time(*psi_matmul_call(
            head_rows, arch.d_model, arch.vocab_size, arch.bits), comp, bw)
    return total


def paged_attention_least_time(arch: Arch, decode_contexts,
                               peak: dict) -> float:
    """Least time of the paged-attention reads of every decode step; each
    entry of ``decode_contexts`` lists the active slots' context lengths.
    The compute roof is the bf16 peak."""
    total = 0.0
    for contexts in decode_contexts:
        total += least_time(*paged_attention_step(arch, contexts),
                            peak["bf16_flops"], peak["hbm_bytes_per_s"])
    return total


def prefill_flops(arch: Arch, prompt_len: int) -> float:
    """Model FLOPs of one prompt's prefill: every layer GEMM over each
    token, causal attention, and the ``lm_head`` at the last position."""
    S = prompt_len
    attn = 4.0 * arch.n_heads * arch.head_dim * S * (S + 1) / 2
    return (2.0 * arch.gemm_params_per_token() * S + arch.n_layers * attn
            + 2.0 * arch.d_model * arch.vocab_size)


def decode_flops(arch: Arch, ctx: int) -> float:
    """Model FLOPs of one decoded token at context ``ctx``."""
    return (2.0 * arch.gemm_params_per_token()
            + arch.n_layers * 4.0 * ctx * arch.n_heads * arch.head_dim
            + 2.0 * arch.d_model * arch.vocab_size)
