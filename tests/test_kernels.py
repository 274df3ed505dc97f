"""Pallas psi_matmul kernels vs the pure-jnp oracle: shape/dtype sweeps in
interpret mode (the kernel body executes on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import psi
from repro.kernels import ops, psi_matmul as pk, ref


def _quant(w, bits):
    q = psi.quantize_weights(w, bits, axis=0)
    return q.codes, q.scale.reshape(-1)


# qwen3-8b's decode GEMMs (K, N): wq/wo, wk/wv, w_gate/w_up, w_down,
# lm_head; and their local N shards on a 2x2 mesh (N over "model").
QWEN3_DECODE_GEMMS = [(4096, 4096), (4096, 1024), (4096, 12288),
                      (12288, 4096), (4096, 151936)]
QWEN3_2X2_SHARDS = [(K, N // 2) for K, N in QWEN3_DECODE_GEMMS]


def _int8_pads(jaxpr):
    """``pad`` equations on int8 operands anywhere in a (closed) jaxpr."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pad" and \
                eqn.invars[0].aval.dtype == jnp.int8:
            found.append(eqn)
        for v in eqn.params.values():
            if hasattr(v, "eqns") or hasattr(getattr(v, "jaxpr", None),
                                             "eqns"):
                found += _int8_pads(v)
    return found


SHAPES = [
    (8, 16, 8),          # tiny (full padding path)
    (128, 128, 128),     # exactly one tile
    (200, 136, 72),      # ragged, all dims padded
    (256, 384, 256),     # multi-tile
    (1, 512, 128),       # decode-like M=1
]


@pytest.mark.parametrize("M,K,N", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_int8_kernel_vs_ref(M, K, N, dtype):
    rng = np.random.default_rng(hash((M, K, N)) % 2 ** 31)
    x = jnp.asarray(rng.normal(size=(M, K)), dtype)
    w = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32))
    codes, scale = _quant(w, 8)
    got = pk.psi_matmul_int8(x, codes, scale, interpret=True)
    want = ref.psi_matmul_int8_ref(x, codes, scale)
    # bf16 outputs may differ by 1 ulp (tiled vs single-einsum f32
    # accumulation order rounds differently at the bf16 cast)
    tol = dict(rtol=1e-5, atol=1e-4) if dtype == jnp.float32 \
        else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("M,K,N", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_int5_kernel_vs_ref(M, K, N, dtype):
    rng = np.random.default_rng(hash((M, K, N, 5)) % 2 ** 31)
    x = jnp.asarray(rng.normal(size=(M, K)), dtype)
    w = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32))
    q = psi.quantize_weights(w, 5, axis=0)
    planes = psi.pack_int5(q.codes)
    scale = q.scale.reshape(-1)
    got = pk.psi_matmul_int5(x, planes, scale, interpret=True)
    want = ref.psi_matmul_int5_ref(x, planes, scale)
    tol = dict(rtol=1e-5, atol=1e-4) if dtype == jnp.float32 \
        else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_int8_kernel_block_shape_sweep():
    """Kernel result is block-shape invariant (accumulation correctness)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(96, 160)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(160, 192)).astype(np.float32))
    codes, scale = _quant(w, 8)
    want = ref.psi_matmul_int8_ref(x, codes, scale)
    for bm, bn, bk in [(32, 64, 32), (128, 128, 128), (96, 192, 160),
                       (16, 16, 16)]:
        got = pk.psi_matmul_int8(x, codes, scale, bm=bm, bn=bn, bk=bk,
                                 interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)


def test_int5_kernel_block_shape_sweep():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(64, 128)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(128, 96)).astype(np.float32))
    q = psi.quantize_weights(w, 5, axis=0)
    planes = psi.pack_int5(q.codes)
    scale = q.scale.reshape(-1)
    want = ref.psi_matmul_int5_ref(x, planes, scale)
    for bm, bn, bk in [(32, 32, 32), (64, 96, 64), (64, 96, 128)]:
        got = pk.psi_matmul_int5(x, planes, scale, bm=bm, bn=bn, bk=bk,
                                 interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)


def test_ops_dispatch_cpu_matches_interpret(monkeypatch):
    """ops.psi_matmul (CPU oracle path) == forced interpret-kernel path."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(4, 10, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(64, 48)).astype(np.float32))
    leaf = psi.quantize_weights(w, 8, axis=0)
    got_ref = ops.psi_matmul(x, leaf)
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    got_kernel = ops.psi_matmul(x, leaf)
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(got_kernel),
                               rtol=1e-5, atol=1e-4)
    assert got_ref.shape == (4, 10, 48)


class TestDecodeDispatch:
    """Small-M tile dispatch: the decode step (M = active slots <= 16) must
    not pad M up to the 128-row MXU tile."""

    def test_pick_bm_tile_floor_and_cap(self):
        assert pk.pick_bm(1, jnp.float32) == 8
        assert pk.pick_bm(8, jnp.float32) == 8
        assert pk.pick_bm(16, jnp.float32) == 16
        assert pk.pick_bm(1, jnp.bfloat16) == 16     # bf16 sublane floor
        assert pk.pick_bm(16, jnp.bfloat16) == 16
        assert pk.pick_bm(128, jnp.float32) == 128
        assert pk.pick_bm(4096, jnp.bfloat16) == 128

    def test_padded_macs_ratio_at_decode_shapes(self):
        for M in (1, 4, 8, 16):
            old = pk.padded_macs(M, 2048, 2048)
            new = pk.padded_macs(M, 2048, 2048,
                                 bm=pk.pick_bm(M, jnp.float32))
            assert old / new >= 2.0, (M, old, new)

    @pytest.mark.parametrize("M", [1, 4, 16])
    def test_small_m_tiles_match_ref(self, M):
        rng = np.random.default_rng(M)
        x = jnp.asarray(rng.normal(size=(M, 256)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(256, 192)).astype(np.float32))
        codes, scale = _quant(w, 8)
        bm, bn, bk = pk.pick_tiles(M, 256, 192, x.dtype)
        got = pk.psi_matmul_int8(x, codes, scale, bm=bm, bn=bn, bk=bk,
                                 interpret=True)
        want = ref.psi_matmul_int8_ref(x, codes, scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("K,N", QWEN3_DECODE_GEMMS + QWEN3_2X2_SHARDS)
    def test_pick_tiles_never_pads_the_weight(self, K, N):
        """At every qwen3-8b decode GEMM, one chip's and a 2x2 mesh's local
        N shard, the decode tiles divide K exactly and N either exactly or
        through the ragged last block; each streams a slab of codes well
        inside scoped VMEM."""
        bm, bn, bk = pk.pick_tiles(32, K, N, jnp.bfloat16)
        assert bm == 32
        assert K % bk == 0 and bk % 128 == 0
        assert bn % 128 == 0 and bn <= N
        assert 512 * 1024 <= bk * bn <= pk._DECODE_TILE_BYTES
        assert pk._int8_vmem_bytes(bm, bn, bk, 2) <= pk._SCOPED_VMEM
        jaxpr = jax.make_jaxpr(
            lambda x, c, s: pk.psi_matmul_int8(x, c, s, bm=bm, bn=bn,
                                               bk=bk))(
            jax.ShapeDtypeStruct((32, K), jnp.bfloat16),
            jax.ShapeDtypeStruct((K, N), jnp.int8),
            jax.ShapeDtypeStruct((N,), jnp.float32))
        assert _int8_pads(jaxpr) == []

    def test_untiled_k_pads_the_codes(self):
        """The jaxpr check above sees a pad where there is one: a bk that
        does not divide K pads the codes on K."""
        jaxpr = jax.make_jaxpr(
            lambda x, c, s: pk.psi_matmul_int8(x, c, s, bm=32, bn=128,
                                               bk=128))(
            jax.ShapeDtypeStruct((32, 200), jnp.bfloat16),
            jax.ShapeDtypeStruct((200, 256), jnp.int8),
            jax.ShapeDtypeStruct((256,), jnp.float32))
        assert len(_int8_pads(jaxpr)) == 1

    def test_pick_tiles_lm_head_takes_the_ragged_path(self):
        # 151936 = 128 * 1187 (prime): only 128-wide blocks divide it
        assert pk.pick_tiles(32, 4096, 151936, jnp.bfloat16) == \
            (32, 1024, 1024)
        assert pk.pick_tiles(32, 4096, 75968, jnp.bfloat16) == \
            (32, 1024, 1024)

    @pytest.mark.parametrize("M", [128, 512, 4096])
    @pytest.mark.parametrize("K,N", QWEN3_DECODE_GEMMS)
    def test_pick_tiles_prefill_keeps_square_tiles(self, M, K, N):
        assert pk.pick_tiles(M, K, N, jnp.bfloat16) == (128, 128, 128)

    def test_packed_body_keeps_its_tiles(self, monkeypatch):
        """The packed sub-byte body is dispatched with the M-tile alone:
        its weight tiles stay at 128 x 128."""
        seen = {}

        def spy(x, planes, scale, **kw):
            seen.update(kw)
            return ref.psi_matmul_packed_ref(x, planes, scale, kw["bits"])

        monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
        monkeypatch.setattr(ops._pk, "psi_matmul_packed", spy)
        rng = np.random.default_rng(13)
        x = jnp.asarray(rng.normal(size=(32, 256)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(256, 1024)).astype(np.float32))
        ops.psi_matmul(x, psi.quantize_weights(w, 5, axis=0).pack())
        assert seen == {"bits": 5, "bm": 32, "interpret": True}
        assert (pk.DEFAULT_BN, pk.DEFAULT_BK) == (128, 128)

    @pytest.mark.parametrize("N", [1024, 1408], ids=["exact", "ragged"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_picked_decode_tiles_match_ref(self, N, dtype):
        """The decode tiles at a reduced width: 1024 x 1024 code slabs, two
        K steps, and (N = 1408) a ragged last N block of 384 columns."""
        M, K = 32, 2048
        rng = np.random.default_rng(N)
        x = jnp.asarray(rng.normal(size=(M, K)), dtype)
        w = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32))
        codes, scale = _quant(w, 8)
        bm, bn, bk = pk.pick_tiles(M, K, N, dtype)
        assert (bn, bk) == (1024, 1024)
        got = pk.psi_matmul_int8(x, codes, scale, bm=bm, bn=bn, bk=bk,
                                 interpret=True)
        want = ref.psi_matmul_int8_ref(x, codes, scale)
        assert got.shape == (M, N)
        tol = dict(rtol=1e-5, atol=1e-4) if dtype == jnp.float32 \
            else dict(rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **tol)


class TestGpuFastPath:
    """The dequantize-then-einsum route for non-TPU accelerators must agree
    with the oracle (scale folded into W commutes with the contraction)."""

    def test_int8_dequant_matches_oracle(self):
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.normal(size=(5, 128)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(128, 96)).astype(np.float32))
        codes, scale = _quant(w, 8)
        got = ref.psi_matmul_int8_dequant(x, codes, scale)
        want = ref.psi_matmul_int8_ref(x, codes, scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_int5_dequant_matches_oracle(self):
        rng = np.random.default_rng(8)
        x = jnp.asarray(rng.normal(size=(3, 64)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(64, 40)).astype(np.float32))
        q = psi.quantize_weights(w, 5, axis=0)
        planes = psi.pack_int5(q.codes)
        scale = q.scale.reshape(-1)
        got = ref.psi_matmul_int5_dequant(x, planes, scale)
        want = ref.psi_matmul_int5_ref(x, planes, scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_backend_routing_is_explicit(self, monkeypatch):
        """A gpu backend must route to the dequant fast path, never the
        bit-plane oracle loop or (worse) a silent CPU fall-through."""
        calls = []
        monkeypatch.setattr(ops, "_backend", lambda: "gpu")
        monkeypatch.setattr(
            ops._ref, "psi_matmul_packed_dequant",
            lambda x, p, s, b: calls.append(f"dequant_packed{b}")
            or ref.psi_matmul_packed_ref(x, p, s, b))
        monkeypatch.setattr(
            ops._ref, "psi_matmul_codes_dequant",
            lambda *a: calls.append("dequant_codes")
            or ref.psi_matmul_codes_ref(*a))
        rng = np.random.default_rng(9)
        x = jnp.asarray(rng.normal(size=(2, 64)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(64, 40)).astype(np.float32))
        ops.psi_matmul(x, psi.quantize_weights(w, 5, axis=0).pack())
        ops.psi_matmul(x, psi.quantize_weights(w, 8, axis=0))
        assert calls == ["dequant_packed5", "dequant_codes"]


def test_packed_kernel_every_sub_byte_width():
    """One kernel body serves every registered sub-byte format: the
    interpret-mode Pallas packed kernel matches the oracle for each."""
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(64, 24)).astype(np.float32))
    for bits in psi.registered_bits():
        if not psi.get_format(bits).sub_byte:
            continue
        q = psi.quantize_weights(w, bits, axis=0).pack()
        scale = q.scale.reshape(-1)
        got = pk.psi_matmul_packed(x, q.data, scale, bits=bits,
                                   interpret=True)
        want = ref.psi_matmul_packed_ref(x, q.data, scale, bits)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_kernel_matches_float_matmul_within_quant_error():
    """End-to-end sanity: the PSI kernel approximates the float matmul with
    per-channel-quantization error bounds (not exactness)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(32, 256)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(256, 64)).astype(np.float32))
    codes, scale = _quant(w, 8)
    got = pk.psi_matmul_int8(x, codes, scale, interpret=True)
    rel = float(jnp.linalg.norm(got - x @ w) / jnp.linalg.norm(x @ w))
    assert rel < 0.02
