"""First-class quantized-weight API: mixed-precision policies, the typed
QuantizedTensor serving path (embedding gather included), and checkpoint
round-trips.  (Format-registry property tests live in test_psi.py; kernel
dispatch tests in test_kernels.py.)"""
import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import psi, quantizer
from repro.quant import embed, linear, tied_logits


def _rand(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape)
                       .astype(np.float32))


class TestPolicy:
    def test_parse_policy_string(self):
        p = quantizer.parse_policy("embed=8, w_down=4, default=5")
        assert p == {"embed": 8, "w_down": 4, "default": 5}
        with pytest.raises(ValueError):
            quantizer.parse_policy("embed=9,default=5")   # unregistered width
        with pytest.raises(ValueError):
            quantizer.parse_policy("embed:8")
        with pytest.raises(ValueError):
            quantizer.parse_policy("w(=5")        # malformed regex name

    def test_policy_assigns_per_leaf_formats(self):
        params = {"embed": _rand((32, 16)),
                  "stack": {"wq": _rand((16, 16), 1),
                            "w_down": _rand((16, 16), 2),
                            "norm": jnp.ones((16,))}}
        qp = quantizer.quantize_param_tree(
            params, policy={"embed": 8, "w_down": 4, "default": 5},
            pack=True)
        assert qp["embed"].fmt.bits == 8 and not qp["embed"].packed
        assert qp["stack"]["wq"].fmt.bits == 5 and qp["stack"]["wq"].packed
        assert qp["stack"]["w_down"].fmt.bits == 4
        assert not isinstance(qp["stack"]["norm"], psi.QuantizedTensor)

    def test_policy_zero_bits_keeps_float(self):
        params = {"wq": _rand((16, 16)), "w_up": _rand((16, 16), 1)}
        qp = quantizer.quantize_param_tree(
            params, policy={"wq": 0, "default": 5})
        assert not isinstance(qp["wq"], psi.QuantizedTensor)
        assert qp["w_up"].fmt.bits == 5

    def test_policy_typo_warns(self):
        """A policy key matching no leaf at all must warn loudly — a typo'd
        layer name silently dropping to default precision is the failure
        mixed precision exists to avoid."""
        import warnings
        params = {"embed": _rand((16, 8))}
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            quantizer.quantize_param_tree(
                params, policy={"embd": 8, "default": 5})   # typo
        assert any("matched no parameter leaf" in str(x.message) for x in w)

    def test_policy_on_excluded_leaf_does_not_warn(self):
        """A deliberate entry for an excluded (non-quantizable) leaf like
        the MoE router is intent, not a typo — no warning."""
        import warnings
        params = {"router": _rand((16, 4)), "wq": _rand((16, 16), 1)}
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            qp = quantizer.quantize_param_tree(
                params, policy={"router": 0, "default": 5})
        assert not any("matched no parameter leaf" in str(x.message)
                       for x in w)
        assert not isinstance(qp["router"], psi.QuantizedTensor)

    def test_policy_nonzero_bits_on_excluded_leaf_warns(self):
        """router=8 contradicts the exclude list (the router never
        quantizes) — that silent no-op must warn."""
        import warnings
        params = {"router": _rand((16, 4)), "wq": _rand((16, 16), 1)}
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            quantizer.quantize_param_tree(
                params, policy={"router": 8, "default": 5})
        assert any("have no effect" in str(x.message) for x in w)

    def test_uniform_bits_still_works(self):
        qp = quantizer.quantize_param_tree({"wq": _rand((16, 8))}, 8)
        assert qp["wq"].fmt.bits == 8

    def test_no_bits_no_policy_raises(self):
        with pytest.raises(ValueError):
            quantizer.quantize_param_tree({"wq": _rand((16, 8))})


class TestServingPaths:
    def test_packed_embedding_lookup_regression(self):
        """A packed (bit-plane) embedding leaf must serve lookups — the old
        dict path read wleaf["codes"] unconditionally and raised KeyError."""
        table = _rand((64, 16))
        q = psi.quantize_weights(table, 5, axis=1)     # per-row scales
        qp = q.pack()
        ids = jnp.asarray([[0, 7, 63], [8, 9, 10]])
        got = embed(qp, ids, jnp.float32)
        want = embed(q, ids, jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(q.dequantize(jnp.float32)[ids]),
            rtol=1e-6, atol=1e-6)

    def test_mixed_precision_embed_matches_uniform_psi8(self):
        """Acceptance: policy {"embed": 8, "default": 5} is token-identical
        to uniform psi8 on the embedding path (same format -> same codes)."""
        params = {"embed": _rand((128, 32)), "wq": _rand((32, 32), 1)}
        mixed = quantizer.quantize_param_tree(
            params, policy={"embed": 8, "default": 5}, pack=True)
        uni8 = quantizer.quantize_param_tree(params, 8)
        ids = jnp.asarray(np.random.default_rng(3).integers(0, 128, (4, 9)))
        np.testing.assert_array_equal(
            np.asarray(embed(mixed["embed"], ids, jnp.float32)),
            np.asarray(embed(uni8["embed"], ids, jnp.float32)))
        # the tied-logits head reads the same table: identical logits too
        x = _rand((4, 32), 5)
        np.testing.assert_array_equal(
            np.asarray(tied_logits(mixed["embed"], x)),
            np.asarray(tied_logits(uni8["embed"], x)))
        # while the 5-bit leaf actually changed format
        assert mixed["wq"].fmt.bits == 5 and uni8["wq"].fmt.bits == 8

    def test_linear_matches_dequantized_einsum(self):
        w = _rand((64, 24), 2)
        x = _rand((3, 64), 4)
        for bits in (4, 5, 8):
            q = psi.quantize_weights(w, bits, axis=0)
            for leaf in (q,) + ((q.pack(),) if q.fmt.sub_byte else ()):
                got = linear(leaf, x)
                want = x @ quantizer.dequantize(leaf, jnp.float32)
                np.testing.assert_allclose(np.asarray(got, np.float32),
                                           np.asarray(want, np.float32),
                                           rtol=2e-2, atol=2e-2)

    def test_shared_dequantize_passthrough(self):
        w = _rand((8, 8))
        assert quantizer.dequantize(w) is w


class TestCheckpointRoundtrip:
    def test_quantized_tree_survives_save_load(self):
        from repro.checkpoint.manager import CheckpointManager
        params = {"embed": _rand((32, 16)),
                  "stack": {"wq": _rand((16, 16), 1), "b": jnp.zeros((16,))}}
        qp = quantizer.quantize_param_tree(
            params, policy={"embed": 8, "default": 4}, pack=True)
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(1, qp)
            tree, _ = mgr.restore(1)
        assert (jax.tree_util.tree_structure(tree)
                == jax.tree_util.tree_structure(qp))
        got = tree["stack"]["wq"]
        assert isinstance(got, psi.QuantizedTensor)
        assert got.fmt == qp["stack"]["wq"].fmt and got.packed
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(qp)):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_custom_format_survives_save_load(self):
        """A non-default term budget (register_format(5, n_psi=3) is exact)
        must restore with ITS format, not the registry default's."""
        from repro.checkpoint.manager import CheckpointManager
        fmt3 = psi.make_format(5, n_psi=3)
        assert fmt3.exact                      # 3 terms cover all of INT5
        w = _rand((16, 8))
        scale = psi.compute_scale(w, fmt3, (0,))
        codes = jnp.clip(jnp.round(w / scale), fmt3.w_min,
                         fmt3.w_max).astype(jnp.int8)
        qt = psi.QuantizedTensor(codes, scale.astype(jnp.float32), fmt3)
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(1, {"wq": qt})
            tree, _ = mgr.restore(1)
        got = tree["wq"].fmt
        assert got == fmt3 and got.n_psi == 3 and got.exact


class TestUnpackRowsGuard:
    def test_stacked_packed_table_rejected(self):
        """unpack_rows on a stacked (L, bits, K//8, N) table must raise, not
        silently gather garbage with the plane index applied to the L dim."""
        codes = jnp.asarray(np.random.default_rng(0).integers(
            -16, 16, size=(2, 16, 8)).astype(np.int8))
        packed = psi.pack_codes(codes, 5)        # (2, 5, 2, 8)
        with pytest.raises(ValueError):
            psi.unpack_rows(packed, jnp.asarray([0, 1]), 5)


class TestSubByteServing:
    def test_psi4_serves_token_stably(self):
        """Acceptance: an INT4 policy serves end-to-end through the slot
        engine on the reduced qwen3-8b config, token-identical between
        static and continuous scheduling."""
        from types import SimpleNamespace
        from repro.launch.serve import build_server, trace_from_args
        args = SimpleNamespace(
            arch="qwen3-8b", reduced=True, quant="psi4", quant_policy=None,
            requests=4, max_batch=2, arrival_rate=1000.0, max_new=6,
            min_new=2, prompt_len=12, prompt_jitter=0, eos_id=-1, seed=0,
            mesh=None)
        server, cfg = build_server(args)
        done_s, _ = server.serve(trace_from_args(args, cfg), continuous=False)
        done_c, stats = server.serve(trace_from_args(args, cfg),
                                     continuous=True, warmup=False)
        for rs, rc in zip(sorted(done_s, key=lambda r: r.rid),
                          sorted(done_c, key=lambda r: r.rid)):
            assert rs.tokens == rc.tokens
        assert stats["tokens"] > 0

    def test_quant_policy_cli_flag_builds(self):
        """--quant-policy threads from the CLI into per-leaf formats."""
        from types import SimpleNamespace
        from repro.launch.serve import build_server
        args = SimpleNamespace(
            arch="qwen3-8b", reduced=True, quant="none",
            quant_policy="embed=8,default=5", requests=1, max_batch=2,
            arrival_rate=1000.0, max_new=4, min_new=1, prompt_len=12,
            prompt_jitter=0, eos_id=-1, seed=0, mesh=None)
        server, cfg = build_server(args)
        p = server.executor.params
        assert p["embed"].fmt.bits == 8
        stack_wq = p["stack"]["groups"]["b0_attn"]["attn"]["wq"]
        assert stack_wq.fmt.bits == 5 and stack_wq.packed
        assert cfg.quant_mode == "psi5"


class TestLayerwiseServingParams:
    """``Model.init_serving`` builds the serving tree a layer at a time,
    never holding the float tree, and must equal the whole-tree path."""

    @pytest.mark.parametrize("arch", ["qwen3-8b", "qwen3-moe-30b-a3b"])
    def test_equals_quantize_of_init(self, arch):
        from repro.configs import get_config, reduced_config
        from repro.models import build_model
        model = build_model(reduced_config(get_config(arch)))
        key = jax.random.PRNGKey(0)
        want = model.quantize(model.init(key), 8, pack=True)
        got = model.init_serving(key, 8, pack=True)
        is_qt = lambda x: isinstance(x, psi.QuantizedTensor)  # noqa: E731
        assert (jax.tree_util.tree_structure(got, is_leaf=is_qt)
                == jax.tree_util.tree_structure(want, is_leaf=is_qt))
        for (path, a), b in zip(
                jax.tree_util.tree_leaves_with_path(got),
                jax.tree_util.tree_leaves(want)):
            assert a.dtype == b.dtype and a.shape == b.shape, path
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=jax.tree_util.keystr(path))

    def test_policy_and_precondition_match(self):
        """Mixed precision (packed sub-byte leaves) over a QAT-
        preconditioned checkpoint: still the whole-tree result exactly."""
        from repro.configs import get_config, reduced_config
        from repro.core.quantizer import fake_quant_param_tree
        from repro.models import build_model
        model = build_model(reduced_config(get_config("qwen3-8b")))
        key = jax.random.PRNGKey(1)
        pol = "embed=8,w_down=4,default=5"
        want = model.quantize(fake_quant_param_tree(model.init(key), 3),
                              None, pack=True, policy=pol)
        got = model.init_serving(key, None, pack=True, policy=pol,
                                 precondition=3)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
