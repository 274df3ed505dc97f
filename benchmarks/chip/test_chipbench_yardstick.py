"""CPU checks of the benchmark's yardstick: trace reduction, work counts,
the peaks table and the checkpoint generator."""
import json
import pathlib

import jax
import numpy as np
import pytest

from chipbench import readers, trace, weights, work

HERE = pathlib.Path(__file__).resolve().parent

# One chip's ops line, as the TPU trace names ops (their HLO text): a layer
# loop 0-3 us holding a matmul kernel 0-2 us and a copy 1-3 us, then a
# paged-attention kernel 5-6 us; programs: decode 0-3, prefill 5-6.
XSPACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 6 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 5 offset_ps: 5000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name:
    "%psi_matmul_int8.7 = bf16[8,64]{1,0:T(8,128)} custom-call(bf16[8,32] %x)" } }
  event_metadata { key: 2 value { id: 2 name:
    "%copy.2 = bf16[4,16]{1,0} copy(bf16[4,16]{1,0} %y)" } }
  event_metadata { key: 3 value { id: 3 name:
    "%paged_attention_pallas.9 = bf16[8,4,16]{2,1,0} custom-call(s32[8] %t)" } }
  event_metadata { key: 4 value { id: 4 name: "jit__decode_fn_paged(12)" } }
  event_metadata { key: 5 value { id: 5 name: "jit__prefill_fn(9)" } }
  event_metadata { key: 6 value { id: 6 name:
    "%while.5 = (s32[], bf16[8,64]) while((s32[], bf16[8,64]) %t)" } }
}
planes { id: 2 name: "/host:CPU" }
'''


@pytest.fixture(scope="module")
def devices():
    return trace.devices_from_profile(
        jax.profiler.ProfileData.from_text_proto(XSPACE))


def _conf(name):
    with open(HERE / "configs" / f"{name}.json") as f:
        return json.load(f)


def test_union_merges_overlaps():
    assert trace.union_ns([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace.union_ns([(5, 6), (0, 1)]) == 2
    assert trace.union_ns([]) == 0


def test_reduction_of_a_recorded_trace(devices):
    assert [d.name for d in devices] == ["/device:TPU:0"]
    red = trace.reduce(devices, window_s=10e-6)
    assert red.busy_s == pytest.approx(4e-6)
    assert red.idle_share == pytest.approx(0.6)
    # kernels are found by their op names; the layer loop holding them is
    # in the busy union but not among the ops
    assert trace.kernel_s(devices[0], readers.PSI_MATMUL) == \
        pytest.approx(2e-6)
    assert trace.kernel_s(devices[0], readers.PAGED_ATTENTION) == \
        pytest.approx(1e-6)
    assert red.device_ops == [["%psi_matmul_int8.7 = bf16[8,64]",
                               pytest.approx(2e-6)],
                              ["%copy.2 = bf16[4,16]", pytest.approx(2e-6)],
                              ["%paged_attention_pallas.9 = bf16[8,4,16]",
                               pytest.approx(1e-6)]]
    assert red.idle_gaps == [["_decode_fn_paged -> _prefill_fn",
                              pytest.approx(2e-6)]]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce([], 1.0)


@pytest.mark.parametrize("name,nbytes", [("qwen3-8b-psi8", 8_198_478_848),
                                         ("phi3-medium-14b-psi5",
                                          8_796_969_472)])
def test_served_bytes_equal_the_count_at_format_width(name, nbytes):
    """The tree the benchmark serves holds exactly the bytes the roofline
    counts: codes at format width, scales, norms."""
    from repro.core import psi
    conf = _conf(name)
    arch = work.Arch.from_config(conf)
    assert arch.served_weight_bytes() == nbytes
    tree = jax.eval_shape(lambda: weights.served_tree(
        0, arch, conf, psi.get_format(arch.bits), psi.QuantizedTensor))
    assert sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree)) == nbytes


def test_decode_step_weight_read_of_qwen3():
    """One decode step's psi_matmul calls read every GEMM code once at
    8 bits: 36 layers plus the lm_head."""
    arch = work.Arch.from_config(_conf("qwen3-8b-psi8"))
    per_step = sum(arch.n_layers * K * N for _, K, N in arch.layer_gemms())
    per_step += arch.d_model * arch.vocab_size
    assert per_step == 8_190_427_136 - arch.vocab_size * arch.d_model


def test_unknown_device_kind_is_an_error():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        work.peaks("TPU v99")


def test_roofline_least_time_takes_the_larger_bound():
    peak = {"int8_ops": 100.0, "bf16_flops": 50.0, "hbm_bytes_per_s": 10.0}
    arch = work.Arch(1, 8, 2, 1, 4, 16, 32, 8)
    # one decode row: memory-bound
    ops, nbytes = work.psi_matmul_call(1, 8, 16, 8)
    assert ops == 256 and nbytes == 8 * 16 + 16 * 4 + 8 * 2 + 16 * 2
    t = work.psi_matmul_least_time(arch, [("decode", 1)], peak)
    want = sum(max(2 * K * N / 100.0,
                   (K * N + 4 * N + 2 * K + 2 * N) / 10.0)
               for _, K, N in arch.layer_gemms())
    want += max(2 * 8 * 32 / 100.0, (8 * 32 + 4 * 32 + 16 + 64) / 10.0)
    assert t == pytest.approx(want)
    flops, nb = work.paged_attention_step(arch, [10])
    assert flops == 4 * 10 * 2 * 4 and nb == 2 * 10 * 1 * 4 * 2 + 2 * 2 * 4 * 2


@pytest.mark.parametrize("bits,terms,missing", [(5, 2, {11, 13, -11, -13}),
                                                (8, 4, set())])
def test_codes_lie_in_the_representable_set(bits, terms, missing):
    rep = set(weights.representable(bits, terms).tolist())
    full = set(range(-(1 << (bits - 1)), 1 << (bits - 1)))
    assert full - rep == missing
    codes = weights.draw_codes(jax.random.PRNGKey(1), (64, 64), bits,
                               weights.projection_table(bits, terms))
    assert set(np.unique(np.asarray(codes)).tolist()) <= rep


def test_a_layer_drawn_alone_equals_the_served_stack():
    """The reference draws layer l again alone; it must be the served
    layer bit for bit."""
    from repro.core import psi
    conf = {"psi_terms": 2, "qk_norm": True}
    arch = work.Arch(2, 16, 2, 1, 8, 32, 24, 5, True)
    tree = weights.served_tree(3, arch, conf, psi.get_format(5),
                               psi.QuantizedTensor)
    base = weights.seed_key(3)
    table = weights.table_for(arch, conf)
    for l in range(arch.n_layers):
        w = weights.layer(base, arch, conf, l, table)
        served = jax.tree_util.tree_map(
            lambda a, l=l: a[l], tree["stack"]["groups"]["b0_attn"])
        codes, scale = w["w_up"]
        assert np.array_equal(np.asarray(served["mlp"]["w_up"].codes),
                              np.asarray(codes))
        assert np.array_equal(np.asarray(served["mlp"]["w_up"].scale)[0],
                              np.asarray(scale))
        assert np.array_equal(np.asarray(served["attn"]["q_norm_scale"]),
                              np.asarray(w["q_norm"]))


def test_seeds_past_32_bits_give_distinct_keys():
    a = weights.seed_key(5)
    b = weights.seed_key(5 + (1 << 32))
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_every_seed_gets_the_same_schedule():
    """Lengths, budgets and arrivals are fixed by the mix; the seed draws
    only the tokens."""
    from chipbench import traffic
    with open(HERE / "traffic" / "chat-sat.json") as f:
        mix = json.load(f)
    a = traffic.generate(mix, 1.0, 30, 5, 1000)
    b = traffic.generate(mix, 1.0, 30, 2 ** 33 + 5, 1000)
    assert len(a) == 30
    assert [(r["arrival_s"], r["max_new"], len(r["prompt"])) for r in a] == \
        [(r["arrival_s"], r["max_new"], len(r["prompt"])) for r in b]
    assert any((x["prompt"] != y["prompt"]).any() for x, y in zip(a, b))
    assert sorted(len(r["prompt"]) for r in a) == \
        [128] * 12 + [256] * 9 + [512] * 6 + [1024] * 3
    assert a[0]["arrival_s"] == 0.0 and a[-1]["arrival_s"] < 30
