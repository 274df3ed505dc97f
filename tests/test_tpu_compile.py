"""Compile-only checks for a TPU v5e, no chip needed: the serving path's
Pallas kernels at qwen3-8b's widths, one full-width psi8 decode step, and
the shard_map-wrapped kernels on a 2x2 mesh, each compiled for a described
``v5e:2x2`` topology.  A compile that passes here is not a chip run; it
shows what the chip's compiler would refuse (tiling, VMEM, HBM fit, a
kernel XLA cannot partition) at no chip time.

The topology is described inside a module fixture, never at import: only
the process that runs these tests loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import get_config
from repro.core import psi
from repro.kernels import ops, paged_attention as pa, psi_matmul as pk
from repro.models import build_model

D, FF = 4096, 12288                        # qwen3-8b widths
HQ, HKV, HD = 32, 8, 128
HBM_BYTES = 16e9                          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def restore():
        jax.config.update("jax_enable_compilation_cache", cache_on)
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                # noqa: BLE001 — becomes the skip
        restore()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    try:
        yield desc
    finally:
        restore()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the kernel dispatch to the TPU route (JAX itself sees CPU)."""
    monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
    monkeypatch.delenv("REPRO_PAGED_ATTN", raising=False)
    monkeypatch.setattr(ops, "_backend", lambda: "tpu")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("M,bm", [(8, 16), (128, 128)],
                         ids=["decode", "prefill"])
def test_psi_matmul_codes_compiles(one_chip, M, bm):
    x = _sds((M, D), jnp.bfloat16, one_chip)
    codes = _sds((D, FF), jnp.int8, one_chip)
    scale = _sds((FF,), jnp.float32, one_chip)
    compiled = pk.psi_matmul_codes.lower(x, codes, scale, bm=bm).compile()
    assert _has_kernel(compiled)


def test_psi_matmul_packed_compiles(one_chip):
    x = _sds((16, D), jnp.bfloat16, one_chip)
    planes = _sds((5, D // 8, FF), jnp.uint8, one_chip)
    scale = _sds((FF,), jnp.float32, one_chip)
    compiled = pk.psi_matmul_packed.lower(x, planes, scale, bits=5,
                                          bm=16).compile()
    assert _has_kernel(compiled)


def _paged_operands(sharding, quantized, B=8, N=96, bs=16, n_bt=11):
    pool_dtype = jnp.int8 if quantized else jnp.bfloat16
    ops_ = [_sds((B, HQ, HD), jnp.bfloat16, sharding),
            _sds((N, bs, HKV, HD), pool_dtype, sharding),
            _sds((N, bs, HKV, HD), pool_dtype, sharding),
            _sds((B, n_bt), jnp.int32, sharding),
            _sds((B,), jnp.int32, sharding)]
    if quantized:
        ops_ += [_sds((N, bs, HKV, 1), jnp.float32, sharding)] * 2
    return ops_


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_paged_attention_compiles(one_chip, quantized):
    args = _paged_operands(one_chip, quantized)
    compiled = pa.paged_attention_pallas.lower(*args).compile()
    assert _has_kernel(compiled)


def test_full_width_decode_step_fits_one_chip(one_chip, on_tpu):
    """One psi8 decode step of qwen3-8b at full width (36 layers, paged
    cache, 8 slots, max_seq 512): kernels present, and arguments plus
    temporaries fit a 16 GB chip."""
    cfg = get_config("qwen3-8b")
    model = build_model(cfg)
    B, S = 8, 512
    params = jax.eval_shape(
        lambda k: model.quantize(model.init(k), 8, pack=True),
        jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(
        B, S, dtype=jnp.bfloat16, layout="paged", block_size=16))
    put = lambda t: jax.tree_util.tree_map(                  # noqa: E731
        lambda a: _sds(a.shape, a.dtype, one_chip), t)

    def step(params, token, pos, active, block_table, cache):
        logits, cache = model.decode_step(
            params, {"token": token, "pos": pos, "active": active,
                     "block_table": block_table}, cache)
        return jnp.argmax(logits, -1), cache

    compiled = jax.jit(step, donate_argnums=5).lower(
        put(params), _sds((B, 1), jnp.int32, one_chip),
        _sds((B, 1), jnp.int32, one_chip), _sds((B,), jnp.bool_, one_chip),
        _sds((B, S // 16), jnp.int32, one_chip), put(cache)).compile()
    assert _has_kernel(compiled)
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, used


def test_sharded_psi_matmul_compiles_on_2x2(mesh, on_tpu):
    """XLA cannot partition a Mosaic kernel; under a 2x2 mesh the dispatch
    wraps it in shard_map, and each device holds half the codes."""
    x = _sds((8, D), jnp.bfloat16, NamedSharding(mesh, P("data", None)))
    codes = _sds((D, FF), jnp.int8, NamedSharding(mesh, P(None, "model")))
    scale = _sds((1, FF), jnp.float32, NamedSharding(mesh, P(None, "model")))
    fmt = psi.get_format(8)

    def f(x, data, scale):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return ops.psi_matmul(x, psi.QuantizedTensor(data, scale, fmt))

    compiled = jax.jit(f).lower(x, codes, scale).compile()
    assert _has_kernel(compiled)
    assert compiled.memory_analysis().argument_size_in_bytes < D * FF


def test_sharded_paged_attention_compiles_on_2x2(mesh, on_tpu):
    """The paged-decode kernel per pool shard, merged across the data
    axis: pools split blocks over "data" and KV heads over "model"."""
    pool = NamedSharding(mesh, P("data", None, "model", None))
    rep = NamedSharding(mesh, P())
    args = _paged_operands(rep, quantized=False)
    args[1] = _sds(args[1].shape, args[1].dtype, pool)
    args[2] = _sds(args[2].shape, args[2].dtype, pool)

    def f(*a):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return ops.paged_decode_attention(*a)

    compiled = jax.jit(f).lower(*args).compile()
    assert _has_kernel(compiled)


def _float_partial_sums(compiled):
    """All-reduces that sum floating-point partial results across devices:
    a norm's mean or a contraction split over a mesh axis.  Their order of
    summation is not one device's, so a sharded program with any of them
    cannot give the one-device program's bits."""
    import re
    found = []
    for line in compiled.as_text().splitlines():
        if not re.search(r"= (f32|bf16)\S* all-reduce(-start)?\(", line):
            continue
        meta = re.search(r'op_name="([^"]*)"', line)
        name = meta.group(1) if meta else ""
        if re.search(r"reduce_sum|dot_general", name):
            found.append(name)
    return found


@pytest.fixture(scope="module")
def sharded_executor(topo):
    """The serving Executor on the described 2x2 mesh, for a two-layer
    model wide enough (d_model 1024) that weights split over "model".
    Placement hands out shapes with shardings: nothing can live on a
    described device."""
    import dataclasses
    from repro.configs import reduced_config
    from repro.runtime import executor as exm

    cfg = reduced_config(get_config("qwen3-8b"), dtype="bfloat16",
                         d_model=1024, n_heads=8, n_kv_heads=4,
                         head_dim=128, d_ff=1024, vocab_size=512)
    model = build_model(cfg)
    params = jax.eval_shape(
        lambda k: model.quantize(model.init(k), 8, pack=True),
        jax.random.PRNGKey(0))
    cfg = dataclasses.replace(cfg, quant_mode="psi8")
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    put = lambda x, s=None, **kw: jax.tree_util.tree_map(    # noqa: E731
        lambda a, sh: _sds(a.shape, a.dtype, sh), x, s)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_backend", lambda: "tpu")
        mp.setattr(jax, "device_put", put)
        ex = exm.Executor(cfg, params, max_batch=8, max_seq=64, mesh=mesh)
        yield ex, mesh


@pytest.mark.parametrize("program", ["decode", "prefill_insert",
                                     "prefill_burst"])
def test_sharded_serving_sums_on_one_device(sharded_executor, on_tpu,
                                            program):
    """Every sum of the sharded serving programs runs on one device, in the
    one-device order: GEMMs keep the full contraction on each device and
    gather their split output, so no norm or matmul ends in an all-reduce
    of floating partial sums (the paged-attention merge over "data" and
    the sharded embedding gather add exact zeros only)."""
    ex, mesh = sharded_executor
    B, rep = 8, NamedSharding(mesh, P())
    sh = ex._step_shardings
    cache = jax.eval_shape(ex._init_cache_fn)
    cache = jax.tree_util.tree_map(lambda a, s: _sds(a.shape, a.dtype, s),
                                   cache, ex.cache_shardings)
    if program == "decode":
        lowered = ex._decode.lower(
            ex.params, _sds((B, 1), jnp.int32, sh["token"]),
            _sds((B, 1), jnp.int32, sh["pos"]),
            _sds((B,), jnp.bool_, sh["active"]),
            _sds((B, ex.n_bt), jnp.int32, sh["block_table"]), cache)
    elif program == "prefill_insert":
        lowered = ex._prefill_insert.lower(
            ex.params, _sds((1, 32), jnp.int32, rep),
            _sds((1,), jnp.int32, rep), cache, _sds((), jnp.int32, rep),
            _sds((ex.n_bt,), jnp.int32, rep))
    else:
        lowered = ex._prefill.lower(ex.params, _sds((B, 32), jnp.int32, rep),
                                    _sds((B,), jnp.int32, rep))
    compiled = lowered.compile()
    assert _has_kernel(compiled)
    assert _float_partial_sums(compiled) == []
