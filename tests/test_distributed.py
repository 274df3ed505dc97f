"""Distribution-layer tests that need multiple devices: run in a SUBPROCESS
with a forced CPU device count so the main test session keeps 1 device
(the dry-run flag must never leak into other tests)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(snippet: str, devices: int = 8, timeout: int = 480) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(snippet)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """Loss of a jit train step on a (2, 4) data x model mesh == 1-device."""
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, reduced_config
        from repro.models import build_model
        from repro.runtime import sharding as shr
        from repro.launch.mesh import make_mesh

        cfg = reduced_config(get_config("qwen3-8b"))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)}
        l1 = float(model.loss(params, batch)[0])

        mesh = make_mesh((2, 4), ("data", "model"))
        pspecs = shr.param_specs(params, cfg, mesh, mode="train")
        with mesh:
            psh = shr.to_shardings(pspecs, mesh)
            bsh = shr.to_shardings(shr.batch_specs(cfg, mesh, batch), mesh)
            pp = jax.device_put(params, psh)
            bb = jax.device_put(batch, bsh)
            l2 = float(jax.jit(lambda p, b: model.loss(p, b)[0],
                               in_shardings=(psh, bsh))(pp, bb))
        print("LOSSES", l1, l2)
        assert abs(l1 - l2) < 5e-3, (l1, l2)
    """)
    assert "LOSSES" in out


def test_psi_serving_sharded_matches_single_device():
    out = _run("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.configs import get_config, reduced_config
        from repro.models import build_model
        from repro.runtime import sharding as shr
        from repro.launch.mesh import make_mesh

        cfg = reduced_config(get_config("chatglm3-6b"), quant_mode="psi8")
        model = build_model(cfg)
        p32 = build_model(dataclasses.replace(cfg, quant_mode="none")).init(
            jax.random.PRNGKey(0))
        qp = model.quantize(p32, 8)
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size)}
        ref, _, _, _ = model.forward(qp, batch)

        mesh = make_mesh((2, 4), ("data", "model"))
        with mesh:
            psh = shr.to_shardings(
                shr.param_specs(qp, cfg, mesh, mode="serve"), mesh)
            pp = jax.device_put(qp, psh)
            got, _, _, _ = jax.jit(model.forward)(pp, batch)
        import numpy as np
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=2e-3, atol=2e-3)
        print("OK")
    """)
    assert "OK" in out


def test_gpipe_pipeline_matches_sequential():
    """GPipe microbatch rotation over a 4-stage mesh == sequential apply."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.runtime.pipeline_par import (pipeline_apply,
                                                pipeline_bubble_fraction)

        L, M, mb, d = 8, 6, 4, 16
        key = jax.random.PRNGKey(0)
        ws = jax.random.normal(key, (L, d, d)) * 0.2
        xs = jax.random.normal(jax.random.fold_in(key, 1), (M, mb, d))

        def layer_fn(w, x):
            return jnp.tanh(x @ w)

        seq = xs
        for i in range(L):
            seq = jax.vmap(lambda x: layer_fn(ws[i], x))(seq)

        mesh = make_mesh((4,), ("stage",))
        got = pipeline_apply(layer_fn, ws, xs, mesh, stage_axis="stage")
        np.testing.assert_allclose(np.asarray(got), np.asarray(seq),
                                   rtol=1e-5, atol=1e-5)
        assert abs(pipeline_bubble_fraction(6, 4) - 3/9) < 1e-9
        print("OK")
    """)
    assert "OK" in out


def test_elastic_restart_resharded():
    """Checkpoint on an 8-device mesh, restore onto a 4-device mesh."""
    out = _run("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        from repro.launch.mesh import make_mesh
        from repro.runtime.elastic import plan_remesh, make_mesh_from_plan

        d = tempfile.mkdtemp()
        mesh8 = make_mesh((2, 4), ("data", "model"))
        w = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                           NamedSharding(mesh8, P("data", "model")))
        mgr = CheckpointManager(d)
        mgr.save(1, {"w": w}, extra={"step": 1})

        plan = plan_remesh(4, model_parallel=2)
        mesh4 = make_mesh_from_plan(plan)
        sh = NamedSharding(mesh4, P("data", "model"))
        got, extra = mgr.restore(shardings={"w": sh})
        assert got["w"].sharding == sh
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.arange(64.0).reshape(8, 8))
        print("OK", extra["step"])
    """)
    assert "OK 1" in out


def test_executor_prefill_decode_matches_single_device():
    """Executor on a (4, 2) data x model mesh: prefill logits and a decode
    step must match the 1-device Executor (allclose at the serving dtype)
    for BOTH qat-float and PSI-packed (bit-plane) params."""
    out = _run("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced_config
        from repro.launch.mesh import make_mesh
        from repro.models import build_model
        from repro.runtime.executor import Executor

        # dense layout pinned: this test exercises the slot-slab machinery
        # (insert_burst row writes); the paged twin lives in
        # test_paged_serving_sharded_matches_dense_single_device
        base = reduced_config(get_config("qwen3-8b"), cache_layout="dense")
        model = build_model(base)
        p32 = model.init(jax.random.PRNGKey(0))
        flavors = {
            "qat-float": (dataclasses.replace(base, quant_mode="qat8"), p32),
            "psi-packed": (dataclasses.replace(base, quant_mode="psi5"),
                           model.quantize(p32, 5, pack=True)),
        }
        mesh8 = make_mesh((4, 2), ("data", "model"))
        toks = np.asarray(
            jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                               base.vocab_size), np.int32)
        tl = np.full((4,), 16, np.int32)
        for name, (cfg, params) in flavors.items():
            mdl = build_model(cfg)
            ex1 = Executor(cfg, params, max_batch=4, max_seq=32)
            ex8 = Executor(cfg, params, max_batch=4, max_seq=32, mesh=mesh8)
            assert ex8.n_slot_shards == 4, ex8.n_slot_shards
            # raw prefill logits: sharded == single-device (f32 on CPU)
            lg1, _ = jax.jit(mdl.prefill)(ex1.params,
                                          {"tokens": jnp.asarray(toks)})
            lg8, _ = jax.jit(mdl.prefill)(ex8.params,
                                          {"tokens": jnp.asarray(toks)})
            np.testing.assert_allclose(np.asarray(lg1, np.float32),
                                       np.asarray(lg8, np.float32),
                                       rtol=2e-3, atol=2e-3)
            f1, c1 = ex1.prefill(toks, tl)
            f8, c8 = ex8.prefill(toks, tl)
            np.testing.assert_array_equal(np.asarray(f1), np.asarray(f8))
            # one decode step from the prefilled state, all slots active
            cache1, cache8 = ex1.init_cache(), ex8.init_cache()
            slots = np.arange(4, dtype=np.int32)
            cache1 = ex1.insert_burst(cache1, c1, slots, np.ones(4, bool))
            cache8 = ex8.insert_burst(cache8, c8, slots, np.ones(4, bool))
            tok = np.asarray(f1).reshape(4, 1)
            pos = np.full((4, 1), 16, np.int32)
            act = np.ones((4,), bool)
            t1, _ = ex1.decode(tok, pos, act, cache1)
            t8, _ = ex8.decode(tok, pos, act, cache8)
            np.testing.assert_array_equal(np.asarray(t1), np.asarray(t8))
            print("OK", name)
    """)
    assert "OK qat-float" in out and "OK psi-packed" in out


def test_sharded_serving_tokens_identical():
    """Full serve loop on a forced 8-device (4, 2) mesh: slots partition
    over the data axis and every request's token stream is identical to the
    single-device engine (greedy decode; scheduling/sharding may change
    *where* work runs, never the tokens)."""
    out = _run("""
        import dataclasses, jax, numpy as np
        from repro.configs import get_config, reduced_config
        from repro.launch.mesh import make_mesh
        from repro.launch.scheduler import Request
        from repro.launch.serve import Server
        from repro.models import build_model

        cfg = reduced_config(get_config("qwen3-8b"))
        model = build_model(cfg)
        params = model.quantize(model.init(jax.random.PRNGKey(0)), 8)
        cfg = dataclasses.replace(cfg, quant_mode="psi8")
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, size=(8,))
                   .astype(np.int32) for _ in range(6)]
        def mk():
            return [Request(rid=i, prompt=prompts[i], max_new=mn,
                            arrival_s=0.0)
                    for i, mn in enumerate([3, 7, 2, 5, 4, 6])]

        s1 = Server(cfg, params, max_batch=4, max_seq=64)
        d1, st1 = s1.serve(mk(), continuous=True)
        s8 = Server(cfg, params, max_batch=4, max_seq=64,
                    mesh=make_mesh((4, 2), ("data", "model")))
        d8, st8 = s8.serve(mk(), continuous=True)
        assert st1["slot_shards"] == 1 and st8["slot_shards"] == 4
        assert st8["decode_compiles"] == 1, st8["decode_compiles"]
        t1 = {r.rid: r.tokens for r in d1}
        t8 = {r.rid: r.tokens for r in d8}
        assert t1 == t8, (t1, t8)
        # slots really spread over the data axis: the first max_batch
        # admissions land one per shard
        shards = {s8.executor.slot_shards[r.slot]
                  for r in d8 if r.rid < 4}
        assert shards == {0, 1, 2, 3}, shards
        print("OK", st8["slot_shards"])
    """)
    assert "OK 4" in out


def test_paged_serving_sharded_matches_dense_single_device():
    """Acceptance: the paged layout on a forced 8-device (4, 2) mesh —
    block pools sharded block-over-data, block tables as decode-step inputs
    — produces token streams identical to BOTH the single-device dense
    engine and the sharded dense engine, in continuous and static modes,
    with the decode step compiling exactly once per server."""
    out = _run("""
        import dataclasses, jax, numpy as np
        from repro.configs import get_config, reduced_config
        from repro.launch.mesh import make_mesh
        from repro.launch.scheduler import Request
        from repro.launch.serve import Server
        from repro.models import build_model

        cfg = reduced_config(get_config("qwen3-8b"))
        model = build_model(cfg)
        params = model.quantize(model.init(jax.random.PRNGKey(0)), 8)
        cfg = dataclasses.replace(cfg, quant_mode="psi8")
        assert cfg.resolved_cache_layout == "paged"
        dense_cfg = dataclasses.replace(cfg, cache_layout="dense")
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, size=(5 + 3 * i,))
                   .astype(np.int32) for i in range(6)]
        def mk():
            return [Request(rid=i, prompt=prompts[i], max_new=mn,
                            arrival_s=0.0)
                    for i, mn in enumerate([3, 7, 2, 5, 4, 6])]
        toks = lambda done: {r.rid: tuple(r.tokens) for r in done}

        ref = Server(dense_cfg, params, max_batch=4, max_seq=64)
        t_ref = toks(ref.serve(mk(), continuous=True)[0])

        mesh = make_mesh((4, 2), ("data", "model"))
        sd = Server(dense_cfg, params, max_batch=4, max_seq=64, mesh=mesh)
        d_d, st_d = sd.serve(mk(), continuous=True)
        sp = Server(cfg, params, max_batch=4, max_seq=64,
                    mesh=make_mesh((4, 2), ("data", "model")))
        ex = sp.executor
        assert ex.paged and ex.n_slot_shards == 4
        assert ex.n_block_shards == 4, ex.n_block_shards
        # block->shard map follows GSPMD chunking of the full pool dim
        assert len(ex.block_shards) == ex.n_blocks
        d_pc, st_pc = sp.serve(mk(), continuous=True)
        d_ps, st_ps = sp.serve(mk(), continuous=False)
        assert toks(d_d) == t_ref
        assert toks(d_pc) == t_ref and toks(d_ps) == t_ref
        assert st_pc["decode_compiles"] == 1, st_pc["decode_compiles"]
        assert st_d["decode_compiles"] == 1
        assert st_pc["cache_layout"] == "paged"
        assert st_pc["blocks_free_end"] == st_pc["n_blocks"]
        print("OK", st_pc["slot_shards"], st_pc["n_blocks"])
    """)
    assert "OK 4" in out


def test_paged_kernel_no_recompile_on_mesh():
    """(4,2)-mesh twin of test_serving's table-content stability test: the
    routed paged-decode kernel path with the block pool sharded
    block-over-data still compiles the decode step exactly once across
    steps whose block tables differ only in content (fresh / permuted /
    freed / reused-with-holes)."""
    out = _run("""
        import dataclasses, jax, numpy as np
        from repro.configs import get_config, reduced_config
        from repro.launch.mesh import make_mesh
        from repro.models import build_model
        from repro.runtime.executor import Executor

        cfg = reduced_config(get_config("qwen3-8b"))
        model = build_model(cfg)
        params = model.quantize(model.init(jax.random.PRNGKey(0)), 8)
        cfg = dataclasses.replace(cfg, quant_mode="psi8")
        mesh = make_mesh((4, 2), ("data", "model"))
        ex = Executor(cfg, params, max_batch=4, max_seq=64, mesh=mesh)
        assert ex.paged and ex.paged_attn_route == "ref", ex.paged_attn_route
        B, n_bt = ex.max_batch, ex.n_bt
        cache = ex.init_cache()
        tok = np.zeros((B, 1), np.int32)
        pos = np.ones((B, 1), np.int32)
        act = np.ones((B,), bool)
        tables = [
            np.arange(B * n_bt, dtype=np.int32).reshape(B, n_bt),
            np.arange(B * n_bt, dtype=np.int32)[::-1].reshape(B, n_bt),
            np.full((B, n_bt), -1, np.int32),
            np.roll(np.arange(B * n_bt, dtype=np.int32), 5).reshape(B, n_bt),
        ]
        tables[3][:, -1] = -1
        for bt in tables:
            _, cache = ex.decode(tok, pos, act, cache, block_table=bt)
        assert ex.decode_cache_size() == 1, ex.decode_cache_size()
        print("OK", ex.decode_cache_size())
    """)
    assert "OK 1" in out


def test_executor_elastic_remesh_and_straggler_noop():
    """The executor's elastic hooks: from_devices sizes the mesh with
    plan_remesh, remesh() is a no-op when the plan matches, and the
    straggler monitor is None (no-op) on a single-process run."""
    out = _run("""
        import dataclasses, jax, numpy as np
        from repro.configs import get_config, reduced_config
        from repro.models import build_model
        from repro.runtime.executor import Executor

        cfg = reduced_config(get_config("qwen3-8b"))
        model = build_model(cfg)
        params = model.quantize(model.init(jax.random.PRNGKey(0)), 8)
        cfg = dataclasses.replace(cfg, quant_mode="psi8")

        ex = Executor.from_devices(cfg, params, max_batch=4, max_seq=32,
                                   model_parallel=2)
        assert dict(ex.mesh.shape) == {"data": 4, "model": 2}, ex.mesh.shape
        assert ex.remesh() is ex                      # plan matches: no-op
        ex4 = ex.remesh(devices=jax.devices()[:4])    # shrink data axis
        assert dict(ex4.mesh.shape) == {"data": 2, "model": 2}
        # same count but a swapped device (hot spare replacing a dead
        # chip): the plan shape matches yet remesh MUST rebuild
        ex_sw = ex4.remesh(devices=jax.devices()[4:8])
        assert ex_sw is not ex4
        assert dict(ex_sw.mesh.shape) == {"data": 2, "model": 2}
        assert ex.observe_step([1.0]) is None         # single-process no-op

        ex1 = Executor.from_devices(cfg, params, max_batch=4, max_seq=32,
                                    devices=jax.devices()[:1])
        assert dict(ex1.mesh.shape) == {"data": 1, "model": 1}
        assert ex1.n_slot_shards == 1 and ex1.monitor is None
        print("OK")
    """)
    assert "OK" in out


def test_dryrun_entry_on_tiny_mesh():
    """The dry-run machinery itself (build_step -> lower -> compile ->
    roofline report) on an 8-device mesh with a reduced arch."""
    out = _run("""
        import jax, numpy as np
        from repro.launch import dryrun as dr
        from repro.launch.mesh import make_mesh
        import repro.launch.dryrun  # noqa
        mesh = make_mesh((2, 4), ("data", "model"))
        with mesh:
            fn, args, in_sh, out_sh = dr.build_step(
                "whisper-base", "train_4k", "psi8", mesh)
        # whisper is the only arch small enough to lower quickly at full
        # config on 8 CPU devices
        with mesh:
            lowered = jax.jit(fn, in_shardings=in_sh,
                              out_shardings=out_sh).lower(*args)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes > 0
        coll, ops = dr.collective_bytes_per_device(compiled.as_text())
        print("OK", coll >= 0, sorted(ops))
    """, devices=8, timeout=560)
    assert "OK True" in out


def test_multistep_decode_sharded_token_identical():
    """Horizon-8 multi-step decode on a forced 8-device (4, 2) mesh (slots
    and paged blocks partitioned over the data axis, the round carry pinned
    to the same slot-over-data shardings) emits exactly the single-device
    horizon-1 streams, the scan compiling once; remesh preserves the
    horizon so an elastic restart keeps the multi-step entry point."""
    out = _run("""
        import dataclasses, jax, numpy as np
        from repro.configs import get_config, reduced_config
        from repro.launch.mesh import make_mesh
        from repro.launch.scheduler import Request
        from repro.launch.serve import Server
        from repro.models import build_model

        cfg = reduced_config(get_config("qwen3-8b"))
        model = build_model(cfg)
        params = model.quantize(model.init(jax.random.PRNGKey(0)), 8)
        cfg = dataclasses.replace(cfg, quant_mode="psi8")
        assert cfg.resolved_cache_layout == "paged"
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, size=(8,))
                   .astype(np.int32) for _ in range(6)]
        def mk():
            return [Request(rid=i, prompt=prompts[i], max_new=mn,
                            arrival_s=0.0)
                    for i, mn in enumerate([3, 7, 2, 13, 4, 9])]
        toks = lambda done: {r.rid: tuple(r.tokens) for r in done}

        ref = Server(cfg, params, max_batch=4, max_seq=64)
        t_ref = toks(ref.serve(mk(), continuous=True)[0])

        s8 = Server(cfg, params, max_batch=4, max_seq=64,
                    mesh=make_mesh((4, 2), ("data", "model")),
                    decode_horizon=8)
        d8, st8 = s8.serve(mk(), continuous=True)
        assert toks(d8) == t_ref, (toks(d8), t_ref)
        assert st8["slot_shards"] == 4
        assert st8["decode_horizon"] == 8
        assert st8["decode_compiles"] == 1, st8["decode_compiles"]
        assert s8.executor.multi_cache_sizes() == \\
            {"decode_multi": 1, "decode": 0}
        assert st8["host_syncs_per_token"] < 0.5
        assert st8["blocks_free_end"] == st8["n_blocks"]

        # elastic restart keeps the horizon (and its compiled entry)
        ex4 = s8.executor.remesh(devices=jax.devices()[:4])
        assert ex4.decode_horizon == 8
        assert ex4.decode_multi_cache_size() == 0   # fresh cache, not lost
        assert ex4._decode_multi is not None
        print("OK", st8["slot_shards"])
    """)
    assert "OK 4" in out


def test_sharded_paged_kernel_merges_cross_shard_blocks():
    """The paged-decode kernel under a (2, 2) mesh runs per pool shard and
    merges the shards' partial softmaxes: tables whose blocks straddle both
    data shards (allocator fallback, shared prefixes) still match the
    oracle, and a slot whose blocks sit on one shard matches the
    single-device kernel bit for bit."""
    out = _run("""
        import os
        os.environ["REPRO_FORCE_INTERPRET"] = "1"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.kernels import ops, paged_attention as pa
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        B, N, bs, Hkv, G, D = 4, 16, 4, 2, 2, 16
        q = jnp.asarray(rng.standard_normal((B, Hkv * G, D)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((N, bs, Hkv, D)), jnp.float32)
        vp = jnp.asarray(rng.standard_normal((N, bs, Hkv, D)), jnp.float32)
        # shard 0 holds blocks 0..7, shard 1 holds 8..15
        bt = jnp.asarray([[0, 1, 2, -1], [9, 3, 12, 5], [8, 10, -1, -1],
                          [-1, -1, -1, -1]], jnp.int32)
        pos = jnp.asarray([10, 15, 6, 0], jnp.int32)
        pool = NamedSharding(mesh, P("data", None, "model", None))
        kp, vp = jax.device_put(kp, pool), jax.device_put(vp, pool)

        @jax.jit
        def sharded(*a):
            with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                return ops.paged_decode_attention(*a)

        got = np.asarray(sharded(q, kp, vp, bt, pos))
        want = np.asarray(pa.paged_attention_ref(q, kp, vp, bt, pos))
        one = np.asarray(pa.paged_attention_pallas(q, kp, vp, bt, pos,
                                                   interpret=True))
        np.testing.assert_allclose(got[:3], want[:3], rtol=1e-5, atol=1e-5)
        assert np.array_equal(got[[0, 2]], one[[0, 2]])   # one shard each
        assert np.array_equal(got[3], np.zeros_like(got[3]))   # no keys
        print("OK")
    """, devices=4)
    assert "OK" in out


def test_sharded_pallas_serving_tokens_identical():
    """Serving with the Pallas kernels (interpret mode) on a (2, 2) mesh:
    every kernel call runs under shard_map and the bf16 token streams
    equal the single-device engine's exactly (d_model 1024 so weights
    really split over "model")."""
    out = _run("""
        import os
        os.environ["REPRO_FORCE_INTERPRET"] = "1"
        import dataclasses, jax, numpy as np
        from repro.configs import get_config, reduced_config
        from repro.launch.mesh import make_mesh
        from repro.launch.scheduler import Request
        from repro.launch.serve import Server
        from repro.models import build_model

        cfg = reduced_config(get_config("qwen3-8b"), dtype="bfloat16",
                             d_model=1024, n_heads=8, n_kv_heads=4,
                             head_dim=128, d_ff=1024, vocab_size=512)
        model = build_model(cfg)
        params = model.init_serving(jax.random.PRNGKey(0), 8)
        cfg = dataclasses.replace(cfg, quant_mode="psi8")
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, size=(12,))
                   .astype(np.int32) for _ in range(4)]
        def mk():
            return [Request(rid=i, prompt=prompts[i], max_new=mn,
                            arrival_s=0.0)
                    for i, mn in enumerate([6, 3, 5, 4])]

        s1 = Server(cfg, params, max_batch=4, max_seq=32)
        d1, st1 = s1.serve(mk(), continuous=True)
        s4 = Server(cfg, params, max_batch=4, max_seq=32,
                    mesh=make_mesh((2, 2), ("data", "model")))
        d4, st4 = s4.serve(mk(), continuous=True)
        assert st4["paged_attn_route"] == "interpret"
        assert st4["decode_compiles"] == 1, st4["decode_compiles"]
        assert {r.rid: r.tokens for r in d1} == {r.rid: r.tokens
                                                  for r in d4}
        print("OK")
    """, devices=4)
    assert "OK" in out
