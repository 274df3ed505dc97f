"""Paged KV-cache subsystem tests (DESIGN.md §3): BlockAllocator
properties, scheduler-level fragmentation churn, request-accounting NaN
semantics, and engine-level paged-vs-dense equivalence on the reduced
qwen3-8b config."""
import dataclasses
import random

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config, reduced_config
from repro.launch.scheduler import (BlockAllocator, Request, Scheduler,
                                    summarize)
from repro.launch.serve import Server
from repro.models import build_model, kvcache as kvc


def _requests(specs, prompt_len=8):
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=rng.integers(0, 256, size=(prompt_len,))
                    .astype(np.int32), max_new=mn, arrival_s=at)
            for i, (at, mn) in enumerate(specs)]


# ---------------------------------------------------------------------------
# BlockAllocator properties.
# ---------------------------------------------------------------------------
class TestBlockAllocator:
    @given(st.integers(4, 48), st.integers(1, 4), st.integers(0, 10_000))
    @settings(max_examples=40, deadline=None)
    def test_churn_invariants(self, n_blocks, n_shards, seed):
        """Random reserve/alloc/release interleavings: a block is never
        double-allocated, free + in_use == n_blocks after every op, the
        high watermark is monotone, and a full trace replay (everything
        released) restores the exact initial allocator state."""
        alloc = BlockAllocator(n_blocks, n_shards=n_shards)
        initial_free = sorted(b for pool in alloc._free for b in pool)
        rng = random.Random(seed)
        live = {}                                 # rid -> unmet reservation
        last_peak = 0
        for rid in range(rng.randint(1, 30)):
            # maybe retire someone first
            if live and rng.random() < 0.4:
                victim = rng.choice(list(live))
                alloc.release(victim)
                del live[victim]
            need = rng.randint(1, max(1, n_blocks // 2))
            if not alloc.can_reserve(need):
                continue
            alloc.reserve(rid, need)
            live[rid] = need
            for _ in range(rng.randint(0, need)):
                blk = alloc.alloc(rid,
                                  shard=rng.choice([None, 0, n_shards - 1]))
                assert 0 <= blk < n_blocks
                assert alloc.owner[blk] == rid     # never double-allocated
                live[rid] -= 1
            assert alloc.free_count + alloc.in_use == n_blocks
            assert alloc.reserved_total == sum(live.values())
            assert alloc.high_watermark >= last_peak  # monotone watermark
            last_peak = alloc.high_watermark
        for rid in list(live):
            alloc.release(rid)
        # freeing returns capacity exactly; no leaks, no duplicates
        assert alloc.free_count == n_blocks
        assert alloc.reserved_total == 0
        assert sorted(b for pool in alloc._free for b in pool) == initial_free
        assert all(o is None for o in alloc.owner)

    def test_alloc_beyond_reservation_rejected(self):
        alloc = BlockAllocator(8)
        alloc.reserve(1, 2)
        alloc.alloc(1)
        alloc.alloc(1)
        with pytest.raises(ValueError, match="beyond its reservation"):
            alloc.alloc(1)

    def test_reservation_gates_capacity(self):
        """Outstanding reservations count against can_reserve even before
        the blocks materialize — the admission guarantee that running
        requests never starve mid-decode."""
        alloc = BlockAllocator(10)
        alloc.reserve(1, 6)                       # nothing allocated yet
        assert not alloc.can_reserve(5)
        assert alloc.can_reserve(4)
        alloc.release(1)                          # early retirement returns
        assert alloc.can_reserve(10)              # the unused reservation

    def test_double_reserve_rejected(self):
        alloc = BlockAllocator(8)
        alloc.reserve(1, 2)
        with pytest.raises(ValueError, match="already holds"):
            alloc.reserve(1, 1)

    def test_shard_preference(self):
        alloc = BlockAllocator(8, n_shards=4)
        assert alloc.shard_of == [0, 0, 1, 1, 2, 2, 3, 3]
        alloc.reserve(1, 3)
        assert alloc.alloc(1, shard=2) == 4       # hint honored
        assert alloc.alloc(1, shard=2) == 5
        assert alloc.alloc(1, shard=2) in (0, 2, 6)  # exhausted: fall back


class TestSchedulerChurn:
    def test_churn_trace_restores_allocator(self):
        """Fragmentation regression: a long admit/decode-alloc/retire churn
        of heterogeneous-length requests must end with the allocator's free
        count equal to its initial free count (no leaked blocks)."""
        bs = 16
        reqs = _requests([(0.0, 1 + (7 * i) % 40) for i in range(40)])
        for i, r in enumerate(reqs):              # heterogeneous prompts
            r.prompt = r.prompt[:1 + (5 * i) % 8]
        blocks = BlockAllocator(12, n_shards=2)
        needed = lambda r: kvc.blocks_for(len(r.prompt) + r.max_new, bs)
        sched = Scheduler(reqs, max_batch=4, blocks=blocks,
                          blocks_needed=needed)
        sched.poll(0.0)
        t, rng = 0.0, random.Random(0)
        while not sched.done:
            t += 0.01
            for slot, req in sched.admit(t):
                for _ in range(kvc.blocks_for(len(req.prompt), bs)):
                    blocks.alloc(req.rid)         # prefill blocks
            # decode: occasionally cross a block boundary
            for slot, req in list(sched.running.items()):
                if rng.random() < 0.3 and blocks._reserved.get(req.rid, 0):
                    blocks.alloc(req.rid)
                if rng.random() < 0.5:
                    sched.retire(slot, t)
        assert len(sched.finished) == 40
        assert blocks.free_count == 12            # == initial free count
        assert blocks.reserved_total == 0
        assert blocks.high_watermark > 0


# ---------------------------------------------------------------------------
# Request accounting (satellite regression): unfinished -> NaN, skipped.
# ---------------------------------------------------------------------------
class TestAccounting:
    def test_unfinished_request_metrics_are_nan(self):
        r = Request(rid=0, prompt=np.zeros((4,), np.int32), max_new=4,
                    arrival_s=3.5)
        assert np.isnan(r.latency_s)              # regression: was -3.5
        assert np.isnan(r.ttft_s)
        assert np.isnan(r.queue_s)
        r.admit_s = 4.0
        assert r.queue_s == pytest.approx(0.5)
        assert np.isnan(r.latency_s)
        r.first_token_s, r.finish_s = 4.25, 5.5
        assert r.ttft_s == pytest.approx(0.75)
        assert r.latency_s == pytest.approx(2.0)

    def test_summarize_skips_unfinished(self):
        reqs = _requests([(0.0, 4), (0.0, 4), (0.0, 4)])
        for r in reqs[:2]:
            r.admit_s, r.first_token_s, r.finish_s = 0.1, 0.2, 1.0
            r.tokens = [1, 2]
        reqs[2].tokens = [3]                      # arrived, never finished
        stats = summarize(reqs, wall_s=2.0)
        assert stats["p99_latency_s"] == pytest.approx(1.0)
        assert stats["p50_ttft_s"] == pytest.approx(0.2)
        assert stats["tokens"] == 5
        stats_none = summarize([reqs[2]], wall_s=1.0)
        assert stats_none["p99_latency_s"] == 0.0  # all-NaN degrades to 0


# ---------------------------------------------------------------------------
# Engine-level paged serving (reduced qwen3-8b).
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def qwen_setup():
    cfg = reduced_config(get_config("qwen3-8b"))
    model = build_model(cfg)
    params = model.quantize(model.init(jax.random.PRNGKey(0)), 8)
    cfg = dataclasses.replace(cfg, quant_mode="psi8")
    return cfg, params


class TestPagedEngine:
    def test_paged_is_default_and_token_identical_to_dense(self, qwen_setup):
        """Acceptance: paged serving (the full-attention default) emits
        exactly the dense layout's greedy tokens in both scheduling modes,
        with the decode step compiling once per server."""
        cfg, params = qwen_setup
        assert cfg.resolved_cache_layout == "paged"

        def mk():
            return _requests([(0.0, 3), (0.0, 7), (0.001, 2), (0.002, 5),
                              (0.003, 4), (0.004, 6)])

        dense = Server(dataclasses.replace(cfg, cache_layout="dense"),
                       params, max_batch=2, max_seq=64)
        paged = Server(cfg, params, max_batch=2, max_seq=64)
        assert paged.paged and not dense.paged
        done_d, stat_d = dense.serve(mk(), continuous=True)
        done_pc, stat_pc = paged.serve(mk(), continuous=True)
        done_ps, stat_ps = paged.serve(mk(), continuous=False)
        toks = lambda done: {r.rid: tuple(r.tokens) for r in done}
        assert toks(done_d) == toks(done_pc) == toks(done_ps)
        assert stat_pc["decode_compiles"] == 1
        assert stat_d["decode_compiles"] == 1
        assert stat_pc["cache_layout"] == "paged"
        assert stat_pc["blocks_free_end"] == stat_pc["n_blocks"]

    def test_paged_kv_int8_matches_dense(self):
        """The paged int8-KV path (per-entry scale pools scattered at
        insert, gathered+dequantized at decode) is token-identical to the
        dense int8 ring — the k/v_scale branch of
        paged_decode_attention_block has no other coverage."""
        cfg = reduced_config(get_config("qwen3-8b"), kv_quant="int8")
        model = build_model(cfg)
        params = model.quantize(model.init(jax.random.PRNGKey(0)), 8)
        cfg = dataclasses.replace(cfg, quant_mode="psi8")

        def mk():
            rng = np.random.default_rng(1)
            return [Request(rid=i, prompt=rng.integers(
                        0, cfg.vocab_size, size=(6 + 3 * i,))
                        .astype(np.int32), max_new=mn, arrival_s=0.0)
                    for i, mn in enumerate([4, 6, 3])]

        dense = Server(dataclasses.replace(cfg, cache_layout="dense"),
                       params, max_batch=2, max_seq=48)
        paged = Server(cfg, params, max_batch=2, max_seq=48)
        done_d, _ = dense.serve(mk(), continuous=True)
        done_p, stat_p = paged.serve(mk(), continuous=True)
        assert {r.rid: r.tokens for r in done_d} == \
               {r.rid: r.tokens for r in done_p}
        assert stat_p["decode_compiles"] == 1
        assert stat_p["blocks_free_end"] == stat_p["n_blocks"]

    def test_final_state_holds_live_pool_and_tables(self, qwen_setup):
        """After a serve the engine keeps the decode cache as the last step
        left it and each request's block-table row as it stood when the
        request retired; the paged-decode kernel reads that live state
        as its oracle does."""
        from repro.kernels import paged_attention as pa
        cfg, params = qwen_setup
        server = Server(cfg, params, max_batch=2, max_seq=48)
        assert server.final_state is None
        reqs = _requests([(0.0, 5), (0.0, 9), (0.001, 3), (0.002, 7)],
                         prompt_len=11)
        done, stats = server.serve(reqs, continuous=True)
        rows = server.final_state["block_rows"]
        assert sorted(rows) == [r.rid for r in sorted(done,
                                                      key=lambda r: r.rid)]
        for r in done:
            row = rows[r.rid]
            ids = row[row >= 0]
            written = len(r.prompt) + len(r.tokens) - 1
            assert len(ids) >= kvc.blocks_for(written, server.block_size)
            assert len(set(ids.tolist())) == len(ids)
            assert ids.max() < stats["n_blocks"]
        last = sorted(done, key=lambda r: r.finish_s)[-2:]
        bt = np.stack([rows[r.rid] for r in last])
        pos = np.asarray([len(r.prompt) + len(r.tokens) - 2 for r in last],
                         np.int32)
        pool = server.final_state["cache"].kv[0]["b0"]
        k_pool, v_pool = pool["k"][0], pool["v"][0]
        q = jax.random.normal(jax.random.PRNGKey(3),
                              (2, cfg.n_heads, cfg.resolved_head_dim),
                              k_pool.dtype)
        args = (q, k_pool, v_pool, bt, pos)
        assert np.abs(np.asarray(k_pool)[bt[bt >= 0]]).max() > 0
        np.testing.assert_allclose(
            np.asarray(pa.paged_attention_pallas(*args, interpret=True)),
            np.asarray(pa.paged_attention_ref(*args)), rtol=1e-5, atol=1e-5)
        server.serve(_requests([(0.0, 2)]), continuous=True)
        assert sorted(server.final_state["block_rows"]) == [0]

    def test_engine_churn_returns_all_blocks(self, qwen_setup):
        """Engine-level fragmentation regression: many heterogeneous
        admit/retire cycles through a small paged pool end with every
        block back in the free list."""
        cfg, params = qwen_setup
        rng = np.random.default_rng(2)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            size=(3 + (11 * i) % 20,))
                        .astype(np.int32),
                        max_new=1 + (5 * i) % 12, arrival_s=0.002 * i)
                for i in range(12)]
        server = Server(cfg, params, max_batch=2, max_seq=48, n_blocks=6)
        done, stats = server.serve(reqs, continuous=True)
        assert stats["n_requests"] == 12
        assert stats["blocks_free_end"] == 6 == stats["n_blocks"]
        assert 0 < stats["peak_blocks_in_use"] <= 6
        assert stats["block_util_pct"] <= 100.0

    def test_block_gated_admission_still_serves_all(self, qwen_setup):
        """A pool smaller than the slot count's worst case gates admission
        (head-of-line waits for blocks, no deadlock) and every request
        still completes with its full budget."""
        cfg, params = qwen_setup
        reqs = _requests([(0.0, 8)] * 5, prompt_len=6)
        # each request worst-case: ceil(max(16, 6+8)/16) = 1 block; pool of
        # 2 blocks but 4 slots: at most 2 concurrent despite 4 free slots
        server = Server(cfg, params, max_batch=4, max_seq=32, n_blocks=2)
        done, stats = server.serve(reqs, continuous=True)
        assert stats["n_requests"] == 5
        assert all(len(r.tokens) == 8 for r in done)
        assert stats["peak_concurrency"] <= 2
        assert stats["blocks_free_end"] == 2

    def test_oversized_request_fails_fast(self, qwen_setup):
        cfg, params = qwen_setup
        server = Server(cfg, params, max_batch=2, max_seq=64, n_blocks=1)
        reqs = _requests([(0.0, 40)], prompt_len=8)   # needs 3 blocks
        with pytest.raises(ValueError, match="more blocks than the pool"):
            server.serve(reqs)

    def test_warmup_skips_unreachable_burst_shapes(self, qwen_setup):
        """Satellite: a 1-request trace can never co-admit, so warmup must
        not compile the max_batch burst prefill path (and must log/return
        the compile count)."""
        cfg, params = qwen_setup
        server = Server(cfg, params, max_batch=4, max_seq=48)
        single = _requests([(0.0, 4)])
        n = server.warmup(single, verbose=False)
        sizes = server.executor.prefill_cache_sizes()
        assert sizes["prefill"] in (0, -1)        # burst path not compiled
        assert sizes["insert_burst"] in (0, -1)
        assert sizes["prefill_insert"] >= 1 or sizes["prefill_insert"] == -1
        assert n == 2                             # fused prefill + decode
        done, _ = server.serve(_requests([(0.0, 4)]), warmup=False)
        assert len(done[0].tokens) == 4
        # a multi-request trace does need (and compile) the burst path
        n_multi = server.warmup(_requests([(0.0, 4)] * 3), verbose=False)
        assert n_multi == 4
        assert server.executor.prefill_cache_sizes()["prefill"] in (1, -1)

    @pytest.mark.parametrize("layout,expected", [("paged", 7), ("dense", 6)])
    def test_warmup_compile_count_multi_bucket(self, qwen_setup, layout,
                                               expected):
        """Two prompt buckets: the burst INSERT compiles per bucket only
        for paged (the seq-cache extent follows the bucket); dense prefills
        at max_seq, so one insert executable covers both buckets — the
        logged count must match what actually compiled."""
        cfg, params = qwen_setup
        server = Server(dataclasses.replace(cfg, cache_layout=layout),
                        params, max_batch=2, max_seq=64)
        reqs = _requests([(0.0, 4)] * 2, prompt_len=8) + \
            _requests([(0.0, 4)] * 2, prompt_len=20)
        n = server.warmup(reqs, verbose=False)
        assert n == expected        # 2 fused + 2 prefill + insert(s) + decode
        sizes = server.executor.prefill_cache_sizes()
        if sizes["insert_burst"] != -1:
            assert sizes["insert_burst"] == (2 if layout == "paged" else 1)


class TestKVCacheType:
    def test_pytree_roundtrip_preserves_layout(self):
        cache = kvc.KVCache(kv={"x": np.zeros((2, 2))}, layout=kvc.PAGED,
                            block_size=16, n_blocks=8)
        leaves, treedef = jax.tree_util.tree_flatten(cache)
        back = jax.tree_util.tree_unflatten(treedef, leaves)
        assert back.layout == kvc.PAGED
        assert back.block_size == 16 and back.n_blocks == 8 and back.paged

    def test_layout_resolution_guards(self):
        swa = reduced_config(get_config("mixtral-8x22b"))
        assert swa.resolved_cache_layout == "dense"      # SWA -> dense
        with pytest.raises(ValueError, match="paged"):
            dataclasses.replace(swa, cache_layout="paged") \
                .resolved_cache_layout
        ssm = reduced_config(get_config("falcon-mamba-7b"))
        assert ssm.resolved_cache_layout == "dense"
        dense_forced = reduced_config(get_config("qwen3-8b"),
                                      cache_layout="dense")
        assert dense_forced.resolved_cache_layout == "dense"

    def test_helpers(self):
        assert kvc.blocks_for(1, 16) == 1
        assert kvc.blocks_for(16, 16) == 1
        assert kvc.blocks_for(17, 16) == 2
        assert kvc.table_width(96, 16) == 6
        sds = jax.ShapeDtypeStruct((4, 2), np.float32)
        assert kvc.cache_nbytes({"a": sds}) == 32
