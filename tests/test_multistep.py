"""Multi-step decode tests (DESIGN.md §3 "Multi-step decode & host
overlap"): token identity of horizon-M rounds vs the step-at-a-time engine
across cache layouts and KV quant modes, EOS retirement landing at every
in-round offset, max_new not a multiple of M, preemption firing between
rounds, the one-compile warmup contract, the DeviceBlockTable zero-transfer
regression, and the idle-loop iteration bound (no 5 ms busy-spin)."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.launch.scheduler import Request, replay_round
from repro.launch.serve import Server
from repro.launch.slo import bursty_heavy_tail_trace, parse_slo_spec
from repro.models import build_model


@pytest.fixture(scope="module")
def qwen_setup():
    cfg = reduced_config(get_config("qwen3-8b"))
    model = build_model(cfg)
    params = model.quantize(model.init(jax.random.PRNGKey(0)), 8)
    cfg = dataclasses.replace(cfg, quant_mode="psi8")
    return cfg, params


def _requests(cfg, specs, prompt_len=8, seed=0):
    """specs: list of (arrival_s, max_new)."""
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               size=(prompt_len,))
                    .astype(np.int32), max_new=mn, arrival_s=at)
            for i, (at, mn) in enumerate(specs)]


def _toks(done):
    return {r.rid: tuple(r.tokens) for r in done}


# ---------------------------------------------------------------------------
# Host-side round replay: the exact device retirement recurrence.
# ---------------------------------------------------------------------------
class TestReplayRound:
    def test_eos_and_budget_retirement(self):
        toks = np.array([[5, 9], [7, 9], [5, 9], [6, 9]], np.int32)
        emitted, act, rem = replay_round(
            toks, np.array([True, True]), np.array([8, 2], np.int32),
            eos_id=7)
        # slot 0 hits EOS at step 1 (the EOS token IS emitted, matching
        # the horizon-1 loop); slot 1 runs out of budget after 2 tokens.
        assert emitted[0] == [5, 7] and emitted[1] == [9, 9]
        assert not act[0] and not act[1]
        assert rem[0] == 6 and rem[1] == 0

    def test_inactive_rows_emit_nothing(self):
        toks = np.array([[1, 2]], np.int32)
        emitted, act, rem = replay_round(
            toks, np.array([False, True]), np.array([4, 4], np.int32),
            eos_id=-1)
        assert emitted[0] == [] and emitted[1] == [2]
        assert act[1] and rem[1] == 3 and rem[0] == 4


# ---------------------------------------------------------------------------
# Construction-time validation.
# ---------------------------------------------------------------------------
class TestValidation:
    def test_horizon_must_be_positive(self, qwen_setup):
        cfg, params = qwen_setup
        with pytest.raises(ValueError, match=">= 1"):
            Server(cfg, params, max_batch=2, max_seq=64, decode_horizon=-2)

    def test_horizon_rejects_speculative(self, qwen_setup):
        cfg, params = qwen_setup
        with pytest.raises(ValueError, match="speculative"):
            Server(cfg, params, max_batch=2, max_seq=64,
                   decode_horizon=4, speculative=(4, 4))


# ---------------------------------------------------------------------------
# Token-identity fuzz: horizon x layout x kv_quant.
# ---------------------------------------------------------------------------
# (layout, kv_quant): int8 KV applies to the paged pool only.
_COMBOS = [("dense", "none"), ("paged", "none"), ("paged", "int8")]


class TestHorizonIdentity:
    @pytest.mark.parametrize("layout,kvq", _COMBOS)
    def test_identity_across_horizons(self, qwen_setup, layout, kvq):
        """Horizons {2, 4, 8} emit bit-identical streams to horizon 1 for
        the same trace — staggered arrivals, mixed max_new (none a multiple
        of any horizon), mid-serve slot reuse."""
        cfg, params = qwen_setup
        cfg = dataclasses.replace(cfg, cache_layout=layout, kv_quant=kvq)
        specs = [(0.0, 3), (0.0, 7), (0.01, 2), (0.01, 5), (0.02, 9),
                 (0.02, 6)]
        base = Server(cfg, params, max_batch=3, max_seq=64)
        d0, s0 = base.serve(_requests(cfg, specs), continuous=True)
        assert s0["decode_horizon"] == 1
        for m in (2, 4, 8):
            srv = Server(cfg, params, max_batch=3, max_seq=64,
                         decode_horizon=m)
            d1, s1 = srv.serve(_requests(cfg, specs), continuous=True)
            assert _toks(d1) == _toks(d0), (layout, kvq, m)
            assert s1["decode_horizon"] == m
            assert s1["decode_rounds"] > 0
            assert s1["decode_compiles"] == 1, (m, s1["decode_compiles"])
            # exact lengths survive the in-round budget mask
            lens = {r.rid: len(r.tokens) for r in d1}
            assert lens == {i: mn for i, (_, mn) in enumerate(specs)}
            if layout == "paged":
                assert s1["blocks_free_end"] == s1["n_blocks"]

    def test_max_new_not_multiple_of_horizon(self, qwen_setup):
        """max_new in {1, 3, 5, 7, 9} at M=4: the remaining-budget mask
        retires each slot mid-round at the exact length."""
        cfg, params = qwen_setup
        specs = [(0.0, mn) for mn in (1, 3, 5, 7, 9)]
        base = Server(cfg, params, max_batch=4, max_seq=64)
        d0, _ = base.serve(_requests(cfg, specs, seed=3), continuous=True)
        srv = Server(cfg, params, max_batch=4, max_seq=64, decode_horizon=4)
        d1, s1 = srv.serve(_requests(cfg, specs, seed=3), continuous=True)
        assert _toks(d1) == _toks(d0)
        assert {r.rid: len(r.tokens) for r in d1} == \
            {i: mn for i, (_, mn) in enumerate(specs)}
        assert s1["decode_compiles"] == 1

    def test_eos_mid_round_at_every_offset(self, qwen_setup):
        """M=4: pick an EOS id that lands at each in-round offset
        {0, 1, 2, 3} of a single request's stream; horizon-4 retires the
        slot inside the scan and still matches horizon 1 exactly (the EOS
        token itself is emitted, then the row masks off).

        An offset is reachable only through a token whose FIRST occurrence
        lands there (an earlier copy would retire the stream first), and
        which offsets a random model's greedy stream reaches depends on the
        RNG; so the trace seed is searched until a stream reaches >= 3."""
        cfg, params = qwen_setup
        ref = Server(cfg, params, max_batch=1, max_seq=64)

        def first_hits(stream):
            # decode emission i is stream[1 + i] (stream[0] comes from
            # prefill); its in-round offset at M=4 is i % 4.
            out = {}
            for off in range(4):
                idx = next((1 + i for i in range(len(stream) - 1)
                            if i % 4 == off
                            and stream[1 + i] not in stream[:1 + i]), None)
                if idx is not None:
                    out[off] = idx
            return out

        for seed in range(5, 37):
            d_ref, _ = ref.serve(_requests(cfg, [(0.0, 12)], seed=seed),
                                 continuous=True)
            stream = list(d_ref[0].tokens)
            targets = first_hits(stream)
            if len(targets) >= 3:
                break
        hit = 0
        for off, idx in targets.items():
            hit += 1
            eos = int(stream[idx])
            h1 = Server(cfg, params, max_batch=1, max_seq=64, eos_id=eos)
            h4 = Server(cfg, params, max_batch=1, max_seq=64, eos_id=eos,
                        decode_horizon=4)
            t1 = _toks(h1.serve(_requests(cfg, [(0.0, 12)], seed=seed),
                                continuous=True)[0])
            t4 = _toks(h4.serve(_requests(cfg, [(0.0, 12)], seed=seed),
                                continuous=True)[0])
            assert t1 == t4, off
            assert t4[0][-1] == eos and len(t4[0]) == idx + 1, off
        assert hit >= 3                       # >=3 distinct offsets hit

    def test_preemption_between_rounds(self, qwen_setup):
        """SLO + chunked prefill + horizon 4 on the deliberately tight
        block pool: preemption fires between rounds (the in-flight round
        drains first), streams stay identical to the FIFO horizon-1
        baseline, and no block leaks."""
        cfg, params = qwen_setup
        pol = parse_slo_spec("default@aging=5@reserve=0.1")
        trace = lambda: bursty_heavy_tail_trace(
            16, vocab_size=cfg.vocab_size, seed=7, burst_size=8,
            burst_gap_s=0.3, long_frac=0.6, mix=pol.mix([3.0, 2.0, 1.0]))
        fifo = Server(cfg, params, max_batch=4, max_seq=112, n_blocks=8)
        multi = Server(cfg, params, max_batch=4, max_seq=112, n_blocks=8,
                       prefill_chunk=16, slo=pol, decode_horizon=4)
        d0, s0 = fifo.serve(trace(), continuous=True)
        d1, s1 = multi.serve(trace(), continuous=True)
        assert _toks(d0) == _toks(d1)
        assert s1["preemptions"] > 0
        assert s1["decode_compiles"] == 1
        assert s1["blocks_free_end"] == s1["n_blocks"]
        assert s0["blocks_free_end"] == s0["n_blocks"]

    def test_compile_contract_and_sync_drop(self, qwen_setup):
        """Warmup pre-compiles exactly ONE decode_multi executable (and no
        horizon-1 step); serving syncs the host once per round, not once
        per token."""
        cfg, params = qwen_setup
        specs = [(0.0, 17)] * 4
        srv = Server(cfg, params, max_batch=4, max_seq=64, decode_horizon=8)
        _, s = srv.serve(_requests(cfg, specs), continuous=True)
        assert srv.executor.multi_cache_sizes() == \
            {"decode_multi": 1, "decode": 0}
        assert s["decode_compiles"] == 1
        assert s["host_syncs"] > 0
        # 4 x 17 = 68 tokens; per-token syncing would be >= 64 decode
        # syncs alone.
        assert s["host_syncs_per_token"] <= 0.25, s
        # 16 decode emissions per slot, 4 slots in lockstep -> 2 useful
        # rounds, plus at most one pipelined trailing all-masked round.
        assert 2 <= s["decode_rounds"] <= 3, s["decode_rounds"]


# ---------------------------------------------------------------------------
# Satellite: DeviceBlockTable transfer caching.
# ---------------------------------------------------------------------------
class TestDeviceBlockTable:
    def test_zero_transfer_when_unchanged(self, qwen_setup):
        """An unchanged table returns the SAME committed device array —
        no host->device transfer — and one dirty row of four goes up as a
        single-row scatter, not a full upload."""
        cfg, params = qwen_setup
        srv = Server(cfg, params, max_batch=4, max_seq=64)
        ex = srv.executor
        bt = ex.make_block_table()
        bt[0, :] = 0
        d0 = bt.device()
        assert bt.stats["full_uploads"] == 1
        d1 = bt.device()
        assert d1 is d0                        # cached object, zero bytes
        assert bt.stats["reuses"] == 1
        v = bt.version
        bt[1, 0] = 3                           # 1 dirty row of 4 -> scatter
        assert bt.version == v + 1
        d2 = bt.device()
        assert d2 is not d1
        assert bt.stats["row_updates"] == 1
        assert bt.stats["full_uploads"] == 1   # unchanged
        np.testing.assert_array_equal(np.asarray(d2), bt.host)
        bt[0] = -1                             # 3 dirty rows of 4 -> full
        bt[2, :] = 1
        bt[3, :] = 2
        bt.device()
        assert bt.stats["full_uploads"] == 2
        assert bt.device() is bt.device()      # steady state reuses again

    def test_serve_reuses_table_across_rounds(self, qwen_setup):
        """A long single-slot decode re-dispatches the same device table:
        stats['block_table_transfers'] shows reuses dominating uploads."""
        cfg, params = qwen_setup
        srv = Server(cfg, params, max_batch=2, max_seq=96, decode_horizon=2)
        _, s = srv.serve(_requests(cfg, [(0.0, 24)]), continuous=True)
        tr = s["block_table_transfers"]
        assert tr["reuses"] > 0
        assert tr["reuses"] > tr["full_uploads"] + tr["row_updates"] - 2


# ---------------------------------------------------------------------------
# Satellite: idle path sleeps the actual wait (no 5 ms busy-spin).
# ---------------------------------------------------------------------------
class TestIdleLoop:
    def test_sparse_trace_loop_iters_bounded(self, qwen_setup):
        """Four requests spread 0.3 s apart: the loop sleeps each gap in
        O(1) iterations instead of spinning 5 ms slices (~60 iterations
        per gap under the old path)."""
        cfg, params = qwen_setup
        specs = [(0.0, 5), (0.3, 5), (0.6, 5), (0.9, 5)]
        srv = Server(cfg, params, max_batch=2, max_seq=64)
        done, s = srv.serve(_requests(cfg, specs), continuous=True)
        assert len(done) == 4
        steps = sum(mn for _, mn in specs)     # decode iterations
        assert s["loop_iters"] <= steps + 8 * len(specs), s["loop_iters"]
