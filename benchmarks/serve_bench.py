"""Serving benchmark: scheduling policy AND cache layout on one trace.

Four sections, all asserting greedy outputs are token-identical —
scheduling, cache layout, and prefix reuse may only change *when and
where* work runs, never the results:

1. **static vs continuous** (DESIGN.md §3): admission barriered until the
   whole batch drains vs iteration-level admission into free slots.  The
   delta isolates the scheduling policy: static pays the convoy effect and
   dead slots between retirement and the batch barrier.
2. **dense vs paged layout** at equal geometry: same ``max_batch`` /
   ``max_seq``, reporting the cache-memory columns (dense slab bytes vs
   paged pool bytes at equal capacity, peak block utilization %).
3. **capacity at equal cache bytes**: a dense server provisions
   ``max_batch`` worst-case slots; a paged server with the SAME usable
   cache bytes (``n_blocks * block_size == max_batch * max_seq``) but twice
   the slots admits strictly more concurrent requests, because blocks are
   reserved per request (bucketed prompt + its own ``max_new``) instead of
   per worst-case slot.
4. **shared-system-prompt trace** (DESIGN.md §3 "Prefix cache"): every
   request carries the same 256-token prefix + an 8-token unique tail;
   ``--prefix-cache on`` serves the prefix out of ref-counted pool blocks
   and prefills only the tail.  Reports prefix hit rate, prefilled vs
   reused tokens, and p50 TTFT with/without the cache, and asserts the
   cached run is token-identical with a measured hit rate > 0, strictly
   fewer mean prefilled tokens, and a p50 TTFT win.
5. **self-speculative decoding** (DESIGN.md §"Self-speculative decoding"):
   the same trace served plain vs with ``--speculative 3:4`` — a psi3
   draft view of the SAME checkpoint drafting 4 tokens/round, verified in
   one target-width pass.  Both runs use a QAT-preconditioned checkpoint
   (``--qat-precondition 3``: random-init logit margins drown in 3-bit
   noise; a trained checkpoint's margins are what speculation exploits).
   Asserts token identity, the compile-exactly-twice contract, and a mean
   accepted length > 1; reports the tokens/s ratio and draft overhead.
6. **SLO scheduling on a bursty heavy-tail trace** (DESIGN.md §3 "SLO
   scheduling"): requests arrive in bursts with a heavy tail of
   long-prompt/long-budget requests, served FIFO + worst-case reservation
   vs ``--slo default`` + ``--prefill-chunk`` on a deliberately tight
   block pool.  Asserts token identity (priority ordering, chunked
   prefill, and preemption/restore may reorder work, never change it),
   decode-compiles-exactly-once, preemptions actually observed, and a
   strict interactive-class p99 TTFT win for the SLO engine — the class
   the policy protects; the overall tail is allowed to tie since batch
   requests absorb the delay by design.

Results go to stdout AND to a machine-readable ``BENCH_serve.json`` (like
``BENCH_quant.json``) so CI can track the serving trajectory across PRs;
the file is re-read through a STRICT ``json.loads`` (non-finite constants
rejected) so an ``Infinity`` regression can never ship a broken artifact.

  PYTHONPATH=src python -m benchmarks.serve_bench --arch qwen3-8b --reduced \\
      --quant psi8 [--out BENCH_serve.json]

Sharded serving (mesh-native Executor, DESIGN.md §5) runs the same bench
with decode slots and cache blocks partitioned over the data axis —
token-identical results:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \\
      python -m benchmarks.serve_bench --arch qwen3-8b --reduced \\
      --quant psi8 --mesh 4x2
"""
from __future__ import annotations

import argparse
import gc
import json
import time

import jax

from repro.core.quantizer import parse_quant_mode
from repro.launch.compile_cache import use_compile_cache
from repro.launch.serve import (add_serve_args, build_server, format_mfu,
                                trace_from_args)
from repro.launch.slo import bursty_heavy_tail_trace, parse_slo_spec

DEFAULT_OUT = "BENCH_serve.json"


def _fmt(stats):
    return (f"{stats['tok_per_s']:8.1f} tok/s | "
            f"latency p50 {stats['p50_latency_s'] * 1e3:7.1f}ms "
            f"p99 {stats['p99_latency_s'] * 1e3:7.1f}ms | "
            f"ttft p50 {stats['p50_ttft_s'] * 1e3:6.1f}ms | "
            f"{stats['decode_steps']} steps | peak "
            f"{stats['peak_concurrency']} live")


def _tokens_by_rid(done):
    return {r.rid: tuple(r.tokens) for r in done}


def _assert_identical(a, b, what):
    ta, tb = _tokens_by_rid(a), _tokens_by_rid(b)
    assert ta == tb, f"token divergence across {what}"


def _clone_args(args, **over):
    ns = argparse.Namespace(**vars(args))
    for k, v in over.items():
        setattr(ns, k, v)
    return ns


def _strict_load(path):
    """Round-trip the emitted artifact through a STRICT parser: json.loads
    accepts bare Infinity/NaN by default, so a non-finite stat (the old
    ``tok_per_s: inf`` bug) would silently ship an artifact that breaks
    strict consumers.  Raise instead."""
    def reject(const):
        raise ValueError(f"non-finite JSON constant {const!r} in {path}")
    with open(path) as f:
        return json.load(f, parse_constant=reject)


def run_bench(args, out_path=None):
    server, cfg = build_server(args)

    def trace(a=args):
        return trace_from_args(a, cfg)

    # ---- 1. scheduling policy (on the configured/default layout) ----
    server.warmup(trace())
    done_s, stat_s = server.serve(trace(), continuous=False, warmup=False)
    done_c, stat_c = server.serve(trace(), continuous=True, warmup=False)
    _assert_identical(done_s, done_c, "static/continuous")

    speedup = stat_c["tok_per_s"] / stat_s["tok_per_s"]
    p99_ratio = stat_c["p99_latency_s"] / stat_s["p99_latency_s"]
    mesh = server.executor.mesh
    print(f"  mesh      : {dict(mesh.shape)} "
          f"({stat_c['slot_shards']} slot shard(s) over the data axis)")
    print(f"  layout    : {stat_c['cache_layout']} "
          f"({stat_c['cache_bytes'] / 1e6:.2f} MB cache)")
    print(f"  static    : {_fmt(stat_s)}")
    print(f"  continuous: {_fmt(stat_c)}")
    print(f"  continuous/static: {speedup:.2f}x tokens/s, "
          f"{p99_ratio:.2f}x p99 latency "
          f"({stat_c['n_requests']} reqs, {stat_c['tokens']} tokens, "
          f"decode compiles: {stat_c['decode_compiles']})")

    payload = {
        "bench": "serve_bench", "arch": args.arch, "reduced": args.reduced,
        "quant": args.quant, "mesh": dict(mesh.shape),
        "requests": args.requests, "max_batch": args.max_batch,
        "modes": {"static": stat_s, "continuous": stat_c},
        "cont_vs_static_tok_per_s": round(speedup, 3),
        "cont_vs_static_p99": round(p99_ratio, 3),
    }

    capacity_win = None
    if server.paged:
        # ---- 2. layout equivalence + cache-memory columns ----
        dense_server, _ = build_server(_clone_args(args,
                                                   cache_layout="dense",
                                                   cache_blocks=None,
                                                   prefix_cache="off"))
        done_d, stat_d = dense_server.serve(trace(), continuous=True)
        _assert_identical(done_c, done_d, "paged/dense layouts")
        dense_b, paged_b = stat_d["cache_bytes"], stat_c["cache_bytes"]
        print(f"  cache mem : dense {dense_b / 1e6:.2f} MB vs paged "
              f"{paged_b / 1e6:.2f} MB at equal capacity "
              f"({stat_c['n_blocks']}x{stat_c['block_size']} blocks "
              f"+ {args.max_batch} scratch, peak block util "
              f"{stat_c['block_util_pct']}%)")
        payload["layout_equivalence"] = {
            "token_identical": True,
            "dense_cache_bytes": dense_b,
            "paged_cache_bytes": paged_b,
            "paged_block_util_pct": stat_c["block_util_pct"],
            "dense": stat_d,
        }

        # ---- 3. capacity at an equal cache-byte budget ----
        # Same usable KV bytes as the dense slab (n_blocks * block_size ==
        # max_batch * max_seq), twice the decode slots: heterogeneous
        # requests reserve only their own need, so strictly more of them
        # fit concurrently.  A heterogeneous trace (prompt jitter + wide
        # decode budgets) is what a dense worst-case slab over-provisions.
        cap_args = _clone_args(
            args, max_batch=2 * args.max_batch,
            prompt_jitter=max(args.prompt_jitter, 8), min_new=1,
            prefix_cache="off")     # isolate the layout from prefix reuse
        cap_dense, _ = build_server(_clone_args(cap_args,
                                                cache_layout="dense",
                                                cache_blocks=None,
                                                max_batch=args.max_batch))
        # budget derived from the CAPACITY dense baseline's own geometry
        # (its max_seq can exceed the section-1 server's when the jitter
        # bump widens the prompt bucket): usable paged rows == dense rows.
        bsz = cap_dense.cfg.cache_block_size
        budget_blocks = args.max_batch * (cap_dense.max_seq // bsz)
        cap_paged, _ = build_server(_clone_args(
            cap_args, cache_blocks=budget_blocks))
        assert cap_paged.max_seq == cap_dense.max_seq
        dtrace = trace(cap_args)
        ptrace = trace(cap_args)
        done_cd, stat_cd = cap_dense.serve(dtrace, continuous=True)
        done_cp, stat_cp = cap_paged.serve(ptrace, continuous=True)
        _assert_identical(done_cd, done_cp, "capacity dense/paged")
        capacity_win = (stat_cp["peak_concurrency"],
                        stat_cd["peak_concurrency"])
        print(f"  capacity  : equal budget "
              f"{stat_cd['cache_bytes'] / 1e6:.2f} MB dense KV -> paged "
              f"admits {stat_cp['peak_concurrency']} concurrent vs dense "
              f"{stat_cd['peak_concurrency']} "
              f"({stat_cp['tok_per_s'] / stat_cd['tok_per_s']:.2f}x "
              f"tokens/s)")
        assert stat_cp["peak_concurrency"] > stat_cd["peak_concurrency"], (
            "paged layout must admit strictly more concurrent requests "
            "than dense at the same cache-byte budget")
        payload["capacity"] = {
            "cache_byte_budget_dense": stat_cd["cache_bytes"],
            "paged_usable_blocks": cap_paged.executor.n_blocks,
            "dense_slots": args.max_batch,
            "paged_slots": 2 * args.max_batch,
            "dense": stat_cd,
            "paged": stat_cp,
            "dense_peak_concurrency": stat_cd["peak_concurrency"],
            "paged_peak_concurrency": stat_cp["peak_concurrency"],
        }

    if server.paged and cfg.rope == "rope":
        # ---- 4. shared-system-prompt trace: prefix cache off vs on ----
        # (skipped for non-plain-RoPE paged archs — qwen2-vl's mrope
        # positions cannot be replayed from a scalar pos0)
        # A dedicated trace (one 256-token system prompt + 8-token unique
        # tails by default — override with --shared-prefix-len /
        # --prompt-len) replayed through two fresh servers; both warm up
        # first so TTFT measures prefill work, not XLA.
        # Default shape: a LONG shared prefix (256 tokens) with short fixed
        # decode budgets keeps TTFT dominated by the prefill compute the
        # cache elides — on the reduced CPU model, shorter prefixes leave
        # the delta inside dispatch noise.  A user-supplied
        # --shared-prefix-len keeps the user's own trace shape.  TTFT is
        # the MEDIAN over 3 serves per config (tokens are deterministic;
        # wall time on a shared CI box is not).
        user_set = bool(getattr(args, "shared_prefix_len", 0))
        pargs = _clone_args(
            args,
            shared_prefix_len=(args.shared_prefix_len if user_set else 256),
            prompt_len=(args.prompt_len if user_set else 8),
            requests=(args.requests if user_set else 16),
            max_new=(args.max_new if user_set else 6),
            min_new=(args.min_new if user_set else 6),
            prompt_jitter=0, cache_blocks=None, prefix_cache="off")
        off_server, pcfg = build_server(pargs)
        on_server, _ = build_server(_clone_args(pargs, prefix_cache="on"))

        def ptrace():
            return trace_from_args(pargs, pcfg)

        def median_serve(server):
            server.warmup(ptrace())
            runs = [server.serve(ptrace(), continuous=True, warmup=False)
                    for _ in range(3)]
            runs.sort(key=lambda ds: ds[1]["p50_ttft_s"])
            return runs[1]                         # median-TTFT run

        done_off, stat_off = median_serve(off_server)
        done_on, stat_on = median_serve(on_server)
        _assert_identical(done_off, done_on, "prefix cache off/on")
        pc = stat_on["prefix_cache"]
        assert stat_on["decode_compiles"] == 1
        if not user_set:
            # hard wins are asserted only on the curated default shape —
            # a user-chosen prefix (e.g. shorter than one aligned block)
            # can legitimately miss the cache or sit inside CPU dispatch
            # noise, and should produce a report, not an AssertionError
            assert pc["hit_rate"] > 0, \
                "shared-prefix trace must hit the cache"
            assert (stat_on["prefilled_tokens_mean"]
                    < stat_off["prefilled_tokens_mean"]), \
                "prefix cache must lower mean prefilled tokens per request"
            assert stat_on["p50_ttft_s"] < stat_off["p50_ttft_s"], \
                "prefix cache must win p50 TTFT on the shared-prefix trace"
        ttft_win = (stat_off["p50_ttft_s"] / stat_on["p50_ttft_s"]
                    if stat_on["p50_ttft_s"] > 0 else 0.0)
        print(f"  prefix    : shared {pargs.shared_prefix_len}-token prompt "
              f"-> hit rate {pc['hit_rate']:.2f}, "
              f"{stat_on['prefix_tokens_reused']} tok reused, prefilled "
              f"mean {stat_on['prefilled_tokens_mean']:.1f} vs "
              f"{stat_off['prefilled_tokens_mean']:.1f} | p50 ttft "
              f"{stat_on['p50_ttft_s'] * 1e3:.1f}ms vs "
              f"{stat_off['p50_ttft_s'] * 1e3:.1f}ms ({ttft_win:.2f}x)")
        payload["prefix_cache"] = {
            "shared_prefix_len": pargs.shared_prefix_len,
            "token_identical": True,
            "hit_rate": pc["hit_rate"],
            "tokens_reused": stat_on["prefix_tokens_reused"],
            "prefilled_tokens_mean_on": stat_on["prefilled_tokens_mean"],
            "prefilled_tokens_mean_off": stat_off["prefilled_tokens_mean"],
            "p50_ttft_s_on": stat_on["p50_ttft_s"],
            "p50_ttft_s_off": stat_off["p50_ttft_s"],
            "ttft_win": round(ttft_win, 3),
            "off": stat_off,
            "on": stat_on,
        }

    kind, sbits = ((None, None) if args.quant == "none"
                   else parse_quant_mode(args.quant))
    if server.paged and cfg.rope == "rope" and kind == "psi" and sbits > 3:
        # ---- 5. self-speculative decoding: psi3 draft + k=4 verify ----
        # Both servers serve the QAT-preconditioned checkpoint so the
        # spec-off baseline emits the same tokens; only the decode engine
        # differs.  The curated default shape (user overrides keep their
        # own) uses longer fixed-ish budgets so rounds dominate prefill.
        # Curated default shape: fixed full-length decode budgets keep the
        # comparison decode-dominated (where the draft/verify round pays),
        # and the tokens/s is the MEDIAN over 3 serves per engine — the
        # tokens are deterministic, wall time on a shared CI box is not.
        user_set = bool(getattr(args, "speculative", None))
        sargs = _clone_args(
            args,
            speculative=(args.speculative if user_set else "3:4"),
            qat_precondition=(getattr(args, "qat_precondition", 0) or 3),
            requests=(args.requests if user_set else 12),
            max_batch=(args.max_batch if user_set else 2),
            max_new=(args.max_new if user_set else 64),
            min_new=(args.min_new if user_set else 64),
            prompt_jitter=0, cache_blocks=None, prefix_cache="off")
        spec_off, scfg = build_server(_clone_args(sargs, speculative=None))
        spec_on, _ = build_server(sargs)

        def strace():
            return trace_from_args(sargs, scfg)

        def median_spec_serve(server):
            # Collect before each timed serve: earlier sections leave dead
            # servers in reference cycles (Executor <-> jitted bound
            # methods), and the cyclic GC otherwise fires MID-SERVE —
            # releasing their XLA buffers inside the timed loop skewed the
            # first post-section serve ~4x.
            server.warmup(strace())
            runs = []
            for _ in range(3):
                gc.collect()
                runs.append(server.serve(strace(), continuous=True,
                                         warmup=False))
            runs.sort(key=lambda ds: ds[1]["tok_per_s"])
            return runs[1]                       # median-throughput run

        done_soff, stat_soff = median_spec_serve(spec_off)
        done_son, stat_son = median_spec_serve(spec_on)
        _assert_identical(done_soff, done_son, "speculative off/on")
        sp = stat_son["speculative"]
        spec_ratio = (stat_son["tok_per_s"] / stat_soff["tok_per_s"]
                      if stat_soff["tok_per_s"] > 0 else 0.0)
        print(f"  spec      : psi{sp['draft_bits']} draft, k={sp['k']} -> "
              f"accepted {stat_son['accepted_per_step']:.2f}/round over "
              f"{sp['rounds']} rounds | {stat_son['tok_per_s']:.1f} vs "
              f"{stat_soff['tok_per_s']:.1f} tok/s ({spec_ratio:.2f}x) | "
              f"draft overhead {stat_son['draft_overhead_s']:.3f}s | "
              f"compiles {sp['spec_compiles']}")
        assert sp["spec_compiles"] == {"draft": 1, "verify": 1,
                                       "decode": 0}, (
            f"speculative compile contract: {sp['spec_compiles']}")
        assert sp["mean_accepted"] > 1, (
            f"speculative draft must amortize the verify pass: mean "
            f"accepted length {sp['mean_accepted']} <= 1")
        if not user_set:
            # hard wall-clock win only on the curated shape (measured
            # ~1.5x on the reduced CPU config; generous flake margin)
            assert spec_ratio > 1.1, (
                f"speculative decode must beat plain decode on the "
                f"curated trace, got {spec_ratio:.2f}x")
        payload["speculative"] = {
            "draft_bits": sp["draft_bits"], "k": sp["k"],
            "token_identical": True,
            "rounds": sp["rounds"],
            "mean_accepted": sp["mean_accepted"],
            "accepted_per_step": stat_son["accepted_per_step"],
            "draft_overhead_s": stat_son["draft_overhead_s"],
            "tok_per_s_off": stat_soff["tok_per_s"],
            "tok_per_s_on": stat_son["tok_per_s"],
            "speedup": round(spec_ratio, 3),
            "spec_compiles": sp["spec_compiles"],
            "off": stat_soff,
            "on": stat_son,
        }

    if server.paged and cfg.rope == "rope":
        # ---- 6. SLO scheduling on a bursty heavy-tail trace ----
        # Curated shape: bursts of 8 back-to-back arrivals, half carrying a
        # long prompt AND a long decode budget, over a block pool sized so
        # a burst of longs cannot all fit — the traffic FIFO + worst-case
        # reservation head-of-line-blocks on.  The SLO engine admits
        # optimistically (reserve_frac of the decode budget), chunks the
        # long prefills between decode steps, and preempts the youngest
        # batch-class runner under pool pressure (restore = suffix-only
        # re-prefill out of the published blocks).  The asserted metric is
        # INTERACTIVE-class p99 TTFT — the class the policy exists to
        # protect; overall p99 may tie because batch requests absorb the
        # delay by design.  Each engine's number is the MEDIAN over 3
        # serves — tokens are deterministic, wall time on a shared CI box
        # is not; the first serve also absorbs the lazy restore-shape
        # compiles (runtime-state-dependent, unforeseeable at warmup) so
        # the median measures scheduling, not XLA.
        slo_spec = "default@aging=5@reserve=0.1"
        base = _clone_args(
            args, requests=24, max_batch=4, prompt_len=56, max_new=32,
            min_new=32, prompt_jitter=0, cache_blocks=9,
            prefix_cache="off", speculative=None, qat_precondition=0,
            prefill_chunk=0, slo="off")
        slo_args = _clone_args(base, prefill_chunk=16, slo=slo_spec)
        fifo_server, bcfg = build_server(base)
        slo_server, _ = build_server(slo_args)
        policy = parse_slo_spec(slo_spec)

        def btrace():
            return bursty_heavy_tail_trace(
                base.requests, vocab_size=bcfg.vocab_size, seed=args.seed,
                burst_size=8, burst_gap_s=0.25, long_frac=0.5,
                long_prompt=56, short_prompt=8, long_new=32, short_new=8,
                mix=policy.mix([3.0, 2.0, 1.0]))

        def class_p99_ttft(done, priority=0):
            ts = sorted(r.ttft_s for r in done if r.priority == priority)
            if not ts:
                return 0.0
            return ts[min(len(ts) - 1, int(0.99 * (len(ts) - 1) + 0.999))]

        def median_slo_serve(server):
            server.warmup(btrace())
            runs = []
            for _ in range(3):
                gc.collect()
                runs.append(server.serve(btrace(), continuous=True,
                                         warmup=False))
            runs.sort(key=lambda ds: class_p99_ttft(ds[0]))
            return runs[1]                 # median interactive-p99 run

        done_fifo, stat_fifo = median_slo_serve(fifo_server)
        done_slo, stat_slo = median_slo_serve(slo_server)
        int_fifo = class_p99_ttft(done_fifo)
        int_slo = class_p99_ttft(done_slo)
        _assert_identical(done_fifo, done_slo, "fifo/slo scheduling")
        assert stat_slo["decode_compiles"] == 1, (
            f"SLO+chunked serving must keep the decode step compiling "
            f"exactly once, got {stat_slo['decode_compiles']}")
        assert stat_slo["preemptions"] > 0, (
            "the tight-pool bursty trace must exercise preemption")
        assert stat_slo["blocks_free_end"] == slo_server.executor.n_blocks, (
            "preemption/restore must leak no blocks")
        assert int_slo < int_fifo, (
            f"SLO scheduling must win interactive-class p99 TTFT on the "
            f"bursty heavy-tail trace: {int_slo:.3f}s vs FIFO "
            f"{int_fifo:.3f}s")
        slo_win = int_fifo / int_slo if int_slo > 0 else 0.0
        rc = stat_slo["prefix_cache"]
        print(f"  slo       : bursty tail -> interactive p99 ttft "
              f"{int_slo * 1e3:.1f}ms vs FIFO {int_fifo * 1e3:.1f}ms "
              f"({slo_win:.2f}x) | overall p99 "
              f"{stat_slo['p99_ttft_s'] * 1e3:.1f}ms vs "
              f"{stat_fifo['p99_ttft_s'] * 1e3:.1f}ms | "
              f"{stat_slo['preemptions']} preemptions, "
              f"{rc['restores']} restores "
              f"({rc['restored_tokens']} tok), "
              f"{stat_slo['prefill_chunks']} chunk pieces")
        payload["slo"] = {
            "token_identical": True,
            "trace": {"requests": base.requests, "burst_size": 8,
                      "long_frac": 0.5, "n_blocks": 9},
            "interactive_p99_ttft_s_fifo": int_fifo,
            "interactive_p99_ttft_s_slo": int_slo,
            "p99_ttft_s_fifo": stat_fifo["p99_ttft_s"],
            "p99_ttft_s_slo": stat_slo["p99_ttft_s"],
            "p99_ttft_win": round(slo_win, 3),
            "preemptions": stat_slo["preemptions"],
            "restores": rc["restores"],
            "restored_tokens": rc["restored_tokens"],
            "prefill_chunks": stat_slo["prefill_chunks"],
            "decode_compiles": stat_slo["decode_compiles"],
            "classes": stat_slo["slo"]["classes"],
            "fifo": stat_fifo,
            "slo": stat_slo,
        }

    # ---- 7. multi-step decode: horizon 1 vs 8 replay ----
    # Curated shape: 16 requests with fixed 48-token decode budgets over 4
    # slots keep the serve decode-round-dominated — exactly where the
    # per-token host round trip pays.  Horizon 8 drains the SAME trace with
    # one host sync per 8-step round (plus admissions), so syncs/token must
    # drop >= 4x and wall-clock tokens/s must strictly improve, with the
    # scan compiling once.  Tokens are deterministic; tokens/s is the
    # MEDIAN over 3 serves per engine (shared-CI wall time is not).
    user_h = int(getattr(args, "decode_horizon", 1) or 1)
    h_hi = user_h if user_h > 1 else 8
    margs = _clone_args(
        args, requests=16, max_batch=4, max_new=48, min_new=48,
        prompt_jitter=0, cache_blocks=None, prefix_cache="off",
        speculative=None, qat_precondition=0, prefill_chunk=0, slo="off")
    h1_server, mcfg = build_server(_clone_args(margs, decode_horizon=1))
    hM_server, _ = build_server(_clone_args(margs, decode_horizon=h_hi))

    def mtrace():
        return trace_from_args(margs, mcfg)

    def median_multi_serve(server):
        server.warmup(mtrace())
        runs = []
        for _ in range(3):
            gc.collect()
            runs.append(server.serve(mtrace(), continuous=True,
                                     warmup=False))
        runs.sort(key=lambda ds: ds[1]["tok_per_s"])
        return runs[1]                       # median-throughput run

    done_h1, stat_h1 = median_multi_serve(h1_server)
    done_hM, stat_hM = median_multi_serve(hM_server)
    _assert_identical(done_h1, done_hM, f"decode horizon 1/{h_hi}")
    for st in (stat_h1, stat_hM):            # serving-metrics contract
        for key in ("host_syncs", "host_syncs_per_token", "mfu",
                    "macs_per_token"):
            assert key in st, f"stats missing {key!r}"
    sync_ratio = (stat_h1["host_syncs_per_token"]
                  / stat_hM["host_syncs_per_token"]
                  if stat_hM["host_syncs_per_token"] > 0 else 0.0)
    multi_ratio = (stat_hM["tok_per_s"] / stat_h1["tok_per_s"]
                   if stat_h1["tok_per_s"] > 0 else 0.0)
    assert stat_hM["decode_compiles"] == 1, (
        f"multi-step serving must compile the horizon scan exactly once, "
        f"got {stat_hM['decode_compiles']}")
    assert sync_ratio >= 4, (
        f"horizon {h_hi} must cut host syncs/token >= 4x vs horizon 1, "
        f"got {sync_ratio:.2f}x ({stat_h1['host_syncs_per_token']} -> "
        f"{stat_hM['host_syncs_per_token']})")
    assert multi_ratio > 1, (
        f"horizon {h_hi} must strictly improve tokens/s, got "
        f"{multi_ratio:.2f}x ({stat_h1['tok_per_s']:.1f} -> "
        f"{stat_hM['tok_per_s']:.1f})")
    mesh_identity = "skipped"
    if len(jax.devices()) >= 8:
        # (4, 2)-mesh twin: the sharded horizon engine emits the exact
        # single-device streams (one serve — identity, not timing).
        hmesh, _ = build_server(_clone_args(margs, mesh="4x2",
                                            decode_horizon=h_hi))
        done_hm, stat_hm = hmesh.serve(mtrace(), continuous=True)
        _assert_identical(done_h1, done_hm, f"horizon {h_hi} 1x1/(4,2)")
        assert stat_hm["decode_compiles"] == 1
        mesh_identity = True
    print(f"  multistep : horizon {h_hi} -> "
          f"{stat_hM['host_syncs_per_token']:.3f} vs "
          f"{stat_h1['host_syncs_per_token']:.3f} syncs/tok "
          f"({sync_ratio:.1f}x fewer) | {stat_hM['tok_per_s']:.1f} vs "
          f"{stat_h1['tok_per_s']:.1f} tok/s ({multi_ratio:.2f}x) | "
          f"mfu {format_mfu(stat_hM['mfu'])} | mesh "
          f"{mesh_identity}")
    payload["multistep"] = {
        "token_identical": True,
        "horizon": h_hi,
        "sync_ratio": round(sync_ratio, 3),
        "host_syncs_per_token_h1": stat_h1["host_syncs_per_token"],
        "host_syncs_per_token_hM": stat_hM["host_syncs_per_token"],
        "tok_per_s_h1": stat_h1["tok_per_s"],
        "tok_per_s_hM": stat_hM["tok_per_s"],
        "speedup": round(multi_ratio, 3),
        "decode_rounds": stat_hM.get("decode_rounds", 0),
        "decode_compiles": stat_hM["decode_compiles"],
        "mfu": stat_hM["mfu"],
        "mesh_identity": mesh_identity,
        "h1": stat_h1,
        "hM": stat_hM,
    }

    if out_path:
        with open(out_path, "w") as f:
            json.dump(payload, f, indent=2, allow_nan=False)
        _strict_load(out_path)         # fail loudly, never ship bad JSON
        print(f"  wrote {out_path}")
    return stat_s, stat_c, speedup, p99_ratio, capacity_win


def run():
    """Entry point for the benchmarks.run harness (reduced CPU defaults);
    emits the machine-readable BENCH_serve.json."""
    ap = argparse.ArgumentParser()
    add_serve_args(ap)
    args = ap.parse_args(["--arch", "qwen3-8b", "--reduced", "--quant",
                          "psi8"])
    t0 = time.time()
    _, stat_c, speedup, p99_ratio, cap = run_bench(args,
                                                   out_path=DEFAULT_OUT)
    us = (time.time() - t0) * 1e6
    derived = (f"cont_vs_static={speedup:.2f}x;p99_ratio={p99_ratio:.2f};"
               f"tok_per_s={stat_c['tok_per_s']:.0f};"
               f"layout={stat_c['cache_layout']}")
    if cap:
        derived += f";capacity_paged_vs_dense={cap[0]}v{cap[1]}"
    d = _strict_load(DEFAULT_OUT)
    if "prefix_cache" in d:
        pc = d["prefix_cache"]
        derived += (f";prefix_hit={pc['hit_rate']:.2f}"
                    f";prefix_ttft_win={pc['ttft_win']:.2f}x")
    if "speculative" in d:
        sp = d["speculative"]
        derived += (f";spec_speedup={sp['speedup']:.2f}x"
                    f";spec_accepted={sp['mean_accepted']:.2f}")
    if "slo" in d:
        sl = d["slo"]
        derived += (f";slo_p99_ttft_win={sl['p99_ttft_win']:.2f}x"
                    f";slo_preemptions={sl['preemptions']}")
    if "multistep" in d:
        ms = d["multistep"]
        derived += (f";horizon{ms['horizon']}_sync_ratio="
                    f"{ms['sync_ratio']:.2f}x"
                    f";horizon_speedup={ms['speedup']:.2f}x"
                    f";mfu={format_mfu(ms['mfu'])}")
    return [("serve_bench", us, derived)]


def main():
    ap = argparse.ArgumentParser()
    add_serve_args(ap)
    ap.add_argument("--out", default=None,
                    help=f"write machine-readable results (default off on "
                         f"the CLI; benchmarks.run writes {DEFAULT_OUT})")
    args = ap.parse_args()
    use_compile_cache()
    run_bench(args, out_path=args.out)


if __name__ == "__main__":
    main()
