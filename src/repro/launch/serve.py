"""Continuous-batching serving engine over PSI-quantized weights.

The engine owns ``max_batch`` decode *slots* backed by one typed ``KVCache``
(DESIGN.md §3).  Under the default **paged** layout (full-attention
families) the cache is a pool of fixed-size blocks driven by host-side
block tables: the scheduler's ``BlockAllocator`` reserves a request's
worst-case blocks at admission, materializes them on demand during decode,
and frees them at retirement — so admission is gated on *actual* token
capacity instead of worst-case slots, and heterogeneous-length traffic fits
more concurrent requests in the same cache bytes (``--cache-layout`` /
``--block-size`` / ``--cache-blocks``).  The **dense** layout (per-slot
``max_seq`` slabs) remains for recurrent/SSM state, SWA rings, and encdec.

A slot-based scheduler (``repro.launch.scheduler``) admits arriving
requests into free slots mid-decode, retires sequences at EOS / ``max_new``
(freeing slot AND blocks immediately for the next arrival), and the engine
interleaves prefill of admissions with ongoing decode steps.  The jitted
decode step is shape-stable — a fixed ``(max_batch, 1)`` token tensor, an
active-slot mask that freezes the cache rows of free slots, and (paged) a
``(max_batch, n_bt)`` block-table input — so XLA compiles it exactly once
per serve lifetime (DESIGN.md §3).  The decode step runs entirely on the
PSI serving format — on TPU the psi_matmul Pallas kernel reads 5/8-bit
weights from HBM (DESIGN.md §2).

The Server is the HOST half only: scheduler loop, prompt buckets, latency
accounting.  Every device interaction — mesh construction, sharded
placement, jit compilation + donation — lives in the mesh-native
``repro.runtime.Executor`` (DESIGN.md §5); there is exactly one compilation
path whether the engine runs on 1 device or a pod.  On a sharded mesh the
decode slots are laid out contiguously over the "data" axis and the
scheduler admits into per-shard free slots.

Under ``--slo`` (DESIGN.md §3 "SLO scheduling") the scheduler orders
admission by an aged-priority policy (``repro.launch.slo``), reservation
turns OPTIMISTIC (expected usage instead of worst case), and pool pressure
is resolved by PREEMPTING the lowest-priority running request — its
pool-resident KV is published into the prefix cache so resume is a cheap
suffix-only re-prefill (the COW machinery as a swap layer).
``--prefill-chunk N`` splits long prompt prefills into N-token chunks
interleaved with decode steps, reusing the prefix path's ``pos0``/``ctx_kv``
absolute-position machinery so chunk N attends over the pool-resident KV of
chunks 0..N-1; intermediate chunks skip the lm-head.  Both keep the decode
step compiling exactly once and the emitted tokens identical to the FIFO
baseline.

A batch-synchronous ("static") mode runs the same machinery with admission
barriered until every slot drains — the baseline ``benchmarks/serve_bench.py``
measures continuous batching against.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \\
      --quant psi8 --requests 32 --max-batch 4 --arrival-rate 1000 \\
      --max-new 48 --mode both --mesh 1x1
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import math
import time
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.configs import get_config, reduced_config
from repro.core.quantizer import (parse_policy, parse_quant_mode,
                                  serving_mode_choices)
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.prefix_cache import PrefixCache
from repro.launch.scheduler import (BlockAllocator, Request, Scheduler,
                                    poisson_trace, replay_round, summarize)
from repro.launch.slo import parse_slo_spec, slo_report
from repro.models import build_model, kvcache as kvc
from repro.runtime.executor import Executor

# Prompt lengths are rounded up to a multiple of this before prefill so the
# number of compiled prefill shapes is bounded (attention caches mask the pad
# slots out via true_lens; recurrent families prefill at exact length).
PREFILL_BUCKET = 16


def parse_spec_spec(spec: Optional[str]) -> Optional[Tuple[int, int]]:
    """"BITS:K" (e.g. "3:4") -> (draft_bits, k) for --speculative; None /
    "off" -> None.  BITS must name a registered PsiFormat narrower than the
    serving width (validated downstream where the serving format is known);
    K is the draft length per round."""
    if not spec or spec == "off":
        return None
    try:
        bits, k = (int(p) for p in spec.split(":"))
    except ValueError as e:
        raise ValueError(
            f"malformed --speculative spec {spec!r}: want \"BITS:K\" with "
            f"two integers, e.g. \"3:4\" (psi3 draft, 4 tokens/round)") from e
    if k < 1:
        raise ValueError(f"--speculative draft length k={k} must be >= 1")
    return bits, k


def parse_mesh_spec(spec: Optional[str]):
    """"DxM" (e.g. "1x1", "4x2") -> a (data, model) Mesh; None / "1x1" with
    one device -> None (the Executor's single-device path)."""
    if not spec or spec == "1x1":
        return None
    try:
        d, m = (int(p) for p in spec.lower().split("x"))
    except ValueError as e:
        # a bare "8" or a "2x2x2" used to surface as an opaque unpacking
        # ValueError; say what shape the spec must have
        raise ValueError(
            f"malformed mesh spec {spec!r}: want \"DATAxMODEL\" with two "
            f"integer extents, e.g. \"1x1\" or \"4x2\"") from e
    if d * m > len(jax.devices()):
        raise ValueError(
            f"mesh {spec} needs {d * m} devices, have {len(jax.devices())} "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count=N on "
            f"CPU)")
    return make_mesh((d, m), ("data", "model"))


class Server:
    """Slot-based serving engine: continuous or batch-synchronous scheduling
    over one shape-stable jitted decode step (DESIGN.md §3).  Device work is
    delegated to a mesh-native Executor (DESIGN.md §5)."""

    def __init__(self, cfg, params, max_batch: int = 4, max_seq: int = 256,
                 eos_id: int = -1, bucket: int = PREFILL_BUCKET, mesh=None,
                 executor: Optional[Executor] = None,
                 n_blocks: Optional[int] = None,
                 speculative: Optional[Tuple[int, int]] = None,
                 prefill_chunk: int = 0, slo=None,
                 decode_horizon: int = 1):
        self.cfg = cfg
        self.paged = cfg.resolved_cache_layout == kvc.PAGED
        self.final_state = None          # set by each serve()
        # Multi-step decode (DESIGN.md §3 "Multi-step decode & host
        # overlap"): horizon-M rounds of the on-device token loop; 1 = the
        # classic step-at-a-time path.
        self.decode_horizon = int(decode_horizon or 1)
        if self.decode_horizon < 1:
            raise ValueError(
                f"decode_horizon={decode_horizon} must be >= 1")
        # Self-speculative decoding (DESIGN.md §"Self-speculative decoding"):
        # (draft_bits, k) or None.  The Executor validates the deep
        # preconditions (paged layout, k <= block_size, quantized params);
        # the Server only tracks the +k-1 cache/block overhang a round's
        # k-wide write needs past the last emitted token.
        self.spec = tuple(speculative) if speculative else None
        self.spec_k = self.spec[1] if self.spec else 0
        self._spec_overhang = self.spec_k - 1 if self.spec else 0
        if self.decode_horizon > 1 and self.spec:
            raise ValueError(
                "--decode-horizon > 1 does not compose with --speculative: "
                "a speculative round is already a fused multi-token device "
                "unit with its own acceptance loop — pick ONE multi-token "
                "decode strategy (drop --speculative or set the horizon "
                "to 1)")
        # Shared-prefix block reuse (DESIGN.md §3 "Prefix cache"):
        # validated here so an impossible combination (dense layout, mrope)
        # fails at construction, not mid-serve.
        self.prefix_enabled = cfg.prefix_cache_enabled
        if n_blocks is not None and not self.paged:
            raise ValueError(
                "n_blocks/--cache-blocks only applies to the paged cache "
                "layout; this server resolved to dense "
                "(cfg.resolved_cache_layout)")
        self.block_size = cfg.cache_block_size if self.paged else 0
        if self.paged:
            # Align the cache extent to the block grid: the paged read
            # attends over n_bt * block_size key columns, and keeping that
            # equal to the dense extent keeps the two layouts bit-identical
            # (same reduction shapes) for the layout-equivalence tests.
            max_seq = -(-max_seq // self.block_size) * self.block_size
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.bucket = bucket
        # SLO scheduling + chunked prefill (DESIGN.md §3 "SLO scheduling").
        # Both lean on the prefix path's pos0/ctx_kv machinery — absolute
        # positions replayed from a scalar offset — so they carry the same
        # paged + plain-RoPE requirement the prefix cache does.
        self.slo = slo
        self.prefill_chunk = int(prefill_chunk or 0)
        if self.prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0, got {prefill_chunk}")
        if self.slo is not None or self.prefill_chunk:
            what = ("--slo" if self.slo is not None else "--prefill-chunk")
            if not self.paged:
                raise ValueError(f"{what} requires the paged cache layout "
                                 f"(cfg.resolved_cache_layout)")
            if cfg.rope != "rope":
                raise ValueError(
                    f"{what} replays absolute positions from a block-"
                    f"aligned offset, which needs plain RoPE; got "
                    f"rope={cfg.rope!r}")
        if self.prefill_chunk:
            # chunk boundaries must land on BOTH grids: the block grid (so
            # ctx_ids covers whole blocks) and the prefill bucket grid (so
            # the final piece's bucketed extent never outruns the
            # bucket(full_seq) reservation: pos0 + bucket(L - pos0) ==
            # bucket(L) only for bucket-aligned pos0)
            grid = math.lcm(self.block_size, self.bucket)
            self.prefill_chunk = -(-self.prefill_chunk // grid) * grid
        # every admission goes through the per-request ctx prefill path
        # (nctx=0 compiles its own shape, identical graph) whenever any of
        # the three ctx consumers is on
        self._ctx_serving = (self.prefix_enabled or self.slo is not None
                             or self.prefill_chunk > 0)
        if executor is not None:
            if mesh is not None:
                raise ValueError("pass mesh= OR executor= (the executor "
                                 "already owns its mesh), not both")
            if (executor.max_batch, executor.max_seq) != (max_batch, max_seq):
                raise ValueError(
                    f"injected executor was built for max_batch="
                    f"{executor.max_batch}, max_seq={executor.max_seq}; "
                    f"Server asked for {max_batch}/{max_seq}")
            if executor.speculative != self.spec:
                raise ValueError(
                    f"injected executor was built with speculative="
                    f"{executor.speculative}; Server asked for {self.spec}")
            if executor.decode_horizon != self.decode_horizon:
                raise ValueError(
                    f"injected executor was built with decode_horizon="
                    f"{executor.decode_horizon}; Server asked for "
                    f"{self.decode_horizon}")
        self.executor = executor if executor is not None else Executor(
            cfg, params, max_batch=max_batch, max_seq=max_seq, mesh=mesh,
            n_blocks=n_blocks if self.paged else None,
            speculative=self.spec, decode_horizon=self.decode_horizon)
        self.cache_bytes = kvc.cache_nbytes(jax.eval_shape(
            self.executor._init_cache_fn))
        # Recurrent state absorbs pad tokens, so SSM/hybrid (and whisper's
        # decoder) prefill at exact prompt length instead of padded buckets.
        self._pad_ok = cfg.family not in ("ssm", "hybrid", "encdec")
        self._swa_window = (cfg.window if cfg.attn_type == "swa" else 0)
        # actual KV ring extent (init_kv_cache caps SWA caches at the window)
        self._ring_extent = (min(max_seq, self._swa_window)
                             if self._swa_window else max_seq)

    # -------------------------------------------------------------- plumbing
    def _blocks_needed(self, req: Request) -> int:
        """Pool blocks to reserve at admission, stated over ``full_seq``
        (prompt plus everything generated — a preempted request's restore
        re-reserves what the restore actually needs, not the original
        prompt's worst case).

        FIFO default: the WORST case — the bucketed prefill extent or the
        sequence+remaining-budget extent, whichever is longer — so a
        running request can never starve mid-decode (early EOS returns the
        unused tail).  Speculative rounds are k positions wide regardless
        of remaining budget, so the last round can write up to k-1
        positions past the final emitted token — the overhang joins the
        reservation.

        Under an SLO policy reservation is OPTIMISTIC (DESIGN.md §3 "SLO
        scheduling"): the full prefill extent plus only ``reserve_frac``
        of the remaining budget.  The shortfall is paid on demand via
        ``grow_reserve``, with preemption as the pressure valve — that is
        the whole point: worst-case gating is what head-of-line-blocks a
        bursty heavy tail."""
        L = len(req.full_seq)
        remaining = max(req.max_new - len(req.tokens), 0)
        need = max(self._bucket_len(L),
                   L + remaining + self._spec_overhang)
        if self.slo is not None:
            expected = max(self._bucket_len(L),
                           L + math.ceil(self.slo.reserve_frac * remaining)
                           + self._spec_overhang)
            need = min(need, expected)
        return kvc.blocks_for(need, self.block_size)

    def _block_pref(self, slot: int) -> Optional[int]:
        """Allocate a slot's blocks from its own data shard when the block
        pools partition the same way the slots do (keeps the decode gather
        shard-local); otherwise let the allocator balance."""
        ex = self.executor
        if ex.n_block_shards == ex.n_slot_shards:
            return int(ex.slot_shards[slot])
        return None

    def _bucket_len(self, n: int) -> int:
        if not self._pad_ok:
            return n
        sb = -(-n // self.bucket) * self.bucket
        # Sliding-window ring cache: pad positions past the ring extent
        # (min(window, max_seq)) would evict *real* prompt tokens from the
        # tail window, so fall back to the exact length whenever the padded
        # prompt would overrun it.
        if self._swa_window and sb > self._ring_extent:
            return n
        return sb

    def _read(self, x, span: str, **args) -> np.ndarray:
        """Blocking device-to-host read of ``x`` under the profiler span
        ``span`` (``prefill.wait`` or ``decode.wait``).  Every wait of the
        serve loop comes through here: it counts one host sync and leaves
        its time out of ``host_busy_s``."""
        t = time.perf_counter()
        with TraceAnnotation(span, **args):
            out = np.asarray(x)
        self._syncs += 1
        self._wait_s += time.perf_counter() - t
        return out

    def _prefill_admits(self, cache, admits: Sequence[Tuple[int, Request]],
                        sched: Optional[Scheduler] = None, bt=None,
                        chunking: Optional[Dict[int, int]] = None):
        """Prefill newly admitted requests and insert each into its slot.

        A single admission (the continuous steady state) runs a (1, Sb)
        prefill; a burst (static mode / startup) pads the batch dimension to
        ``max_batch`` and prefills all rows at once, so both engines pay one
        compile per prompt bucket for each of the two batch shapes.
        Returns the first greedy token per admission, aligned with `admits`
        — an entry is None when chunked prefill deferred the slot (it is in
        ``chunking`` state and emits nothing yet).

        Paged layout: each admission's prompt blocks are allocated here
        (drawing down the reservation made at admission) and written into
        the host block table ``bt``; the insert scatters the prefilled rows
        into exactly those blocks (a burst's shared padding beyond a row's
        own allocation routes to the slot's scratch block).

        Ctx serving (prefix cache / SLO / chunked prefill): every admission
        runs the fused suffix-prefill path individually (hits and restore
        depths are per-request — nctx varies — so the padded burst cannot
        batch them), sharing the hit's blocks read-only into the table and
        prefilling only the uncached suffix of ``full_seq``.
        """
        if self._ctx_serving:
            firsts = []
            for slot, req in admits:
                f, cache = self._begin_fill(cache, slot, req, sched, bt,
                                            chunking)
                firsts.append(f)
            return firsts, cache
        lens = [len(r.prompt) for _, r in admits]
        sb = self._bucket_len(max(lens))
        if self.paged:
            for slot, req in admits:
                nb = kvc.blocks_for(self._bucket_len(len(req.prompt)),
                                    self.block_size)
                pref = self._block_pref(slot)
                bt[slot, :] = -1
                for j in range(nb):
                    bt[slot, j] = sched.blocks.alloc(req.rid, shard=pref)
        if not self._swa_window and not self.cfg.is_attention_free:
            # Full-attention cache extent: a longer prefill — or a decode
            # that runs past max_seq — would wrap the ring and silently
            # evict prompt tokens the causal mask still expects.  (SWA is
            # exempt — rolling the window is its defined semantics — and so
            # are attention-free SSMs, whose state is constant-size.)
            need = max(sb, *(len(r.prompt) + r.max_new + self._spec_overhang
                             for _, r in admits))
            if need > self.max_seq:
                raise ValueError(
                    f"request needs cache extent {need} (bucketed prompt + "
                    f"max_new) but Server was built with max_seq="
                    f"{self.max_seq}; size the Server for the longest "
                    f"request")
        # Right-padding a shorter row to sb is only safe when the pads are
        # maskable: never for recurrent state (_pad_ok False), and not for a
        # SWA ring the padded length would overrun (real tokens of shorter
        # rows would roll out of the window).  Otherwise, one per request.
        pad_safe = self._pad_ok and not (self._swa_window
                                         and sb > self._ring_extent)
        if len(set(lens)) > 1 and not pad_safe:
            firsts = []
            for slot, req in admits:
                f, cache = self._prefill_admits(cache, [(slot, req)],
                                                sched, bt)
                firsts.extend(f)
            return firsts, cache
        for _, req in admits:            # leaf call: the prefill really runs
            req.prefilled_tokens += len(req.prompt)
        # a burst's spans name every request it prefills
        rid = (admits[0][1].rid if len(admits) == 1
               else ",".join(str(r.rid) for _, r in admits))
        with TraceAnnotation("prefill.dispatch", rid=rid, offset=0,
                             tokens=sum(lens)):
            B = 1 if len(admits) == 1 else self.max_batch
            toks = np.zeros((B, sb), np.int32)
            tl = np.ones((B,), np.int32)
            for i, (_, req) in enumerate(admits):
                toks[i, :len(req.prompt)] = req.prompt
                tl[i] = len(req.prompt)
            if len(admits) == 1:                 # fused prefill + insert
                slot = admits[0][0]
                row = bt[slot] if self.paged else None
                first, cache = self.executor.prefill_insert(
                    toks, tl, cache, slot, block_row=row)
            else:
                first, seq_cache = self.executor.prefill(toks, tl)
        first = self._read(first, "prefill.wait", rid=rid)
        if len(admits) == 1:
            return [int(first[0])], cache
        with TraceAnnotation("prefill.dispatch", rid=rid, offset=0,
                             tokens=0):
            slots = np.zeros((self.max_batch,), np.int32)
            valid = np.zeros((self.max_batch,), bool)
            rows = (np.full((self.max_batch, self.executor.n_bt), -1,
                            np.int32) if self.paged else None)
            for i, (slot, _) in enumerate(admits):
                slots[i] = slot
                valid[i] = True
                if self.paged:
                    rows[i] = bt[slot]
            cache = self.executor.insert_burst(cache, seq_cache, slots,
                                               valid, block_rows=rows)
        return [int(first[i]) for i in range(len(admits))], cache

    def _begin_fill(self, cache, slot, req, sched, bt,
                    chunking: Optional[Dict[int, int]] = None):
        """Start filling a slot's KV for one (re-)admission on the ctx
        path (prefix cache / SLO / chunked prefill): the lookup hit's
        blocks enter the table read-only (shared references held by the
        scheduler), then either the whole remaining suffix prefills now
        (emitting the next token) or — chunked prefill, suffix longer than
        one chunk — the slot enters ``chunking`` state and the engine
        advances it one chunk per loop iteration, interleaved with decode.

        The suffix is ``full_seq[pos0:]``.  For a fresh admission that is
        the uncached prompt tail.  For a preempted request it ends with
        the PENDING token (the newest emitted token, whose KV the decode
        step never wrote), so the final piece's last-position logits ARE
        the next decode output — restore emits exactly the token plain
        decode would have (DESIGN.md §3 "SLO scheduling")."""
        pos0 = len(req.prefix_blocks) * self.block_size
        bt[slot, :] = -1
        if req.prefix_blocks:
            bt[slot, :len(req.prefix_blocks)] = req.prefix_blocks
        if (self.prefill_chunk
                and len(req.full_seq) - pos0 > self.prefill_chunk):
            chunking[slot] = pos0
            return None, cache
        return self._fill_piece(cache, slot, req, sched, bt, pos0)

    def _fill_piece(self, cache, slot, req, sched, bt, cur: int):
        """Prefill + insert one contiguous piece of ``full_seq`` starting
        at the block- and bucket-aligned offset ``cur``, attending over
        the pool-resident KV of ``[0, cur)`` via ``ctx_ids`` at true
        absolute positions.  A non-final piece is exactly ``prefill_chunk``
        tokens with the lm-head skipped (emit=False — nothing to emit);
        the final piece is the bucketed remainder and returns the next
        greedy token.  Fresh blocks draw down the admission reservation —
        and because ``cur`` is bucket-aligned, total coverage is exactly
        ``bucket(len(full_seq))``, never past it."""
        seq = req.full_seq
        bs = self.block_size
        rem = len(seq) - cur
        final = not (self.prefill_chunk and rem > self.prefill_chunk)
        n = self._bucket_len(rem) if final else self.prefill_chunk
        take = rem if final else n
        with TraceAnnotation("prefill.dispatch", rid=req.rid, offset=cur,
                             tokens=int(take)):
            pref = self._block_pref(slot)
            for j in range(cur // bs, kvc.blocks_for(cur + n, bs)):
                if bt[slot, j] < 0:
                    bt[slot, j] = sched.blocks.alloc(req.rid, shard=pref)
            toks = np.zeros((1, n), np.int32)
            toks[0, :take] = seq[cur:cur + take]
            tl = np.asarray([take], np.int32)
            req.prefilled_tokens += int(take)
            first, cache = self.executor.prefill_insert(
                toks, tl, cache, slot, block_row=bt[slot],
                ctx_ids=bt[slot, :cur // bs], emit=final)
        if not final:
            return None, cache
        return int(self._read(first, "prefill.wait", rid=req.rid)[0]), cache

    def _advance_chunk(self, cache, slot, sched, bt,
                       chunking: Dict[int, int]):
        """Advance one chunking slot by one piece; returns (first | None,
        cache) — non-None means the final piece ran and the slot is ready
        to decode."""
        req = sched.running[slot]
        cur = chunking[slot]
        first, cache = self._fill_piece(cache, slot, req, sched, bt, cur)
        if first is None:
            chunking[slot] = cur + self.prefill_chunk
        else:
            del chunking[slot]
        return first, cache

    def warmup(self, requests: Sequence[Request], verbose: bool = True) -> int:
        """Compile every shape the trace CAN reach (per prompt bucket: the
        fused single-admission prefill+insert, plus — only when the trace
        can ever co-admit two requests — the max_batch burst prefill + row
        insert, plus the decode step) against a throwaway cache, so serving
        measures steady-state latency, not XLA.

        A single-request trace (or a max_batch=1 engine) can never take the
        burst path, so its shapes are skipped instead of paying their
        compiles up front.  Returns the number of compiled shapes (also
        logged, so compile-count regressions are visible in serve output).
        """
        ex = self.executor
        if self._ctx_serving:
            return self._warmup_ctx(requests, verbose)
        buckets = sorted({self._bucket_len(len(r.prompt)) for r in requests})
        # Burst admission needs >= 2 requests waiting at once; a 1-request
        # trace provably cannot reach those shapes.
        burst_reachable = len(requests) > 1 and self.max_batch > 1
        cache = ex.init_cache()
        n_shapes = 0
        brow = (np.full((ex.n_bt,), -1, np.int32) if self.paged else None)
        for sb in buckets:
            # single admission: fused prefill+insert (the only B=1 path)
            toks1 = np.zeros((1, sb), np.int32)
            tl1 = np.ones((1,), np.int32)
            _, cache = jax.block_until_ready(
                ex.prefill_insert(toks1, tl1, cache, 0, block_row=brow))
            n_shapes += 1
            if burst_reachable:
                # admission burst: batched prefill + one scatter insert
                toksB = np.zeros((self.max_batch, sb), np.int32)
                tlB = np.ones((self.max_batch,), np.int32)
                _, seq_cache = jax.block_until_ready(ex.prefill(toksB, tlB))
                slots = np.arange(self.max_batch, dtype=np.int32)
                rows = (np.full((self.max_batch, ex.n_bt), -1, np.int32)
                        if self.paged else None)
                cache = ex.insert_burst(cache, seq_cache, slots,
                                        np.zeros((self.max_batch,), bool),
                                        block_rows=rows)
                n_shapes += 1
        if burst_reachable:
            # the burst insert compiles per bucket only when the prefilled
            # seq cache's extent follows the bucket (paged); dense prefills
            # at cache_len=max_seq, so one insert executable covers all
            n_shapes += len(buckets) if self.paged else 1
        n_shapes += self._warm_decode(cache)
        if verbose:
            skipped = 0 if burst_reachable else 2 * len(buckets)
            print(f"[warmup] compiled {n_shapes} shapes "
                  f"({len(buckets)} prompt bucket(s), layout "
                  f"{'paged' if self.paged else 'dense'}"
                  + (f", skipped {skipped} unreachable burst shape(s)"
                     if skipped else "") + ")")
        return n_shapes

    def _warm_decode(self, cache) -> int:
        """Compile the decode-side step(s) against a throwaway cache and
        return how many shapes that took.  Plain engine: the single
        shape-stable decode step.  Speculative engine: the fused draft scan
        plus the k-token verify — and the compile contract (exactly those
        TWO executables, the plain decode step never traced) is asserted
        here so a shape regression fails loudly at warmup, not as a silent
        slowdown in a benchmark diff."""
        ex = self.executor
        B = self.max_batch
        tok = np.zeros((B, 1), np.int32)
        act = np.zeros((B,), bool)
        bt = (np.full((B, ex.n_bt), -1, np.int32) if self.paged else None)
        if self.decode_horizon > 1:
            # multi-step engine: the horizon-M round is THE decode shape;
            # the single-step twin must never trace (same contract shape as
            # the speculative pair below)
            rem = np.zeros((B,), np.int32)
            jax.block_until_ready(ex.decode_multi(
                tok, tok, act, rem, cache, block_table=bt,
                eos_id=self.eos_id))
            sizes = ex.multi_cache_sizes()
            if sizes != {"decode_multi": 1, "decode": 0}:
                raise RuntimeError(
                    f"multi-step compile contract violated at warmup: want "
                    f"exactly one horizon-{self.decode_horizon} round "
                    f"executable with the single-step decode untraced, got "
                    f"{sizes}")
            return 1
        if not self.spec:
            jax.block_until_ready(ex.decode(tok, tok, act, cache,
                                            block_table=bt))
            return 1
        drafts, cache = jax.block_until_ready(
            ex.draft(tok, tok, act, cache, bt))
        jax.block_until_ready(ex.verify(tok, drafts, tok, act, cache, bt))
        sizes = ex.spec_cache_sizes()
        if sizes != {"draft": 1, "verify": 1, "decode": 0}:
            raise RuntimeError(
                f"speculative compile contract violated at warmup: want "
                f"exactly one draft + one verify executable with the plain "
                f"decode step untraced, got {sizes}")
        return 2

    def _warmup_ctx(self, requests: Sequence[Request],
                    verbose: bool) -> int:
        """Warmup for ctx serving (prefix cache / SLO / chunked prefill):
        every admission takes the per-request ctx prefill path, so compile,
        per distinct prompt length, the COLD admission's piece ladder —
        each intermediate chunk at ``(prefill_chunk, depth, emit=False)``,
        then the final bucketed piece — and, when organic prefix hits are
        possible, the deepest reachable hit (the longest block-aligned
        proper prefix, at the suffix's bucket).  Intermediate hit depths
        and preemption-restore shapes (suffix over prompt + GENERATED
        tokens — runtime state warmup cannot foresee) compile lazily
        mid-serve.  The decode step is shared with the non-ctx engine and
        still compiles exactly once."""
        ex = self.executor
        # the deepest REACHABLE hit must mirror PrefixCache's caps: keep
        # >= 1 suffix token AND land pos0 on the prefill-bucket grid
        step = PrefixCache.hit_alignment_step(self.block_size, self.bucket)
        bs = self.block_size
        shapes = set()                      # (seq_len, ctx_depth, emit)
        for r in requests:
            L = len(r.prompt)
            cur = 0
            while self.prefill_chunk and L - cur > self.prefill_chunk:
                shapes.add((self.prefill_chunk, cur // bs, False))
                cur += self.prefill_chunk
            shapes.add((self._bucket_len(L - cur), cur // bs, True))
            if self.prefix_enabled or self.slo is not None:
                nmax = ((L - 1) // bs // step) * step
                rem = L - nmax * bs
                if nmax and not (self.prefill_chunk
                                 and rem > self.prefill_chunk):
                    shapes.add((self._bucket_len(rem), nmax, True))
        cache = ex.init_cache()
        n_shapes = 0
        for sb, nctx, em in sorted(shapes):
            toks1 = np.zeros((1, sb), np.int32)
            tl1 = np.ones((1,), np.int32)
            brow = np.full((ex.n_bt,), -1, np.int32)
            _, cache = jax.block_until_ready(
                ex.prefill_insert(toks1, tl1, cache, 0, block_row=brow,
                                  ctx_ids=np.zeros((nctx,), np.int32),
                                  emit=em))
            n_shapes += 1
        n_shapes += self._warm_decode(cache)
        if verbose:
            print(f"[warmup] compiled {n_shapes} shapes "
                  f"({len(shapes)} (len, ctx-depth, emit) triple(s), "
                  f"layout paged + ctx serving)")
        return n_shapes

    def _spec_round(self, sched, cache, tok, pos, act, bt, now_fn,
                    retire):
        """One self-speculative round (DESIGN.md §"Self-speculative
        decoding"): a fused k-step draft pass at the low-bit view of the
        serving checkpoint, then ONE k-token verify at the target width.
        Per slot, accept the longest draft prefix the target agrees with
        (a) and emit min(a+1, k) tokens — the accepted drafts plus the
        target's correction verdict, which IS the plain-decode token for
        that position, so a=0 degrades to exactly non-speculative output.
        Emission is capped at the request's remaining budget and truncated
        at the first EOS; ``pos`` advances by the emitted count only, so
        rejected-tail cache entries sit strictly at/above the next feed
        position and are overwritten by the next round before any query can
        causally read them (no rollback pass)."""
        ex = self.executor
        K = self.spec_k
        t_draft = time.perf_counter()
        with TraceAnnotation("decode.dispatch", n_active=int(act.sum())):
            drafts_dev, cache = ex.draft(tok, pos, act, cache, bt)
            # the verify window is assembled on device from the draft
            # output, so both dispatches enqueue back-to-back with no host
            # round-trip
            verdicts, cache = ex.verify(tok, drafts_dev, pos, act, cache,
                                        bt)
        drafts = self._read(drafts_dev, "decode.wait", what="draft")
        # drafts materialize as soon as the draft executable finishes (the
        # verify is merely queued behind it), so this measures the round's
        # draft side; the verify sync below is the target-model cost any
        # decode engine pays
        draft_dt = time.perf_counter() - t_draft
        verdicts = self._read(verdicts, "decode.wait", what="verdict")
        now = now_fn()
        share = draft_dt / max(int(act.sum()), 1)
        n_retired = 0
        with TraceAnnotation("decode.emit") as ann:
            for slot in list(sched.running):
                if not act[slot]:
                    continue        # chunking slot: masked out of the round
                req = sched.running[slot]
                req.draft_s += share
                d, v = drafts[slot], verdicts[slot]
                a = 0
                while a < K and d[a] == v[a]:
                    a += 1
                emit = [int(x) for x in d[:a]]
                if a < K:
                    emit.append(int(v[a]))
                req.spec_rounds += 1
                req.spec_accepted += a
                finished = False
                n_emit = 0
                for t in emit:
                    req.emit(t, now)
                    n_emit += 1
                    if t == self.eos_id or len(req.tokens) >= req.max_new:
                        finished = True
                        break
                pos[slot, 0] += n_emit
                if finished:
                    act[slot] = False
                    retire(slot, now)
                    n_retired += 1
                else:
                    tok[slot, 0] = emit[n_emit - 1]
            ann.set_metadata(n_retired=n_retired)
        return cache

    # ------------------------------------------------------------- the loop
    def serve(self, requests: Sequence[Request], continuous: bool = True,
              warmup: bool = True):
        """Serve an arrival trace; returns (finished requests, stats).

        ``continuous=False`` barriers admission until all slots are free —
        classic batch-synchronous serving over the identical jitted step, so
        benchmark deltas isolate the scheduling policy.  Arrival times are
        interpreted on the wall clock, starting when this call begins.

        Afterwards ``self.final_state`` holds the engine's state: the
        decode cache as the last step left it and each finished request's
        block-table row as it stood when the request retired (paged layout;
        rows are freed at retirement, so a row's blocks may since hold
        later requests' writes).

        While a ``jax.profiler`` session is on, the loop writes host spans
        into it on the device's clock: ``serve.window`` around the served
        window, ``serve.iter`` per loop iteration, and within it
        ``sched.admit``, ``prefill.dispatch``/``prefill.wait``,
        ``kv.alloc``, ``decode.dispatch``/``decode.wait``,
        ``decode.emit``, ``idle.sleep`` and ``gc`` (every Python
        collection).  With no session they cost about a microsecond each.
        """
        gc_open = []                # (span, start) of a running collection
        gc_pauses = [0, 0.0]

        def on_gc(phase, info):
            if phase == "start":
                ann = TraceAnnotation("gc", generation=info["generation"])
                ann.__enter__()
                gc_open.append((ann, time.perf_counter()))
            elif gc_open:
                ann, t = gc_open.pop()
                ann.__exit__(None, None, None)
                gc_pauses[0] += 1
                gc_pauses[1] += time.perf_counter() - t

        gc.callbacks.append(on_gc)
        try:
            finished, stats = self._serve(requests, continuous, warmup)
        finally:
            gc.callbacks.remove(on_gc)
        stats["gc_pauses"], stats["gc_pause_s"] = gc_pauses
        return finished, stats

    def _serve(self, requests: Sequence[Request], continuous: bool,
               warmup: bool):
        """The body of :meth:`serve`."""
        clock = time.perf_counter
        ex = self.executor

        def worst_extent(r: Request) -> int:
            # Under an SLO policy a preempted request can restore prompt +
            # generated in one bucketed re-prefill, so the worst cache
            # extent is the BUCKETED full sequence, not max(bucketed
            # prompt, exact sequence).  This is also what guarantees the
            # preemption pressure path terminates: every request is
            # individually feasible, so preempting down to one runner
            # always makes progress.
            if self.slo is not None:
                return self._bucket_len(len(r.prompt) + r.max_new
                                        + self._spec_overhang)
            return max(self._bucket_len(len(r.prompt)),
                       len(r.prompt) + r.max_new + self._spec_overhang)

        if not (self._swa_window or self.cfg.is_attention_free):
            # fail fast, before any request is served/mutated, rather than
            # aborting mid-run at admission time
            bad = [r.rid for r in requests if worst_extent(r) > self.max_seq]
            if bad:
                raise ValueError(
                    f"requests {bad} need more cache than max_seq="
                    f"{self.max_seq} (bucketed prompt + max_new"
                    + (f" + the k-1 speculative overhang"
                       if self._spec_overhang else "")
                    + "); size the Server for the longest request")
        if self.paged:
            # same fail-fast for the block pool: a request whose worst case
            # exceeds the whole pool could never reserve, and admission
            # would head-of-line-block forever
            bad = [r.rid for r in requests
                   if kvc.blocks_for(worst_extent(r), self.block_size)
                   > ex.n_blocks]
            if bad:
                raise ValueError(
                    f"requests {bad} need more blocks than the pool holds "
                    f"(n_blocks={ex.n_blocks} of {self.block_size} "
                    f"positions); grow --cache-blocks or shrink the "
                    f"requests")
        if warmup:
            self.warmup(requests)
        blocks = None
        prefix = None
        if self.paged:
            blocks = BlockAllocator(ex.n_blocks, n_shards=ex.n_block_shards,
                                    shard_of=ex.block_shards)
            if self.prefix_enabled or self.slo is not None:
                # align hits to the prefill-bucket grid: the reservation /
                # fail-fast / table-width math bounds suffix coverage by
                # bucket(len(prompt)) only for bucket-aligned pos0.  SLO
                # mode needs the cache even with --prefix-cache off — it is
                # the swap layer preemption publishes into and restore
                # re-attaches from.
                prefix = PrefixCache(self.block_size,
                                     align_tokens=self.bucket)
        sched = Scheduler(requests, self.max_batch,
                          n_shards=ex.n_slot_shards, shard_of=ex.slot_shards,
                          blocks=blocks,
                          blocks_needed=(self._blocks_needed if blocks
                                         is not None else None),
                          prefix=prefix, policy=self.slo)
        cache = ex.init_cache()
        B = self.max_batch
        tok = np.zeros((B, 1), np.int32)
        pos = np.zeros((B, 1), np.int32)
        act = np.zeros((B,), bool)
        # remaining emission budget per slot — the multi-step round's
        # in-kernel retirement counter (mirrors Executor.decode_multi's
        # ``remaining`` input; unused state at horizon 1)
        rem = np.zeros((B,), np.int32)
        bt = (ex.make_block_table() if self.paged else None)
        kept_rows: Dict[int, np.ndarray] = {}     # -> final_state

        def retire(slot: int, now: float) -> None:
            """Retire the slot's request and free its table row, keeping a
            copy of the row for ``final_state``."""
            if self.paged:
                kept_rows[sched.running[slot].rid] = bt[slot].copy()
                bt[slot, :] = -1
            sched.retire(slot, now)

        chunking: Dict[int, int] = {}      # slot -> next piece offset
        steps = 0
        n_chunks = 0
        rounds = 0
        loop_iters = 0
        sleep_s = 0.0                      # idle.sleep, waiting to arrive
        self._syncs, self._wait_s = 0, 0.0     # see _read
        peak_running = 0
        M = self.decode_horizon
        multi = M > 1
        # Round pipelining (DESIGN.md §3 "Multi-step decode & host
        # overlap"): dispatch round N+1 from the DEVICE-resident carry
        # before the host processes round N's tokens, so scheduler work
        # overlaps device compute.  SLO preemption and chunked prefill
        # mutate host slot state at arbitrary boundaries, so those modes
        # drain every round immediately instead (still one sync per M
        # tokens — only the overlap is forgone).
        pipeline = multi and self.slo is None and not self.prefill_chunk
        pending = None        # in-flight round's (M, B) device tokens
        carry = None          # device carry chained round-to-round
        window = TraceAnnotation("serve.window", n_requests=len(requests))
        window.__enter__()
        t0 = clock()

        def process_toks(toks_dev) -> None:
            """Sync one finished round and replay the device's retirement
            recurrence over the host mirrors: emit per-slot streams, retire
            EOS/budget-exhausted slots, and leave tok/pos/act/rem exactly
            equal to the device carry row-for-row (the identity argument in
            DESIGN.md — same recurrence, same state)."""
            toks = self._read(toks_dev, "decode.wait")   # (M, B)
            now = clock() - t0
            emitted, act_out, rem_out = replay_round(toks, act, rem,
                                                     self.eos_id)
            n_retired = 0
            with TraceAnnotation("decode.emit") as ann:
                for slot in list(sched.running):
                    if not emitted[slot]:
                        continue   # not entry-active (free / chunking slot)
                    req = sched.running[slot]
                    for t in emitted[slot]:
                        req.emit(t, now)
                    pos[slot, 0] += len(emitted[slot])
                    tok[slot, 0] = emitted[slot][-1]
                    rem[slot] = rem_out[slot]
                    if not act_out[slot]:
                        act[slot] = False
                        retire(slot, now)
                        n_retired += 1
                ann.set_metadata(n_retired=n_retired)

        def drain() -> None:
            """Process the in-flight round, if any.  MUST run before any
            host mutation of tok/pos/act/rem outside :func:`process_toks`
            (admission emit, chunk completion, preemption) — the mirrors
            lag the device by one round while a round is in flight, and
            mutating stale mirrors would fork the state."""
            nonlocal pending
            if pending is not None:
                prev, pending = pending, None
                process_toks(prev)

        def emit_first(slot: int, req: Request, first: int,
                       now: float) -> None:
            """Book a prefill's emitted token and arm the slot for decode
            (shared by fresh admission, final chunk, and restore — the
            feed position is uniformly the index of the newest token in
            ``full_seq``, whose KV the NEXT step writes)."""
            nonlocal carry
            carry = None       # host mutated: rebuild from mirrors
            req.emit(first, now)
            if first == self.eos_id or len(req.tokens) >= req.max_new:
                retire(slot, now)
                return
            tok[slot, 0] = first
            pos[slot, 0] = len(req.prompt) + len(req.tokens) - 1
            act[slot] = True
            rem[slot] = req.max_new - len(req.tokens)

        def preempt_slot(vslot: int, vnow: float) -> None:
            """Evict a victim: publish only the KV actually written (a
            decode victim's pending token never was — ``pos`` is the feed
            position; a chunking victim has exactly ``[0, cur)``), clear
            the slot state, and re-queue it at its policy position."""
            nonlocal carry
            carry = None       # host mutated: rebuild from mirrors
            covered = chunking.pop(vslot, None)
            if covered is None:
                covered = int(pos[vslot, 0])
            sched.preempt(vslot, vnow, covered=covered)
            act[vslot] = False
            bt[vslot, :] = -1
            tok[vslot, 0] = 0
            pos[vslot, 0] = 0

        def secure_one(req: Request) -> bool:
            """Make one more block allocatable for ``req`` — the
            optimistic-reservation pressure path (DESIGN.md §3 "SLO
            scheduling"): spend remaining reservation if any; otherwise
            free capacity (LRU prefix eviction first — preempted victims'
            published blocks land there — then preempt the policy's
            preferred victim) and grow the reservation.  Returns False
            when ``req`` itself had to yield (no other victims left); the
            caller must skip the alloc.  Terminates: each round either
            evicts or preempts, at most max_batch preemptions are
            possible, and the per-request feasibility fail-fast means a
            lone runner always fits."""
            if sched.blocks.reserved_of(req.rid) > 0:
                return True
            while not sched.blocks.can_reserve(1):
                if prefix is not None:
                    prefix.evict_until(sched.blocks, 1)
                    if sched.blocks.can_reserve(1):
                        break
                victims = [s for s in sched.running if s != req.slot]
                if not victims:
                    preempt_slot(req.slot, clock() - t0)
                    return False
                v = max(victims,
                        key=lambda s: self.slo.victim_key(sched.running[s]))
                preempt_slot(v, clock() - t0)
            sched.blocks.grow_reserve(req.rid, 1)
            return True

        while not sched.done:
            loop_iters += 1
            with StepTraceAnnotation("serve.iter", step_num=loop_iters):
                now = clock() - t0
                with TraceAnnotation("sched.admit") as ann:
                    sched.poll(now)
                    admits = (sched.admit(now)
                              if continuous or not sched.running else [])
                    ann.set_metadata(n_admitted=len(admits))
                if admits:
                    drain()      # mirrors must be current before emit_first
                    firsts, cache = self._prefill_admits(cache, admits,
                                                         sched, bt, chunking)
                    now = clock() - t0
                    peak_running = max(peak_running, len(sched.running))
                    for (slot, req), first in zip(admits, firsts):
                        if first is None:
                            continue     # chunking: nothing emitted yet
                        emit_first(slot, req, first, now)
                if chunking:
                    # one piece per loop iteration (lowest slot first, for
                    # determinism): a long prefill interleaves with decode
                    # steps instead of stalling every running request
                    slot = min(chunking)
                    first, cache = self._advance_chunk(cache, slot, sched,
                                                       bt, chunking)
                    n_chunks += 1
                    if first is not None:
                        emit_first(slot, sched.running[slot], first,
                                   clock() - t0)
                if not sched.running:
                    if sched.waiting:
                        continue   # slots free (instant retirements)
                    nxt = sched.next_arrival_s()
                    if nxt is None:
                        break                      # everything drained
                    wait = nxt - (clock() - t0)
                    if wait > 0:
                        # sleep the actual remaining gap (capped so a clock
                        # hiccup can't oversleep an arrival by much) — the
                        # old 5 ms slices busy-spun O(gap / 5ms) iterations
                        # per arrival gap on sparse traces
                        t_sleep = clock()
                        with TraceAnnotation("idle.sleep"):
                            time.sleep(min(wait, 0.25))
                        sleep_s += clock() - t_sleep
                    continue
                if not act.any():
                    continue       # every running slot is still mid-chunking
                if self.paged:
                    # alloc-on-demand: every block this step's writes can
                    # touch must exist before the step runs.  FIFO mode
                    # reserved the worst case at admission so the alloc
                    # cannot fail; the SLO policy's optimistic reservation
                    # secures the shortfall here (eviction, then
                    # preemption).  A plain step writes one position; a
                    # speculative round writes k consecutive; a multi-step
                    # round writes up to M — and with a round in flight
                    # the device carry can already sit M ahead of the host
                    # mirror, so the pipelined span doubles.  Positions
                    # past the request's final feed (prompt + max_new - 2)
                    # are never written, so the span is capped there and
                    # the FIFO worst-case reservation still covers it.
                    span = max(self.spec_k, M)
                    if pipeline and pending is not None:
                        span += M
                    with TraceAnnotation("kv.alloc",
                                         n_active=int(act.sum())):
                        for slot, req in list(sched.running.items()):
                            if not act[slot]:
                                continue    # chunking, or preempted now
                            p0 = int(pos[slot, 0])
                            hi = min(p0 + span - 1,
                                     len(req.prompt) + req.max_new - 2)
                            for li in range(p0 // self.block_size,
                                            hi // self.block_size + 1):
                                if bt[slot, li] < 0:
                                    if (self.slo is not None
                                            and not secure_one(req)):
                                        break   # req itself yielded
                                    bt[slot, li] = sched.blocks.alloc(
                                        req.rid,
                                        shard=self._block_pref(slot))
                    if not act.any():
                        continue   # pressure path preempted every decoder
                if self.spec:
                    cache = self._spec_round(sched, cache, tok, pos, act,
                                             bt, lambda: clock() - t0,
                                             retire)
                    steps += 1
                    continue
                if multi:
                    # one horizon-M round: chained from the device carry
                    # when the host hasn't touched its mirrors since the
                    # last round (zero carry upload in steady state),
                    # rebuilt from the mirrors otherwise
                    src = carry if carry is not None else {
                        "token": tok, "pos": pos, "active": act,
                        "remaining": rem}
                    with TraceAnnotation("decode.dispatch",
                                         n_active=int(act.sum())):
                        toks_dev, carry, cache = ex.decode_multi(
                            src["token"], src["pos"], src["active"],
                            src["remaining"], cache, block_table=bt,
                            eos_id=self.eos_id)
                    steps += M
                    rounds += 1
                    prev, pending = pending, toks_dev
                    if prev is not None:
                        # double buffer: the device is already running
                        # round N+1 while the host replays round N here
                        process_toks(prev)
                    if not pipeline:
                        drain()
                    continue
                with TraceAnnotation("decode.dispatch",
                                     n_active=int(act.sum())):
                    new_tok, cache = ex.decode(tok, pos, act, cache,
                                               block_table=bt)
                new_tok = self._read(new_tok, "decode.wait")
                steps += 1
                now = clock() - t0
                n_retired = 0
                with TraceAnnotation("decode.emit") as ann:
                    for slot in list(sched.running):
                        if not act[slot]:
                            continue        # chunking slot: masked
                        req = sched.running[slot]
                        t = int(new_tok[slot])
                        req.emit(t, now)
                        pos[slot, 0] += 1
                        if t == self.eos_id or len(req.tokens) >= req.max_new:
                            act[slot] = False
                            retire(slot, now)
                            n_retired += 1
                        else:
                            tok[slot, 0] = t
                    ann.set_metadata(n_retired=n_retired)
        drain()      # a trailing all-masked round can still be in flight
        wall = clock() - t0
        window.__exit__(None, None, None)
        stats = summarize(sched.finished, wall,
                          mode="continuous" if continuous else "static")
        stats["decode_steps"] = steps
        stats["decode_compiles"] = self.decode_cache_size()
        stats["slot_shards"] = ex.n_slot_shards
        stats["cache_layout"] = "paged" if self.paged else "dense"
        stats["cache_bytes"] = self.cache_bytes
        stats["peak_concurrency"] = peak_running
        # Host-overlap accounting (DESIGN.md §3 "Multi-step decode & host
        # overlap"): every host-BLOCKING device->host materialization the
        # loop paid (decode steps / multi-step rounds / spec draft+verdict
        # pairs / prefill first-token reads).  The per-token ratio is the
        # serve_bench §7 gate: horizon M cuts it ~Mx.
        stats["host_syncs"] = self._syncs
        stats["host_syncs_per_token"] = round(
            self._syncs / max(stats["tokens"], 1), 4)
        # the host work that, at horizon 1, lies on the device's critical
        # path: the window less its blocking reads and arrival sleeps
        stats["host_busy_s"] = wall - self._wait_s - sleep_s
        stats["loop_iters"] = loop_iters
        stats["decode_horizon"] = self.decode_horizon
        if multi:
            stats["decode_rounds"] = rounds
        if self.spec:
            rounds = int(sum(r.spec_rounds for r in sched.finished))
            accepted = int(sum(r.spec_accepted for r in sched.finished))
            stats["speculative"] = {
                "draft_bits": self.spec[0],
                "k": self.spec[1],
                "rounds": rounds,
                "accepted_draft_tokens": accepted,
                "mean_accepted": (round(accepted / rounds, 3)
                                  if rounds else 0.0),
                "spec_compiles": ex.spec_cache_sizes(),
            }
        # prefill accounting: the per-request counter, not len(prompt) -
        # hits — chunked pieces, preemption restores, and cumulative
        # re-admission hits all move the real forwarded count away from
        # that difference (which can even go negative once hit accounting
        # is cumulative across re-admissions)
        n_done = max(len(sched.finished), 1)
        prefilled = int(sum(r.prefilled_tokens for r in sched.finished))
        stats["prefilled_tokens"] = prefilled
        stats["prefilled_tokens_mean"] = round(prefilled / n_done, 2)
        stats["prefix_tokens_reused"] = int(sum(r.prefix_hit_tokens
                                                for r in sched.finished))
        if self.prefill_chunk:
            stats["prefill_chunks"] = n_chunks
        if self.slo is not None:
            stats["slo"] = {
                "aging_s": self.slo.aging_s,
                "reserve_frac": self.slo.reserve_frac,
                "classes": slo_report(sched.finished, self.slo),
            }
        if self.paged:
            stats["block_size"] = self.block_size
            stats["n_blocks"] = ex.n_blocks
            stats["paged_attn_route"] = ex.paged_attn_route
            # DeviceBlockTable transfer accounting: reuses are dispatches
            # that moved ZERO table bytes host->device
            stats["block_table_transfers"] = dict(bt.stats)
            stats["peak_blocks_in_use"] = blocks.high_watermark
            stats["block_util_pct"] = round(
                100.0 * blocks.high_watermark / max(ex.n_blocks, 1), 1)
            if prefix is not None:
                stats["prefix_cache"] = prefix.stats()
                # teardown: with refcounts, "allocator back to initial"
                # includes draining the LRU — after this, blocks_free_end
                # must equal n_blocks again (leak check in tests)
                prefix.drain(blocks)
            stats["blocks_free_end"] = blocks.free_count
        self.final_state = {"cache": cache, "block_rows": kept_rows}
        return sched.finished, stats

    def decode_cache_size(self) -> int:
        """Compiled decode-side executable count for the engine's ACTIVE
        decode path: the horizon-M round when multi-step decode is on
        (the single-step twin is never traced then — warmup asserts it),
        else the classic single step."""
        if self.decode_horizon > 1:
            return self.executor.decode_multi_cache_size()
        return self.executor.decode_cache_size()


def build_server(args) -> Tuple[Server, object]:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    cfg = dataclasses.replace(
        cfg,
        cache_layout=getattr(args, "cache_layout", "auto") or "auto",
        cache_block_size=int(getattr(args, "block_size", 0)
                             or cfg.cache_block_size),
        prefix_cache=(getattr(args, "prefix_cache", "off") == "on"))
    cfg.resolved_cache_layout        # validate the layout/family combo early
    cfg.prefix_cache_enabled         # ...and the prefix-cache combo
    model = build_model(cfg)
    # --qat-precondition emulates a checkpoint TRAINED with the quantizer in
    # the loop (the paper's QAT flow): weights snap to the psi`pre` grid
    # before the serving quantization.  Random-init weights have logit
    # margins smaller than low-bit quantization noise, so without this the
    # speculative draft's acceptance rate is ~0 — a trained checkpoint's
    # margins are what make self-speculation pay (DESIGN.md).
    pre = int(getattr(args, "qat_precondition", 0) or 0)
    policy = parse_policy(getattr(args, "quant_policy", None))
    bits = None
    if args.quant != "none":
        _, bits = parse_quant_mode(args.quant)
    # Built a layer at a time, never as a float tree (Model.init_serving):
    # pack=True only bit-plane-packs sub-byte leaves, so uniform psi8
    # stays plain int8 codes while psi5/psi4/... leaves shrink to
    # fmt.bits/8 bytes per weight.
    params = model.init_serving(jax.random.PRNGKey(0), bits, pack=True,
                                policy=policy, precondition=pre)
    if args.quant != "none" or policy:
        # quant_mode drives the float-leaf (QAT) path only; for serving it
        # records the uniform format (or the policy default) for logging.
        mode = args.quant
        if mode == "none" and policy and policy.get("default"):
            mode = f"psi{policy['default']}"
        cfg = dataclasses.replace(cfg, quant_mode=mode)
    spec = parse_spec_spec(getattr(args, "speculative", None))
    if spec:
        kind, sbits = parse_quant_mode(args.quant)
        if kind != "psi" or sbits <= spec[0]:
            raise ValueError(
                f"--speculative {spec[0]}:{spec[1]} derives the draft from "
                f"the PSI serving codes, so it needs a WIDER serving format "
                f"(--quant psiN with N > {spec[0]}); got --quant "
                f"{args.quant}")
    # Cache extent must cover the *bucketed* prefill plus the decode budget,
    # or the ring layout would silently drop the prompt head.  A shared
    # system prompt prepends to every request's unique tail.  Speculative
    # rounds write k positions regardless of remaining budget: +k-1.
    longest = (args.prompt_len + args.prompt_jitter
               + getattr(args, "shared_prefix_len", 0))
    prompt_pad = -(-longest // PREFILL_BUCKET) * PREFILL_BUCKET
    mesh = parse_mesh_spec(getattr(args, "mesh", None))
    # Round the cache extent to the block grid for EVERY layout: a paged
    # Server rounds anyway, and giving dense the same extent keeps the two
    # layouts' attention shapes — and therefore their greedy tokens —
    # bit-identical for the serve_bench cross-layout assertion.
    max_seq = prompt_pad + args.max_new + 8 + (spec[1] - 1 if spec else 0)
    chunk = int(getattr(args, "prefill_chunk", 0) or 0)
    slo = parse_slo_spec(getattr(args, "slo", "off") or "off")
    if slo is not None or chunk:
        # restore headroom: a preempted request re-prefills prompt +
        # generated in one bucketed piece, whose padded extent can exceed
        # the prompt-only pad by up to one bucket
        max_seq += PREFILL_BUCKET
    bsz = cfg.cache_block_size
    max_seq = -(-max_seq // bsz) * bsz
    server = Server(cfg, params, max_batch=args.max_batch, max_seq=max_seq,
                    eos_id=args.eos_id, mesh=mesh,
                    n_blocks=getattr(args, "cache_blocks", None),
                    speculative=spec, prefill_chunk=chunk, slo=slo,
                    decode_horizon=int(getattr(args, "decode_horizon", 1)
                                       or 1))
    return server, cfg


def trace_from_args(args, cfg):
    """One arrival trace from the shared CLI flags (used by both the serve
    CLI and benchmarks/serve_bench so the two can never drift).
    ``--trace-seed`` decouples the arrival RNG from ``--seed`` (which also
    fixes the weights) so traffic can vary against a fixed checkpoint;
    ``--priority-mix`` draws each request's SLO class from the --slo
    policy's classes with the given weights."""
    seed = getattr(args, "trace_seed", None)
    if seed is None:
        seed = args.seed
    mix = None
    pm = getattr(args, "priority_mix", None)
    if pm:
        slo = parse_slo_spec(getattr(args, "slo", "off") or "off")
        if slo is None:
            raise ValueError("--priority-mix draws classes from the --slo "
                             "policy; pass --slo as well")
        mix = slo.mix([float(x) for x in pm.split(",")])
    return poisson_trace(args.requests, rate_rps=args.arrival_rate,
                         prompt_len=args.prompt_len,
                         max_new=args.max_new, min_new=args.min_new,
                         prompt_jitter=args.prompt_jitter,
                         shared_prefix_len=getattr(args, "shared_prefix_len",
                                                   0),
                         vocab_size=cfg.vocab_size, seed=int(seed),
                         priority_mix=mix)


def _positive_rate(s: str) -> float:
    """--arrival-rate parser: the trace generator divides by the rate, so 0
    is a ZeroDivisionError waiting to happen and a negative rate would run
    time backwards — reject both at the CLI boundary."""
    v = float(s)
    if not v > 0:
        raise argparse.ArgumentTypeError(
            f"--arrival-rate must be > 0 requests/s, got {s!r}")
    return v


def add_serve_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--quant", default="psi8",
                    choices=list(serving_mode_choices()),
                    help="uniform PSI serving width (any registered "
                         "PsiFormat; sub-byte widths bit-plane pack)")
    ap.add_argument("--quant-policy", default=None,
                    help='per-layer mixed precision, e.g. '
                         '"embed=8,w_down=4,default=5" — names match '
                         'terminal weight leaves, "default" covers the '
                         'rest, 0 keeps a leaf in float.  Overrides '
                         '--quant where it matches.')
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=4,
                    help="decode slots (the fixed decode batch dimension)")
    ap.add_argument("--arrival-rate", type=_positive_rate, default=1000.0,
                    help="Poisson arrival rate, requests/s, > 0 (the "
                         "reduced CPU model decodes ~3k tok/s, so this "
                         "saturates it)")
    ap.add_argument("--max-new", type=int, default=48,
                    help="per-request decode budgets are drawn from "
                         "[min-new, max-new]")
    ap.add_argument("--min-new", type=int, default=1)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--prompt-jitter", type=int, default=0,
                    help="+- this many tokens of per-request prompt-length "
                         "variation (exercises heterogeneous admission)")
    ap.add_argument("--cache-layout", default="auto",
                    choices=["auto", "dense", "paged"],
                    help="decode-cache layout (DESIGN.md §3): paged = block "
                         "pool + per-slot block tables (default for "
                         "full-attention families); dense = per-slot slabs "
                         "(required for SSM/hybrid/SWA/encdec state)")
    ap.add_argument("--block-size", type=int, default=0,
                    help="positions per paged cache block (0 = config "
                         "default, 16)")
    ap.add_argument("--cache-blocks", type=int, default=None,
                    help="usable pool blocks for --cache-layout paged "
                         "(default: dense-equivalent capacity, "
                         "max_batch * ceil(max_seq / block_size); smaller "
                         "values trade capacity for memory and gate "
                         "admission on block availability)")
    ap.add_argument("--prefix-cache", default="off", choices=["on", "off"],
                    help="shared-prefix block reuse over the paged pool "
                         "(DESIGN.md §3): admission serves the longest "
                         "cached block-aligned prompt prefix out of "
                         "ref-counted blocks and prefills only the suffix. "
                         "Requires --cache-layout paged (the full-attention "
                         "default).")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="prepend ONE fixed random prefix of this many "
                         "tokens to every prompt (the shared-system-prompt "
                         "traffic shape; --prompt-len then sizes the "
                         "unique tail)")
    ap.add_argument("--speculative", default=None, metavar="BITS:K",
                    help="self-speculative decoding (DESIGN.md): draft K "
                         "tokens per round with a psiBITS view of the "
                         "serving checkpoint (derived from the stored "
                         "codes — no second model), then verify all K in "
                         "one target-width pass; greedy acceptance keeps "
                         "outputs token-identical to plain decode.  e.g. "
                         "\"3:4\".  Requires --quant psiN with N > BITS, "
                         "the paged cache layout, and K <= --block-size.")
    ap.add_argument("--qat-precondition", type=int, default=0, metavar="BITS",
                    help="snap the random-init weights to the psiBITS grid "
                         "before serving quantization (emulates a QAT-"
                         "trained checkpoint; 0 = off).  Random weights' "
                         "logit margins drown in low-bit noise, so "
                         "speculative acceptance studies need this.")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="split prompt prefills into pieces of this many "
                         "tokens, interleaved with decode steps so a long "
                         "admission stops stalling running requests (0 = "
                         "off; rounded UP to lcm(block-size, prefill "
                         "bucket)).  Requires the paged layout + plain "
                         "RoPE; tokens stay identical to unchunked serving.")
    ap.add_argument("--slo", default="off",
                    help='SLO scheduling (DESIGN.md §3 "SLO scheduling"): '
                         '"off", "default" (interactive/standard/batch), '
                         'or "name:prio:ttft:itl,..." custom classes; '
                         'append "@aging=S" / "@reserve=F" knobs.  Turns '
                         'on aged-priority admission, optimistic block '
                         'reservation, and preemption with prefix-cache-'
                         'backed restore.  Requires the paged layout + '
                         'plain RoPE.')
    ap.add_argument("--trace-seed", type=int, default=None,
                    help="RNG seed for the arrival trace only (default: "
                         "--seed), so traffic varies against fixed weights")
    ap.add_argument("--priority-mix", default=None, metavar="W1,W2,...",
                    help="per-class arrival weights, one per --slo class "
                         "in declaration order; each request draws its "
                         "class i.i.d. from the normalized mix")
    ap.add_argument("--decode-horizon", type=int, default=1, metavar="M",
                    help='multi-step decode (DESIGN.md §3 "Multi-step '
                         'decode & host overlap"): fuse M decode steps '
                         'into ONE on-device round (lax.scan) with EOS/'
                         'max-new retirement masked in-kernel, and let the '
                         'host process each round\'s tokens while the '
                         'device runs the next — ~Mx fewer host syncs per '
                         'token, bit-token-identical to M=1.  Does not '
                         'compose with --speculative (hard error).')
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="-1 disables EOS retirement")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None,
                    help='serving mesh "DATAxMODEL" (e.g. 4x2); decode '
                         'slots partition over the data axis, weights TP '
                         'over model.  Default/1x1: single-device path')


def main():
    ap = argparse.ArgumentParser()
    add_serve_args(ap)
    ap.add_argument("--mode", default="continuous",
                    choices=["continuous", "static", "both"])
    args = ap.parse_args()
    use_compile_cache()

    server, cfg = build_server(args)
    modes = (["continuous", "static"] if args.mode == "both"
             else [args.mode])
    for mode in modes:
        trace = trace_from_args(args, cfg)
        done, stats = server.serve(trace, continuous=(mode == "continuous"))
        cache_info = f"cache {stats['cache_layout']}"
        if stats["cache_layout"] == "paged":
            cache_info += (f" ({stats['n_blocks']}x{stats['block_size']} "
                           f"blocks, {stats['paged_attn_route']} read, "
                           f"peak util {stats['block_util_pct']}%)")
        if "prefix_cache" in stats:
            pc = stats["prefix_cache"]
            cache_info += (f" | prefix hit rate {pc['hit_rate']:.2f}, "
                           f"{stats['prefix_tokens_reused']} tok reused / "
                           f"{stats['prefilled_tokens']} prefilled")
        if "speculative" in stats:
            sp = stats["speculative"]
            cache_info += (f" | spec psi{sp['draft_bits']} k={sp['k']}: "
                           f"{stats['accepted_per_step']:.2f} accepted/"
                           f"round, draft {stats['draft_overhead_s']:.3f}s")
        if stats.get("preemptions") or "slo" in stats:
            cache_info += (f" | preemptions {stats['preemptions']}, "
                           f"restores "
                           f"{stats.get('prefix_cache', {}).get('restores', 0)}")
        if stats["decode_horizon"] > 1:
            cache_info += (f" | horizon {stats['decode_horizon']}: "
                           f"{stats['decode_rounds']} rounds, "
                           f"{stats['host_syncs_per_token']:.3f} syncs/tok")
        print(f"[{mode}] served {stats['n_requests']} requests: "
              f"{stats['tokens']} tokens in {stats['wall_s']:.3f}s = "
              f"{stats['tok_per_s']:.1f} tok/s | "
              f"latency p50 {stats['p50_latency_s'] * 1e3:.0f}ms "
              f"p99 {stats['p99_latency_s'] * 1e3:.0f}ms | "
              f"ttft p50 {stats['p50_ttft_s'] * 1e3:.0f}ms | "
              f"decode compiles {stats['decode_compiles']} | "
              f"slot shards {stats['slot_shards']} | {cache_info}")
        if "slo" in stats:
            for name, c in stats["slo"]["classes"].items():
                print(f"  [{name}] n={c['n_requests']} "
                      f"ttft p99 {c['p99_ttft_s'] * 1e3:.0f}ms "
                      f"(attain {c['ttft_attainment']:.2f} of "
                      f"{c['ttft_deadline_s'] * 1e3:.0f}ms) | "
                      f"itl p99 {c['p99_itl_s'] * 1e3:.0f}ms "
                      f"(attain {c['itl_attainment']:.2f}) | "
                      f"preemptions {c['preemptions']}")
        for r in done[:2]:
            print(f"  req {r.rid}: slot {r.slot}, {len(r.tokens)} tokens, "
                  f"{r.out[:10].tolist()}...")


if __name__ == "__main__":
    main()
