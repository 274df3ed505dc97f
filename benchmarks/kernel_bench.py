"""Microbenchmark of the psi_matmul + paged-attention kernels (CPU oracle
path timing + the analytic HBM-traffic advantage that is each kernel's
reason to exist).

Wall-times here are CPU-oracle numbers (the container has no TPU); the
roofline-relevant quantities are analytic: the weight-byte column (bf16
2.0 B/w, PSI-INT8 1.0 B/w, PSI-INT5 0.625 B/w) and, for the decode-shaped
sweep (M in {1, 4, 8, 16} = active slots), the padded-MAC count the
small-M tile dispatch (``psi_matmul.pick_bm``) issues versus the fixed
128-row tile it replaced.

The paged-decode sweep (B x n_bt x {bf16, int8} pools) reports, per
config, the bytes of dense gathered/dequantized temporaries the old read
path materialized per decode step per layer (``gathered_bytes_eliminated``
— the fused kernel's win), the pool bytes the kernel streams instead, the
oracle-vs-gather agreement, and (with ``--kernel-check``, the CI
kernel-bench leg) the interpret-mode Pallas kernel's max error against the
oracle.  ``python -m benchmarks.kernel_bench --out BENCH_kernel.json``
writes the machine-readable artifact CI asserts on.

``--tile-sweep OUT.json`` runs on a TPU only: it times the int8 body of
``psi_matmul`` at qwen3-8b's decode GEMMs (M = 32 slots, bf16) for each
legal (bn, bk) and prints microseconds per call and the share of the HBM
floor (the codes' bytes at the chip's bandwidth) — the measurement
``psi_matmul.pick_tiles`` was fixed from.  Without a TPU it exits 2.
"""
from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import psi
from repro.kernels import paged_attention as pa
from repro.kernels import psi_matmul as pk
from repro.kernels import ref


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6


# paged-decode sweep geometry (reduced-config-scale heads, serving-scale
# block size); the traffic model is per decode step per layer.
PAGED_BS, PAGED_HQ, PAGED_HKV, PAGED_HD = 16, 8, 2, 64


def _paged_case(rng, B, n_bt, quantized):
    bs, hq, hkv, hd = PAGED_BS, PAGED_HQ, PAGED_HKV, PAGED_HD
    N = B * n_bt + B                                   # + per-slot scratch
    q = jnp.asarray(rng.normal(size=(B, hq, hd)), jnp.bfloat16)
    if quantized:
        kp = jnp.asarray(rng.integers(-127, 128, size=(N, bs, hkv, hd)),
                         jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, size=(N, bs, hkv, hd)),
                         jnp.int8)
        ks = jnp.asarray(rng.uniform(1e-3, 0.05, size=(N, bs, hkv, 1)),
                         jnp.float32)
        vs = jnp.asarray(rng.uniform(1e-3, 0.05, size=(N, bs, hkv, 1)),
                         jnp.float32)
    else:
        kp = jnp.asarray(rng.normal(size=(N, bs, hkv, hd)), jnp.bfloat16)
        vp = jnp.asarray(rng.normal(size=(N, bs, hkv, hd)), jnp.bfloat16)
        ks = vs = None
    # permuted physical blocks, fully allocated, full-length decode (the
    # worst-case gather the kernel eliminates)
    bt = jnp.asarray(rng.permutation(B * n_bt).reshape(B, n_bt), jnp.int32)
    pos = jnp.full((B,), n_bt * bs - 1, jnp.int32)
    return q, kp, vp, bt, pos, ks, vs


def paged_sweep(kernel_check=False):
    """B x n_bt x pool-dtype sweep of the paged-decode read side.  Returns
    (csv_rows, json_records)."""
    rows, records = [], []
    bs, hkv, hd = PAGED_BS, PAGED_HKV, PAGED_HD
    print("paged-decode read side (CPU oracle vs dense gather; bytes = "
          "dense temporaries the fused kernel eliminates per step/layer):")
    for quantized in (False, True):
        pool = "int8" if quantized else "bf16"
        for n_bt in (4, 16, 64):
            for B in (1, 4, 8, 16):
                rng = np.random.default_rng(hash((B, n_bt, quantized))
                                            % 2 ** 31)
                args = _paged_case(rng, B, n_bt, quantized)
                t_ref = _time(pa.paged_attention_ref, *args)
                t_gat = _time(pa.paged_attention_gather, *args)
                o_ref = np.asarray(pa.paged_attention_ref(*args), np.float32)
                o_gat = np.asarray(pa.paged_attention_gather(*args),
                                   np.float32)
                max_err = float(np.abs(o_ref - o_gat).max())
                # greedy-proxy token identity: per slot, the argmax over the
                # flattened head output must agree between the engine's
                # routed oracle and the pre-kernel gather math
                tok_ok = bool((o_ref.reshape(B, -1).argmax(-1)
                               == o_gat.reshape(B, -1).argmax(-1)).all())
                kerr = None
                if kernel_check and B * n_bt <= 64:     # bounded interpret
                    o_ker = np.asarray(pa.paged_attention_pallas(
                        *args, interpret=True), np.float32)
                    kerr = float(np.abs(o_ker - o_ref).max())
                elim = pa.gathered_bytes(B, n_bt, bs, hkv, hd,
                                         quantized=quantized)
                stream = pa.streamed_bytes(B * n_bt, bs, hkv, hd,
                                           quantized=quantized)
                name = f"paged_decode_{pool}_B{B}_nbt{n_bt}"
                print(f"  {pool} B={B:<3d} n_bt={n_bt:<3d} "
                      f"oracle {t_ref:7.0f} us  gather {t_gat:7.0f} us  "
                      f"eliminated {elim / 1e3:8.1f} KB  "
                      f"streamed {stream / 1e3:8.1f} KB"
                      + (f"  kernel_err {kerr:.3g}" if kerr is not None
                         else ""))
                rows.append((name, t_ref,
                             f"gathered_bytes_eliminated={elim};"
                             f"streamed_bytes={stream};"
                             f"token_identical={tok_ok}"))
                records.append({
                    "name": name, "B": B, "n_bt": n_bt, "pool": pool,
                    "block_size": bs, "n_kv": hkv, "head_dim": hd,
                    "t_oracle_us": round(t_ref, 1),
                    "t_gather_us": round(t_gat, 1),
                    "gathered_bytes_eliminated": elim,
                    "streamed_bytes": stream,
                    "oracle_gather_max_err": max_err,
                    "token_identical": tok_ok,
                    "kernel_vs_oracle_max_err": kerr,
                })
    return rows, records


def run():
    rows = []
    M, K, N = 256, 2048, 2048
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32))
    q8 = psi.quantize_weights(w, 8, axis=0)
    q5 = psi.quantize_weights(w, 5, axis=0)
    planes = psi.pack_int5(q5.codes)

    f_bf16 = jax.jit(lambda x, w: x @ w)
    f_int8 = jax.jit(lambda x, c, s: ref.psi_matmul_int8_ref(x, c, s))
    f_int5 = jax.jit(lambda x, p, s: ref.psi_matmul_int5_ref(x, p, s))

    t_b = _time(f_bf16, x, w)
    t_8 = _time(f_int8, x, q8.codes, q8.scale.reshape(-1))
    t_5 = _time(f_int5, x, planes, q5.scale.reshape(-1))
    wb = K * N
    print(f"psi_matmul {M}x{K}x{N} (CPU oracle timings; bytes = HBM model):")
    print(f"  bf16      {t_b:9.0f} us   weight bytes {2.0 * wb / 1e6:7.2f} MB")
    print(f"  psi-int8  {t_8:9.0f} us   weight bytes {1.0 * wb / 1e6:7.2f} MB (2.0x less)")
    print(f"  psi-int5  {t_5:9.0f} us   weight bytes {0.625 * wb / 1e6:7.2f} MB (3.2x less)")
    rows.append(("kernel_bf16", t_b, f"bytes={2.0*wb:.0f}"))
    rows.append(("kernel_psi8", t_8, f"bytes={1.0*wb:.0f}"))
    rows.append(("kernel_psi5", t_5, f"bytes={0.625*wb:.0f}"))

    # Decode-shaped sweep: M = active decode slots.  Wall time is the CPU
    # oracle; the dispatch-relevant column is padded MACs — what the TPU
    # kernel grid actually issues with the old fixed bm=128 tile vs the
    # small-M tile ops.psi_matmul_2d now picks (>=2x fewer at M<=16 is the
    # acceptance bar; at M=1/f32 it is 16x).
    print(f"decode-shaped dispatch (K={K}, N={N}; M = active slots):")
    for M in (1, 4, 8, 16):
        xm = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
        t_m = _time(f_int8, xm, q8.codes, q8.scale.reshape(-1))
        bm = pk.pick_bm(M, jnp.float32)
        macs_old = pk.padded_macs(M, K, N)            # fixed 128-row tile
        macs_new = pk.padded_macs(M, K, N, bm=bm)
        ratio = macs_old / macs_new
        print(f"  M={M:<3d} bm {pk.DEFAULT_BM}->{bm:<3d} "
              f"padded MACs {macs_old / 1e6:7.1f}M -> {macs_new / 1e6:6.1f}M "
              f"({ratio:4.1f}x fewer)  oracle {t_m:7.0f} us")
        rows.append((f"kernel_decode_m{M}", t_m,
                     f"bm={bm};padded_macs={macs_new};"
                     f"macs_vs_128tile={ratio:.1f}x"))

    # paged-decode read-side sweep (no interpret-mode kernel check here to
    # keep `python -m benchmarks.run` fast; the CI kernel-bench leg runs
    # `-m benchmarks.kernel_bench --kernel-check --out BENCH_kernel.json`)
    prows, _ = paged_sweep(kernel_check=False)
    rows.extend(prows)
    return rows


# qwen3-8b's decode GEMMs (K, N): wq/wo, wk/wv, w_gate/w_up, w_down, lm_head
SWEEP_SHAPES = [(4096, 4096), (4096, 1024), (4096, 12288), (12288, 4096),
                (4096, 151936)]
SWEEP_BN = (128, 256, 512, 1024, 2048)
SWEEP_BK = (512, 1024, 2048, 4096, 12288)
HBM_BYTES_PER_S = 819e9          # TPU v5e (Google Cloud, "TPU v5e")


def _us_per_call(fn, args, reps):
    """Device microseconds per call of ``fn(x, *rest)``: ``reps`` calls
    chained in one program (each call's x depends on the last output, so
    none is hoisted), less the same loop around an empty body."""
    def looped(body):
        def run(x, *rest):
            def step(_, s):
                return body(x + s.astype(x.dtype), *rest) * 1e-30
            return jax.lax.fori_loop(0, reps, step, jnp.float32(0))
        return jax.jit(run)

    def best(f):
        jax.block_until_ready(f(*args))
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            times.append(time.perf_counter() - t0)
        return min(times)

    full = looped(lambda *a: fn(*a)[0, 0].astype(jnp.float32))
    empty = looped(lambda xi, *_: jnp.sum(xi, dtype=jnp.float32))
    return (best(full) - best(empty)) / reps * 1e6


def tile_sweep(M=32, dtype=jnp.bfloat16):
    """(bn, bk) sweep of the int8 body at the decode shapes; on a TPU."""
    if jax.default_backend() != "tpu":
        raise SystemExit(2)
    itemsize = jnp.dtype(dtype).itemsize
    bm = pk.pick_bm(M, dtype)
    rng = np.random.default_rng(0)
    records = []
    for K, N in SWEEP_SHAPES:
        x = jnp.asarray(rng.normal(size=(M, K)), dtype)
        codes = jnp.asarray(rng.integers(-127, 128, size=(K, N),
                                         dtype=np.int8))
        scale = jnp.asarray(rng.uniform(1e-3, 1e-2, size=(N,)), jnp.float32)
        floor_us = K * N / HBM_BYTES_PER_S * 1e6
        picked = pk.pick_tiles(M, K, N, dtype)
        reps = max(20, min(400, int(20000 / floor_us)))
        tiles = [(bm, 128, 128), picked] + [
            (bm, bn, bk) for bn in SWEEP_BN for bk in SWEEP_BK
            if bn <= N and K % bk == 0
            and pk._int8_vmem_bytes(bm, bn, bk, itemsize) <= pk._SCOPED_VMEM]
        want = np.asarray(ref.psi_matmul_int8_ref(x, codes, scale),
                          np.float32)
        for tile in dict.fromkeys(tiles):
            fn = functools.partial(pk.psi_matmul_codes, bm=tile[0],
                                   bn=tile[1], bk=tile[2])
            rec = {"K": K, "N": N, "bm": tile[0], "bn": tile[1],
                   "bk": tile[2], "picked": tile == picked,
                   "floor_us": round(floor_us, 2)}
            try:
                us = _us_per_call(fn, (x, codes, scale), reps)
                got = np.asarray(fn(x, codes, scale), np.float32)
                rec.update(us=round(us, 2),
                           floor_pct=round(100 * floor_us / us, 1),
                           max_err_vs_ref=float(np.abs(got - want).max()))
            except Exception as e:               # noqa: BLE001 — recorded
                rec["error"] = str(e).splitlines()[0][:160]
            records.append(rec)
            print(json.dumps(rec), flush=True)
    dev = jax.devices()[0]
    return {"device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": jax.device_count()},
            "M": M, "dtype": jnp.dtype(dtype).name, "rows": records}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None,
                    help="write the paged-decode sweep as machine-readable "
                         "JSON (BENCH_kernel.json)")
    ap.add_argument("--kernel-check", action="store_true",
                    help="also run the interpret-mode Pallas kernel against "
                         "the oracle on the bounded-size configs")
    ap.add_argument("--tile-sweep", default=None, metavar="OUT.json",
                    help="on a TPU: time psi_matmul's int8 body per (bn, bk) "
                         "at qwen3-8b's decode GEMMs and write the table")
    args = ap.parse_args(argv)
    if args.tile_sweep is not None:
        table = tile_sweep()
        with open(args.tile_sweep, "w") as f:
            json.dump(table, f, indent=1)
        print(f"wrote {args.tile_sweep}: {len(table['rows'])} tiles")
        return
    if args.out is None:
        run()
        return
    _, records = paged_sweep(kernel_check=args.kernel_check)
    with open(args.out, "w") as f:
        json.dump({"rows": records}, f, indent=1)
    print(f"wrote {args.out}: {len(records)} paged-decode configs")


if __name__ == "__main__":
    main()
