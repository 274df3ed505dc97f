"""Smoke run of the serving path on TPU: qwen3-8b at its published widths
(36 layers, d_model 4096, 32/8 heads, d_ff 12288, vocab 151936) with psi8
weights, driven through the normal entry points — the serve CLI's argument
parser, ``build_server`` and ``trace_from_args`` — in one process.

One chip (no arguments): serve one Poisson trace (12 requests, prompts of
64-128 tokens, 16-32 new tokens, 8 decode slots, paged cache) in continuous
mode, then check, failing with a non-zero exit on the first miss:

  * JAX's first device is a TPU (there is no CPU fallback);
  * the paged-decode read route is ``pallas`` and the compiled decode step
    contains the Pallas kernels (``tpu_custom_call``);
  * the decode step compiled exactly once and every request finished with
    its full token budget;
  * the Pallas kernels agree with their oracles on the served parameters
    at real widths: ``psi_matmul`` on layer 0's ``wq`` and ``w_down`` and on
    the ``lm_head``, at M = 8 and M = 128, and the paged-decode kernel on
    layer 0's live pool and block tables after the serve (tolerances
    below);
  * the logits of a full forward over the served prompts are finite.

``--chips 4`` runs only the sharded path and its comparison: the same trace
on a 2x2 (data x model) mesh and on a 1x1 mesh on device 0, asserting that
their tokens are identical.

The last line of stdout is ``{"ok": true, "device": {"platform": ...,
"kind": ..., "count": ...}}``; the lines before it are a smoke run's
record, not benchmark results.

  python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.quantizer import quantized_bytes  # noqa: E402
from repro.kernels import ops, paged_attention, ref  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.serve import (add_serve_args, build_server,  # noqa: E402
                                format_mfu, trace_from_args)
from repro.perf.roofline_model import device_peaks  # noqa: E402

SERVE_ARGS = ["--arch", "qwen3-8b", "--quant", "psi8", "--max-batch", "8",
              "--requests", "12", "--prompt-len", "96",
              "--prompt-jitter", "32", "--min-new", "16", "--max-new", "32",
              "--arrival-rate", "1000", "--seed", "0"]

# Kernel-vs-oracle bound: max |kernel - oracle| / max |oracle|.  Both
# matmul sides form exact products of bf16 activations and int8 codes and
# sum them in f32 in a different order, then round to bf16 (2^-8 relative);
# the paged-decode oracle also rounds its softmax weights to bf16 before
# the value product, where the kernel keeps f32.
MATMUL_TOL = 1e-2
ATTN_TOL = 2e-2


def _serve_args(*extra):
    ap = argparse.ArgumentParser()
    add_serve_args(ap)
    return ap.parse_args(SERVE_ARGS + list(extra))


def _check(ok, what):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")
    print(f"  ok: {what}")


def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _serve(args):
    """Build the server and serve the CLI trace once in continuous mode;
    returns (server, cfg, finished requests, stats)."""
    t0 = time.perf_counter()
    server, cfg = build_server(args)
    jax.block_until_ready(server.executor.params)
    setup_s = time.perf_counter() - t0
    trace = trace_from_args(args, cfg)
    t0 = time.perf_counter()
    server.warmup(trace)
    compile_s = time.perf_counter() - t0
    done, stats = server.serve(trace_from_args(args, cfg), continuous=True,
                               warmup=False)
    print(f"  set-up {setup_s:.3f}s (weights built and placed), compile "
          f"{compile_s:.3f}s (warmup), serving weights "
          f"{quantized_bytes(server.executor.params)} bytes")
    keys = ("n_requests", "tokens", "wall_s", "tok_per_s", "p50_ttft_s",
            "p99_ttft_s", "p50_itl_s", "p99_itl_s", "decode_steps",
            "decode_compiles", "slot_shards", "paged_attn_route",
            "peak_concurrency")
    smoke = {k: stats.get(k) for k in keys}
    smoke["mfu"] = format_mfu(stats["mfu"])
    print(f"  serve stats (smoke run, not a benchmark result): "
          f"{json.dumps(smoke)}")
    _check(stats["decode_compiles"] == 1,
           f"decode compiled once ({stats['decode_compiles']})")
    _check(len(done) == len(trace)
           and all(len(r.tokens) == r.max_new for r in done),
           f"all {len(trace)} requests finished with their full budget")
    return server, cfg, done, stats


def _decode_program_text(ex):
    """Lowered text of the Executor's decode entry point."""
    B = ex.max_batch
    tok = jnp.zeros((B, 1), jnp.int32)
    act = jnp.zeros((B,), bool)
    bt = jnp.full((B, ex.n_bt), -1, jnp.int32)
    return ex._decode.lower(ex.params, tok, tok, act, bt,
                            ex.init_cache()).as_text()


def _check_matmuls(params):
    rng = np.random.default_rng(0)
    layer0 = params["stack"]["groups"]["b0_attn"]
    weights = {
        "wq": jax.tree_util.tree_map(lambda a: a[0], layer0["attn"]["wq"]),
        "w_down": jax.tree_util.tree_map(lambda a: a[0],
                                         layer0["mlp"]["w_down"]),
        "lm_head": params["lm_head"],
    }
    kernel = jax.jit(ops.psi_matmul)

    @jax.jit
    def oracle(x, codes, scale):
        with jax.default_matmul_precision("highest"):
            return ref.psi_matmul_codes_ref(x, codes, scale)

    for name, qt in weights.items():
        for M in (8, 128):
            x = jnp.asarray(rng.standard_normal((M, qt.data.shape[0])),
                            jnp.bfloat16)
            err = _rel_err(kernel(x, qt), oracle(x, qt.data, qt.scale))
            _check(err <= MATMUL_TOL,
                   f"psi_matmul {name} {tuple(qt.data.shape)} M={M}: "
                   f"rel err {err:.3e} <= {MATMUL_TOL}")


def _check_paged_attention(server, done):
    """Kernel vs oracle on layer 0's live pool after the serve: the decode
    cache as the last step left it, read through the block-table rows the
    engine allocated to the last requests to retire (prompt KV written by
    prefill, generated KV by decode).  A row's blocks were freed when its
    request retired and may since hold a later request's writes; kernel
    and oracle read the same blocks either way."""
    ex = server.executor
    state = server.final_state
    rows = sorted(done, key=lambda r: r.finish_s)[-ex.max_batch:]
    bt = np.stack([state["block_rows"][r.rid] for r in rows])
    # newest written position of each request: its last token is never fed
    pos = np.asarray([len(r.prompt) + len(r.tokens) - 2 for r in rows],
                     np.int32)
    pool = state["cache"].kv[0]["b0"]
    k_pool, v_pool = pool["k"][0], pool["v"][0]
    cfg = server.cfg
    q = jnp.asarray(np.random.default_rng(1).standard_normal(
        (len(rows), cfg.n_heads, cfg.resolved_head_dim)), jnp.bfloat16)
    args = (q, k_pool, v_pool, jnp.asarray(bt), jnp.asarray(pos))
    got = paged_attention.paged_attention_pallas(*args)
    want = paged_attention.paged_attention_ref(*args)
    err = _rel_err(got, want)
    _check(err <= ATTN_TOL,
           f"paged_attention_pallas vs oracle on layer 0's live pool "
           f"{tuple(k_pool.shape)}, {len(rows)} served rows "
           f"({int((bt >= 0).sum())} blocks): rel err {err:.3e} <= "
           f"{ATTN_TOL}")


def _check_logits(server, done):
    """Forward the served prompts as one batch and check every logit is
    finite.  Whether each prompt's last-position argmax is the token the
    serve emitted first is printed, not checked: the serve prefilled each
    prompt alone or in a padded burst, a different XLA program from this
    batch-of-8 forward, and a near-tie between two logits may round either
    way in a different program."""
    ex = server.executor
    rows = done[:ex.max_batch]
    S = max(len(r.prompt) for r in rows)
    toks = np.zeros((len(rows), S), np.int32)
    for i, r in enumerate(rows):
        toks[i, :len(r.prompt)] = r.prompt
    logits = jax.jit(lambda p, t: ex.model.forward(p, {"tokens": t})[0])(
        ex.params, toks)
    finite = bool(jnp.all(jnp.isfinite(logits)))
    last = np.asarray(jnp.argmax(logits, -1))
    agree = sum(int(last[i, len(r.prompt) - 1]) == r.tokens[0]
                for i, r in enumerate(rows))
    print(f"  forward logits {tuple(logits.shape)}: argmax at each prompt's "
          f"last position equals the served first token for {agree}/"
          f"{len(rows)} requests")
    _check(finite, "all forward logits finite")


def _param_bytes_per_device(params):
    per = {}
    for leaf in jax.tree_util.tree_leaves(params):
        for sh in leaf.addressable_shards:
            per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
    return dict(sorted(per.items()))


def one_chip():
    server, _, done, stats = _serve(_serve_args())
    ex = server.executor
    _check(ex.paged_attn_route == "pallas",
           f"paged-decode route is {ex.paged_attn_route!r}")
    _check("tpu_custom_call" in _decode_program_text(ex),
           "decode program contains tpu_custom_call")
    _check_matmuls(ex.params)
    _check_paged_attention(server, done)
    _check_logits(server, done)
    mem = jax.devices()[0].memory_stats() or {}
    print(f"  device memory: peak_bytes_in_use "
          f"{mem.get('peak_bytes_in_use')} of bytes_limit "
          f"{mem.get('bytes_limit')}")


def four_chips():
    server, _, done2, _ = _serve(_serve_args("--mesh", "2x2"))
    per_dev = _param_bytes_per_device(server.executor.params)
    total = quantized_bytes(server.executor.params)
    print(f"  2x2 serving weights per device (bytes): {per_dev}")
    _check(len(per_dev) == 4 and max(per_dev.values()) < 0.75 * total,
           "weights split across the 4 devices")
    tokens_2x2 = {r.rid: list(r.tokens) for r in done2}
    del server, done2
    gc.collect()
    _, _, done1, _ = _serve(_serve_args("--mesh", "1x1"))
    tokens_1x1 = {r.rid: list(r.tokens) for r in done1}
    same = sum(tokens_2x2[k] == tokens_1x1[k] for k in tokens_1x1)
    first_diff = {k: next(i for i, (a, b) in enumerate(zip(tokens_2x2[k], v))
                          if a != b)
                  for k, v in tokens_1x1.items() if tokens_2x2[k] != v}
    _check(tokens_2x2 == tokens_1x1,
           f"2x2 tokens identical to 1x1 ({same}/{len(tokens_1x1)} requests"
           + (f"; first differing token per request: {first_diff}"
              if first_diff else "") + ")")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    opts = ap.parse_args()
    use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform!r} devices")
    if len(devices) < opts.chips:
        raise SystemExit(f"chip_smoke: --chips {opts.chips} needs "
                         f"{opts.chips} devices, JAX found {len(devices)}")
    print(f"device_kind {dev.device_kind!r}, {len(devices)} device(s), "
          f"jax {jax.__version__}, published peaks {device_peaks(dev)}")
    (four_chips if opts.chips == 4 else one_chip)()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
