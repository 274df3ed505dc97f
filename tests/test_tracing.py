"""Host spans of the serve loop in the profiler's own trace (CPU): each
blocking device read is one ``*.wait`` span and one counted host sync, the
spans nest inside the loop's iterations, the prefill spans name the
requests served, the collection hook leaves ``gc.callbacks`` as it found
it, a profiler session changes no token, and the decode step's ops carry
the model's named scopes."""
import dataclasses
import gc
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.launch.scheduler import Request
from repro.launch.serve import Server
from repro.models import build_model

SPANS = ("serve.window", "serve.iter", "sched.admit", "prefill.dispatch",
         "prefill.wait", "kv.alloc", "decode.dispatch", "decode.wait",
         "decode.emit", "idle.sleep", "gc")


@pytest.fixture(scope="module")
def qwen_setup():
    cfg = reduced_config(get_config("qwen3-8b"))
    model = build_model(cfg)
    params = model.quantize(model.init(jax.random.PRNGKey(0)), 8)
    return dataclasses.replace(cfg, quant_mode="psi8"), params


def _requests(cfg):
    """Three arrive together (co-admitted, one chunked), two later: one
    after an idle gap, so the loop sleeps."""
    rng = np.random.default_rng(0)
    specs = [(0.0, 40, 6), (0.0, 12, 9), (0.0, 8, 5), (0.02, 24, 7),
             (0.3, 10, 4)]
    return [Request(rid=10 + i,
                    prompt=rng.integers(0, cfg.vocab_size, size=(L,))
                    .astype(np.int32), max_new=n, arrival_s=at)
            for i, (at, L, n) in enumerate(specs)]


def _server(qwen_setup, horizon):
    cfg, params = qwen_setup
    return Server(cfg, params, max_batch=4, max_seq=64,
                  prefill_chunk=16 if horizon == 1 else 0,
                  decode_horizon=horizon)


def _traced_serve(server, reqs, log_dir):
    server.warmup(reqs, verbose=False)
    with jax.profiler.trace(str(log_dir)):
        done, stats = server.serve(reqs, warmup=False)
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    for plane in profile.planes:
        for line in plane.lines:
            events = list(line.events)
            if any(e.name == "serve.window" for e in events):
                spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                          dict(e.stats)) for e in events if e.name in SPANS]
                return done, stats, spans, [e.name for e in events]
    raise AssertionError("no host line holds serve.window")


@pytest.fixture(scope="module")
def traced(qwen_setup, tmp_path_factory):
    """One chunked serve at horizon 1 under the profiler, with Python's
    collector made eager so collections fall inside the serve."""
    server = _server(qwen_setup, 1)
    reqs = _requests(qwen_setup[0])
    thresholds = gc.get_threshold()
    gc.set_threshold(5, 1000, 1000)
    try:
        return (reqs,) + _traced_serve(server, reqs,
                                       tmp_path_factory.mktemp("trace"))
    finally:
        gc.set_threshold(*thresholds)


@pytest.mark.parametrize("horizon", [1, 4], ids=["chunked", "multistep"])
def test_one_wait_span_per_host_sync(qwen_setup, tmp_path, horizon):
    server = _server(qwen_setup, horizon)
    _, stats, spans, _ = _traced_serve(server, _requests(qwen_setup[0]),
                                       tmp_path)
    waits = [s for s in spans if s[0].endswith(".wait")]
    assert stats["host_syncs"] == len(waits) > 0
    assert stats["decode_horizon"] == horizon


def test_spans_nest_in_the_loop(traced):
    _, _, stats, spans, _ = traced
    window, = [s for s in spans if s[0] == "serve.window"]
    iters = [s for s in spans if s[0] == "serve.iter"]
    assert [s[3]["step_num"] for s in iters] == \
        list(range(1, stats["loop_iters"] + 1))
    assert all(window[1] <= s[1] and s[2] <= window[2] for s in iters)
    names = set()
    for name, lo, hi, _ in spans:
        if name in ("serve.window", "serve.iter", "gc"):
            continue                 # a collection can come anywhere
        names.add(name)
        assert any(s[1] <= lo and hi <= s[2] for s in iters), name
    assert names == {"sched.admit", "prefill.dispatch", "prefill.wait",
                     "kv.alloc", "decode.dispatch", "decode.wait",
                     "decode.emit", "idle.sleep"}
    gcs = [s for s in spans if s[0] == "gc"]
    assert stats["gc_pauses"] == len(gcs) > 0
    assert all(s[3]["generation"] in (0, 1, 2) for s in gcs)
    assert stats["gc_pause_s"] > 0
    # wall less waits and sleeps: host work, inside the window
    assert 0 < stats["host_busy_s"] < stats["wall_s"]


def test_prefill_spans_name_the_requests(traced):
    reqs, done, _, spans, _ = traced
    rids = {s[3]["rid"] for s in spans if s[0].startswith("prefill.")}
    assert rids == {r.rid for r in reqs} == {r.rid for r in done}
    # the 40-token prompt prefills in chunks of 16 at offsets 0, 16, 32
    assert sorted(s[3]["offset"] for s in spans
                  if s[0] == "prefill.dispatch" and s[3]["rid"] == 10) \
        == [0, 16, 32]


def test_block_table_update_is_named(traced):
    *_, line_events = traced
    assert "PjitFunction(_set_table_row)" in line_events
    assert not any("lambda" in n for n in line_events
                   if n.startswith("PjitFunction"))


def test_a_session_changes_no_token(qwen_setup, traced):
    _, done, *_ = traced
    server = _server(qwen_setup, 1)
    plain, _ = server.serve(_requests(qwen_setup[0]), warmup=False)
    assert {r.rid: r.tokens for r in plain} == \
        {r.rid: r.tokens for r in done}


def test_gc_hook_is_removed(qwen_setup):
    server = _server(qwen_setup, 1)
    n = len(gc.callbacks)
    server.serve(_requests(qwen_setup[0])[:2], warmup=False)
    assert len(gc.callbacks) == n
    too_long = Request(rid=0, prompt=np.zeros((80,), np.int32), max_new=4)
    with pytest.raises(ValueError, match="max_seq"):
        server.serve([too_long], warmup=False)
    assert len(gc.callbacks) == n


def test_head_matmul_carries_its_scope(qwen_setup):
    """The lowered decode step's HLO names the lm_head scope in the
    op_name of the head's matmul (the reference route on a CPU), and the
    layer body its attn and mlp scopes."""
    server = _server(qwen_setup, 1)
    ex = server.executor
    B, vocab = 4, qwen_setup[0].vocab_size
    tok = jnp.zeros((B, 1), jnp.int32)
    table = jnp.full((B, ex.n_bt), -1, jnp.int32)
    hlo = ex._decode.lower(ex.params, tok, tok, jnp.zeros((B,), bool),
                           table, ex.init_cache()).as_text(
        dialect="hlo", debug_info=True)
    head = [l for l in hlo.splitlines()
            if f" = f32[{B},{vocab}]" in l and " dot(" in l]
    assert head and all("/lm_head/" in l for l in head)
    for scope in ("attn", "mlp"):          # relative inside the layer scan
        assert re.search(f'op_name="([^"]*/)?{scope}/', hlo), scope
