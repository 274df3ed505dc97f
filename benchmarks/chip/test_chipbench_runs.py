"""CPU runs of the harness at a tiny size: a configuration, a traffic mix, a
cell and a metric added as files alone under a throwaway checkout, loaded
by name, and driven through a whole run past the look for a chip.  A sound
program comes out correct; the control and the timed path broken
underneath come out not correct; without a TPU, ``run.py`` prints no
result and exits non-zero."""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

from chipbench import harness

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]

# A float32 program on the CPU and the float32 reference differ in the
# order of their sums alone: a served token is the reference's best up to
# a near-tie, so the widest gap stays under 1e-3.  The control (bfloat16,
# one step down) flips near-ties among 4096 logits and reads above it.
TINY = {"name": "tiny", "source": "throwaway test configuration",
        "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
        "torch_dtype": "float32", "vocab_size": 4096, "qk_norm": True,
        "psi_bits": 8, "psi_terms": 4}
MIX = {"arrivals": "poisson", "schedule_seed": 3,
       "prompt_len": {"classes": [16, 48], "weights": [0.5, 0.5]},
       "output_len": {"log_uniform": [24, 64]}}
CELL = {"rate_rps": 6.0,
        "serve": {"max_batch": 4, "block_size": 16, "n_blocks": 64,
                  "max_seq": 128, "prefill_chunk": 0},
        "check": {"max_logit_gap": 1e-3, "min_tokens": 160,
                  "max_requests": 6}}
METRIC = '''"""Every token the run emitted (a throwaway metric)."""


def read(run):
    return sum(len(r.tokens) for r in run.requests)
'''


def make_checkout(root: pathlib.Path, bits: int,
                  prefill_chunk: int = 0) -> pathlib.Path:
    """A checkout holding only files: BENCHMARK.json naming one new cell,
    its configuration, mix, cell file and a new metric."""
    bench = root / "bench"
    for sub in ("configs", "traffic", "cells", "metrics"):
        (bench / sub).mkdir(parents=True)
    conf = dict(TINY, psi_bits=bits, psi_terms=4 if bits == 8 else 2)
    (bench / "configs" / "tiny.json").write_text(json.dumps(conf))
    (bench / "traffic" / "chat-tiny.json").write_text(json.dumps(MIX))
    cell = dict(CELL, serve=dict(CELL["serve"], prefill_chunk=prefill_chunk))
    (bench / "cells" / "tiny.chat-tiny.json").write_text(json.dumps(cell))
    (bench / "metrics" / "tokens_total.py").write_text(METRIC)
    for m in ("tokens_per_s", "ttft_p90_s"):
        shutil.copy(HERE / "metrics" / f"{m}.py", bench / "metrics" / f"{m}.py")
    (root / "BENCHMARK.json").write_text(json.dumps({
        "paths": ["bench"],
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.chat-tiny", "config": "tiny",
                       "traffic": "chat-tiny", "chips": 1}],
        "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                       {"name": "ttft_p90_s", "unit": "s"},
                       {"name": "tokens_total", "unit": "tokens"}],
        "per_layer": []}))
    return root


@pytest.fixture
def run_tiny(tmp_path, monkeypatch):
    """Drive one whole run of the throwaway cell on the CPU; the compile
    cache stays off (a test leaves no cache directory behind)."""
    monkeypatch.setattr(harness, "use_compile_cache", lambda root: None)

    def go(bits=8, seed=2 ** 31 + 7, control=False, prefill_chunk=0):
        root = make_checkout(tmp_path / f"checkout{bits}", bits,
                             prefill_chunk)
        cell = harness.load_cell(root, "tiny.chat-tiny")
        return harness.run_cell(cell, seed, 1.5, False, time.perf_counter(),
                                require_tpu=False, control=control)
    return go


@pytest.mark.parametrize("bits,chunk", [(8, 0), (5, 0), (8, 32)],
                         ids=["psi8", "psi5-packed", "psi8-chunked"])
def test_sound_run_is_correct(run_tiny, monkeypatch, bits, chunk):
    """Prefill through psi_matmul, the paged decode and the lm_head agree
    with the plain reference on every checked token, with the Pallas
    kernels (the int8 body for psi8, the packed bit-plane body for psi5,
    the paged-decode kernel) run by the interpreter; and with prompts
    prefilled one request at a time in 32-token chunks over the pool."""
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    res = run_tiny(bits=bits, prefill_chunk=chunk)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 9
    assert res["checks"]["max_logit_gap"]["value"] <= 1e-3
    # the metric added as a file is found and read by name
    assert res["metrics"]["tokens_total"]["value"] > 160
    assert res["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
    assert 0 < res["metrics"]["ttft_p90_s"]["value"] < float("inf")
    assert res["checks"]["compiles_in_window"]["value"] == 0


def test_control_is_not_correct(run_tiny, capfd):
    """The reference one precision down (bfloat16 below float32), put in
    the program's place, fails the limit the program passes."""
    res = run_tiny(control=True)
    err = capfd.readouterr().err
    line = next(l for l in err.splitlines() if l.startswith("run: "))
    program_gap = json.loads(line[len("run: "):])["program_max_logit_gap"]
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] \
        > CELL["check"]["max_logit_gap"]
    assert program_gap <= CELL["check"]["max_logit_gap"]


def _alter_token(orig):
    def decode(self, params, token, pos, active, block_table, cache):
        tok, cache = orig(self, params, token, pos, active, block_table,
                          cache)
        return (tok + 1) % self.cfg.vocab_size, cache
    return decode


def _keep_state(orig):
    def decode(self, params, token, pos, active, block_table, cache):
        tok, _ = orig(self, params, token, pos, active, block_table, cache)
        return tok, cache
    return decode


@pytest.mark.parametrize("fault", [_alter_token, _keep_state],
                         ids=["token-altered", "state-unchanged"])
def test_broken_timed_path_is_not_correct(run_tiny, monkeypatch, fault):
    """The decode step broken under the serve loop: a token altered where
    it is produced, or a step that returns the KV cache unchanged."""
    from repro.runtime import executor
    monkeypatch.setattr(executor.Executor, "_decode_fn_paged",
                        fault(executor.Executor._decode_fn_paged))
    res = run_tiny()
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > 1e-3


def test_run_without_a_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         "qwen3-8b-psi8.chat-sat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
