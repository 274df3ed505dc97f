"""One run of one cell: set up the program, serve the cell's traffic for the
window, check what was served against the reference, and print the result.

Everything that belongs to one configuration, traffic mix, cell or metric
is a file found by its name in ``BENCHMARK.json``:

  <bench>/configs/<config>.json   sizes, PSI width, source (``file`` key)
  <bench>/traffic/<traffic>.json  arrival process and length distributions
  <bench>/cells/<workload>.json   rate, serve options, correctness limit
  <bench>/metrics/<metric>.py     ``read(run) -> float | None``

where <bench> is the first of the benchmark's ``paths``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import traffic as traffic_gen
from chipbench import work


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def _read_json(path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _named(entries, name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    """A workload with its files loaded."""
    name: str
    root: pathlib.Path
    bench_dir: pathlib.Path
    chips: int
    conf: Dict
    mix: Dict
    cell: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def arch(self) -> work.Arch:
        return work.Arch.from_config(self.conf)

    def reader(self, metric: str):
        path = self.bench_dir / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def load_cell(root, workload: str) -> Cell:
    root = pathlib.Path(root)
    bench = _read_json(root / "BENCHMARK.json")
    bench_dir = root / bench["paths"][0]
    wl = _named(bench["workloads"], workload, "workload")
    cfg = _named(bench["configs"], wl["config"], "config")

    def applies(m):
        return workload in m.get("workloads", [workload])

    return Cell(
        name=workload, root=root, bench_dir=bench_dir, chips=wl["chips"],
        conf=_read_json(root / cfg["file"]),
        mix=_read_json(bench_dir / "traffic" / f"{wl['traffic']}.json"),
        cell=_read_json(bench_dir / "cells" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def check_devices(chips: int):
    """The devices a cell runs on; raises :class:`NoChip` unless JAX's
    first device is a TPU and there are at least ``chips`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"need {chips} TPU chip(s), JAX found "
                     f"{len(devs)} {devs[0].platform} device(s) "
                     f"({devs[0].device_kind})")
    return devs[:chips]


def use_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one; every
    program is kept, however short its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts backend compiles, and the persistent cache's hits and misses,
    while on."""

    def __init__(self):
        import jax
        self.n = 0
        self.cache: Dict[str, int] = {}
        self.on = False

        def on_duration(event, duration, **_):
            if self.on and event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        def on_event(event, **_):
            if self.on and "compilation_cache" in event:
                self.cache[event] = self.cache.get(event, 0) + 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def start(self):
        self.n, self.cache, self.on = 0, {}, True

    def stop(self) -> int:
        self.on = False
        return self.n


def program_config(conf: Dict, serve: Dict):
    """The program's ``ModelConfig`` for a configuration file, every serve
    option stated."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        head_dim=conf["head_dim"], rope="rope",
        rope_theta=float(conf["rope_theta"]),
        qk_norm=bool(conf.get("qk_norm")), norm="rmsnorm", act="swiglu",
        norm_eps=float(conf["rms_norm_eps"]), tie_embeddings=False,
        quant_mode=f"psi{conf['psi_bits']}", dtype=conf["torch_dtype"],
        cache_layout="paged", cache_block_size=serve["block_size"],
        prefix_cache=False, kv_quant="")


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""
    cell: Cell
    seconds: float
    requests: List
    stats: Dict
    timings: Dict
    peaks: Optional[Dict]
    trace: Optional[object] = None        # chipbench.trace.Reduced
    devices: Optional[List] = None        # chipbench.trace.Device list

    @property
    def arch(self) -> work.Arch:
        return self.cell.arch

    @property
    def slots(self) -> int:
        return self.cell.cell["serve"]["max_batch"]

    def steps(self, until: Optional[float] = None):
        """(decode, prefill): each decode step's active contexts and each
        prefill call's prompt lengths, in time order, read from when each
        token was emitted — one step's tokens share one timestamp, and a
        request's first token comes from its prefill."""
        dec: Dict[float, List[int]] = {}
        pre: Dict[float, List[int]] = {}
        for r in self.requests:
            L = len(r.prompt)
            for i, t in enumerate(r.token_s):
                if until is not None and t > until:
                    break
                if i == 0:
                    pre.setdefault(t, []).append(L)
                else:
                    dec.setdefault(t, []).append(L + i)
        return ([dec[t] for t in sorted(dec)], [pre[t] for t in sorted(pre)])

    def model_flops(self, until: Optional[float] = None) -> float:
        dec, pre = self.steps(until)
        a = self.arch
        return (sum(work.prefill_flops(a, L) for p in pre for L in p)
                + sum(work.decode_flops(a, c) for d in dec for c in d))

    def kernel_s(self, pattern: str) -> Optional[float]:
        if self.devices is None:
            return None
        from chipbench import trace
        return sum(trace.kernel_s(d, pattern)
                   for d in self.devices) / len(self.devices)

    def window_gaps(self) -> np.ndarray:
        """Every inter-token gap that ends inside the window, pooled."""
        out = []
        for r in self.requests:
            ts = np.asarray(r.token_s, np.float64)
            if len(ts) > 1:
                g = np.diff(ts)
                out.append(g[ts[1:] <= self.seconds])
        return np.concatenate(out) if out else np.empty((0,))


def _requests(cell: Cell, seed: int, seconds: float):
    from repro.launch.scheduler import Request
    raw = traffic_gen.generate(cell.mix, cell.cell["rate_rps"], seconds,
                               seed, cell.conf["vocab_size"])
    return [Request(rid=r["rid"], prompt=r["prompt"], max_new=r["max_new"],
                    arrival_s=r["arrival_s"]) for r in raw]


def sample_for_check(requests, seed: int, min_tokens: int,
                     max_requests: int):
    """Finished requests to compare, drawn from the seed: the one with the
    most served tokens, then others at random until ``min_tokens`` served
    tokens or ``max_requests`` requests."""
    done = [r for r in requests if r.tokens]
    if not done:
        return []
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 1])
    longest = max(done, key=lambda r: (len(r.tokens), -r.rid))
    pick, n = [longest], len(longest.tokens)
    for i in rng.permutation(len(done)):
        if n >= min_tokens or len(pick) >= max_requests:
            break
        if done[i] is not longest:
            pick.append(done[i])
            n += len(done[i].tokens)
    return pick


def check_served(cell: Cell, seed: int, requests, control: bool = False):
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over a sample of finished requests.  With ``control``
    the reference one precision lower takes the program's place: the gap
    compared (``max_logit_gap``) is that of the tokens it puts first, at
    the same positions, and the program's own is kept beside it."""
    from chipbench import reference
    chk = cell.cell["check"]
    pick = sample_for_check(requests, seed, chk["min_tokens"],
                            chk["max_requests"])
    seqs, targets = [], []
    for r in pick:
        toks = np.asarray(r.tokens, np.int32)
        seqs.append(np.concatenate([r.prompt, toks[:-1]]))
        L = len(r.prompt)
        targets.append(np.stack([np.arange(L - 1, L - 1 + len(toks)), toks],
                                1))
    lower = reference.control_dtype(cell.conf) if control else None
    res = reference.logit_gaps(seed, cell.arch, cell.conf, seqs, targets,
                               cell.cell["serve"]["max_seq"], lower=lower)
    gap = max(float(g.max()) for g, _ in res)
    cgap = max(float(c.max()) for _, c in res) if control else None
    return {"program_max_logit_gap": gap, "control_max_logit_gap": cgap,
            "max_logit_gap": cgap if control else gap,
            "checked_requests": len(pick),
            "checked_tokens": int(sum(len(r.tokens) for r in pick))}


def make_server(cell: Cell, params):
    """The program's ``Server`` with every serve option the cell file
    states: no speculation, prefix cache or SLO, horizon 1."""
    from repro.launch.serve import Server
    serve = cell.cell["serve"]
    return Server(program_config(cell.conf, serve), params,
                  max_batch=serve["max_batch"], max_seq=serve["max_seq"],
                  eos_id=-1, n_blocks=serve["n_blocks"], speculative=None,
                  prefill_chunk=serve["prefill_chunk"], slo=None,
                  decode_horizon=1)


def warm(server, requests):
    """Compile, or load from the cache, every program serving ``requests``
    runs.  ``Server.warmup`` leaves out the host read of a single
    admission's first token and the device block table's row update: a lone
    short request, then two admitted together of which one retires while
    the other decodes, compile those too, so nothing compiles in the
    window."""
    server.warmup(requests, verbose=False)
    short = min(requests, key=lambda r: len(r.prompt))
    for budgets in ((2,), (2, 3)):
        server.serve([dataclasses.replace(short, rid=i, max_new=n,
                                          arrival_s=0.0, tokens=[],
                                          token_s=[])
                      for i, n in enumerate(budgets)],
                     continuous=True, warmup=False)
        # the serve keeps its last KV pool; a second pool beside the next
        # one leaves the decode step no room for its temporaries
        server.final_state = None


def _in_use(devs) -> Dict:
    stats = devs[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "bytes_limit")}


def _log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             control: bool = False) -> Dict:
    """One run; returns the result object (the last line's JSON)."""
    import jax
    phases = {"start_to_import_s": time.perf_counter() - t_start}
    t0 = time.perf_counter()
    if require_tpu:
        devs = check_devices(cell.chips)
    else:
        devs = jax.devices()[:cell.chips]
    t_ready = time.perf_counter()
    phases["devices_s"] = t_ready - t0
    kind = devs[0].device_kind
    peaks = work.peaks(kind) if require_tpu else None
    use_compile_cache(cell.root)
    counter = CompileCounter()

    from repro.core import psi
    from chipbench import weights

    arch = cell.arch
    counter.start()
    t0 = time.perf_counter()
    params = weights.served_tree(seed, arch, cell.conf,
                                 psi.get_format(arch.bits),
                                 psi.QuantizedTensor)
    jax.block_until_ready(params)
    phases["weights_s"] = time.perf_counter() - t0
    _log(f"memory after weights: {_in_use(devs)}")
    t0 = time.perf_counter()
    server = make_server(cell, params)
    del params
    requests = _requests(cell, seed, seconds)
    phases["server_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm(server, requests)
    warmup_compile_s = time.perf_counter() - t0
    phases["warm_s"] = warmup_compile_s
    _log(f"memory after warm-up: {_in_use(devs)}")
    setup_compiles = counter.stop()
    setup_cache = dict(counter.cache)
    # from the chip in hand: the runtime's own start (6.8-11.7 s on a v5e
    # host) is the machine's, and varies by more than the rest together
    setup_s = time.perf_counter() - t_ready
    phases["setup_s"] = setup_s
    _log(f"set-up phases: {json.dumps(phases)}")

    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    counter.start()
    try:
        if trace:
            jax.profiler.start_trace(log_dir)
        t0 = time.perf_counter()
        done, stats = server.serve(requests, continuous=True, warmup=False)
        traced_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
        window_compiles = counter.stop()
        mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs]
        server.final_state = None
        del server
        gc.collect()

        timings = {"setup_s": setup_s, "warmup_compile_s": warmup_compile_s}
        run = Run(cell, seconds, done, stats, timings, peaks)
        dec, _ = run.steps()
        if len(dec) != stats["decode_steps"]:
            raise RuntimeError(f"read {len(dec)} decode steps from token "
                               f"times, the server counted "
                               f"{stats['decode_steps']}")
        if trace:
            from chipbench import trace as trace_mod
            t0 = time.perf_counter()
            run.devices = trace_mod.load(log_dir)
            run.trace = trace_mod.reduce(run.devices, traced_s)
            _log(f"trace read and reduced in {time.perf_counter() - t0:.1f}s")
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)

    failed = sum(1 for r in requests
                 if r.finish_s is None or len(r.tokens) != r.max_new)
    t0 = time.perf_counter()
    checked = check_served(cell, seed, done, control=control)
    _log(f"reference check in {time.perf_counter() - t0:.1f}s")
    limit = cell.cell["check"]["max_logit_gap"]
    checks = {"max_logit_gap": {"value": checked["max_logit_gap"],
                                "limit": limit},
              "failed_requests": {"value": failed, "limit": 0},
              "compiles_in_window": {"value": window_compiles, "limit": 0}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cell.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(max(mem))}
    result = {"correct": correct, "attempted": len(requests),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.device_ops,
                               "idle_gaps": run.trace.idle_gaps}
    extra = {"setup_compiles": setup_compiles,
             "setup_cache_events": setup_cache,
             "checked_requests": checked["checked_requests"],
             "checked_tokens": checked["checked_tokens"],
             "tokens": stats["tokens"], "decode_steps": stats["decode_steps"],
             "serve_wall_s": traced_s, "n_blocks": stats.get("n_blocks"),
             "peak_blocks_in_use": stats.get("peak_blocks_in_use")}
    if control:
        extra["control"] = True
        extra["program_max_logit_gap"] = checked["program_max_logit_gap"]
    _log(f"run: {json.dumps(extra)}")
    for name, c in checks.items():
        _log(f"check {name}: {c['value']} (limit {c['limit']})")
    result["checks"] = checks
    return result
