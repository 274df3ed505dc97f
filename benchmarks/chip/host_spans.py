"""Where the device's idle time goes, by the serve loop's host spans: serve
a cell's traffic under the profiler once a seed, in one process, and print
one JSON line a seed.

  python3 benchmarks/chip/host_spans.py --workload <cell> --seeds 1,2,3 \\
      --seconds 30 [--out spans.json]

Run from the root of a checkout on a machine with the cell's TPU chip.
Each line holds the run line's ``tokens`` and ``serve_wall_s``; the
device's idle time inside ``serve.window`` put down to the innermost host
span over it (``idle_by_host_span``); ``host_ms_per_step`` from the spans
beside the serve stats' counter; the ``*.wait`` spans counted against
``host_syncs``; the share of decode programs that end inside a
``decode.wait`` span, with the median time from that end to the wait's;
every inter-token gap over twice the median decode program, grouped by
the device programs and host spans across it; and the stats of the
lm_head's kernel op (where the named scope shows).  The weights are built
once, from the first seed; each seed draws its own traffic.
"""
import argparse
import bisect
import glob
import json
import os
import pathlib
import re
import shutil
import statistics
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

DECODE = "_decode_fn_paged"
PREFILL = "_prefill_insert_fn"


def clock_agreement(dev, spans_):
    """Share of the decode programs whose end lies inside a
    ``decode.wait`` span, and the median ms from that end to the wait's."""
    waits = sorted((s.start_ns, s.end_ns) for s in spans_
                   if s.name == "decode.wait")
    starts = [w[0] for w in waits]
    mods = [m for m in dev.modules if DECODE in m.name]
    offsets = []
    for m in mods:
        i = bisect.bisect_right(starts, m.end_ns) - 1
        if i >= 0 and m.end_ns <= waits[i][1]:
            offsets.append((waits[i][1] - m.end_ns) * 1e-6)
    return {"decode_programs": len(mods),
            "end_inside_wait": len(offsets) / max(len(mods), 1),
            "median_wait_end_after_ms": (statistics.median(offsets)
                                         if offsets else None)}


def _overlaps(events, a, b, key):
    out = {}
    for e in events:
        ov = min(e.end_ns, b) - max(e.start_ns, a)
        if ov > 0:
            out[key(e)] = out.get(key(e), 0.0) + ov * 1e-6
    return out


def stalls(dev, spans_, requests):
    """Inter-token gaps over twice the median decode program, one per
    distinct (previous, next) emission time, grouped by the programs the
    device ran across each (and ``gc`` or ``idle.sleep`` on the host)."""
    window = next(s for s in spans_ if s.name == "serve.window")
    mods = sorted(dev.modules, key=lambda m: m.start_ns)
    step = statistics.median(m.dur_ns for m in mods if DECODE in m.name)
    leaves = [s for s in spans_ if s.name not in ("serve.window",
                                                  "serve.iter")]
    gaps = {(a, b) for r in requests for a, b in zip(r.token_s,
                                                     r.token_s[1:])
            if (b - a) * 1e9 > 2 * step}
    groups, worst = {}, []
    for a, b in sorted(gaps):
        lo, hi = window.start_ns + a * 1e9, window.start_ns + b * 1e9
        host = _overlaps(leaves, lo, hi, lambda s: s.name)
        dev_ms = _overlaps(mods, lo, hi, lambda m: (
            "decode" if DECODE in m.name else "prefill"
            if PREFILL in m.name else "other"))
        n_pre = sum(1 for m in mods if PREFILL in m.name
                    and m.end_ns > lo and m.start_ns < hi)
        n_dec = sum(1 for m in mods if DECODE in m.name
                    and m.end_ns > lo and m.start_ns < hi)
        key = f"decode x{n_dec} + prefill x{n_pre}"
        for name in ("gc", "idle.sleep"):
            if host.get(name, 0.0) > 1.0:
                key += f" + {name}"
        g = groups.setdefault(key, [])
        g.append(b - a)
        worst.append({"gap_s": b - a, "at_s": a, "host_ms": host,
                      "device_ms": dev_ms})
    worst.sort(key=lambda w: -w["gap_s"])
    return {"median_decode_program_ms": step * 1e-6,
            "gaps": len(gaps),
            "groups": {k: {"n": len(v), "min_s": min(v), "max_s": max(v)}
                       for k, v in sorted(groups.items())},
            "longest": worst[:5]}


def lm_head_op(profile, vocab):
    """Stats of the first ``psi_matmul`` op of vocab width on the device
    plane, and which stats of the first ops name a model scope."""
    from chipbench import readers
    rx = re.compile(readers.PSI_MATMUL)
    head, scoped = None, {}
    for plane in profile.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for i, e in enumerate(line.events):
                if i >= 20000 and head is not None:
                    break
                st = {k: str(v)[:300] for k, v in e.stats}
                for k, v in st.items():
                    if re.search(r"(^|/)(lm_head|attn|mlp)/", v):
                        scoped[k] = scoped.get(k, 0) + 1
                if (head is None and rx.search(e.name)
                        and f",{vocab}]" in e.name.split(" = ")[1][:40]):
                    head = {"name": e.name[:200], "stats": st}
        break
    return {"lm_head_op": head, "stats_naming_a_scope": scoped}


def one_seed(cell, server, seed, seconds):
    import jax
    from chipbench import harness, spans, trace
    requests = harness._requests(cell, seed, seconds)
    harness.warm(server, requests)
    log_dir = tempfile.mkdtemp(prefix="host-spans-")
    try:
        jax.profiler.start_trace(log_dir)
        t0 = time.perf_counter()
        done, stats = server.serve(requests, continuous=True, warmup=False)
        wall = time.perf_counter() - t0
        jax.profiler.stop_trace()
        server.final_state = None
        path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
        profile = jax.profiler.ProfileData.from_file(path)
        devs = trace.devices_from_profile(profile)
        sp = spans.host_spans(profile)
        scope = lm_head_op(profile, cell.conf["vocab_size"])
        del profile
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    red = trace.reduce(devs, wall)
    base = {"seed": seed, "tokens": stats["tokens"], "serve_wall_s": wall,
            "decode_steps": stats["decode_steps"],
            "host_syncs": stats["host_syncs"],
            "host_syncs_per_token": stats["host_syncs_per_token"],
            "device_idle_share": red.idle_share, "busy_s": red.busy_s,
            "idle_gaps": red.idle_gaps, "device_ops": red.device_ops[:5]}
    if not sp:                       # a program that writes no spans
        return base
    idle = spans.idle_by_host_span(devs, sp, n=None)
    idle_s = sum(v for _, v in idle)
    no_span = dict(idle).get(spans.NO_SPAN, 0.0)
    steps = stats["decode_steps"]
    return {
        **base, "loop_iters": stats["loop_iters"],
        "idle_in_window_s": idle_s,
        "idle_named_share": 1.0 - no_span / idle_s if idle_s else None,
        "idle_by_host_span": idle,
        "host_ms_per_step_spans": spans.host_ms_per_step(sp, steps),
        "host_ms_per_step_counter": 1e3 * stats["host_busy_s"] / steps,
        "wait_spans": sum(1 for s in sp if s.name.endswith(".wait")),
        "gc_pauses": stats["gc_pauses"], "gc_pause_s": stats["gc_pause_s"],
        "gc_spans": sum(1 for s in sp if s.name == "gc"),
        "clocks": clock_agreement(devs[0], sp),
        "stalls": stalls(devs[0], sp, done), **scope}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from chipbench import harness, weights
    from repro.core import psi

    cell = harness.load_cell(ROOT, args.workload)
    harness.check_devices(cell.chips)
    harness.use_compile_cache(cell.root)
    seeds = [int(s) for s in args.seeds.split(",")]
    arch = cell.arch
    params = weights.served_tree(seeds[0], arch, cell.conf,
                                 psi.get_format(arch.bits),
                                 psi.QuantizedTensor)
    server = harness.make_server(cell, params)
    del params
    out = []
    for seed in seeds:
        res = one_seed(cell, server, seed, args.seconds)
        out.append(res)
        print(json.dumps(res), flush=True)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
