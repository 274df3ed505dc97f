"""Find a cell's knee: serve its configuration at several fixed rates of a
traffic mix in one process, on the chip, and print one JSON line a rate.

  python3 benchmarks/chip/sweep.py --workload <cell> --mix <traffic> \\
      --rates 0.4,0.6,0.8 --seconds 20

The knee is the highest rate, with every lower rate swept also
sustained, at which the queue does not grow across the window: the last
quarter of arrivals waits for a slot at most 0.5 s longer, on the mean,
than the first quarter.
A cell's rate is then fixed from it (0.8x below the knee, 1.5x above) and
the sweep recorded in the cell file.
"""
import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import numpy as np
    from chipbench import harness, traffic, weights
    from repro.core import psi
    from repro.launch.scheduler import Request

    cell = harness.load_cell(ROOT, args.workload)
    harness.check_devices(cell.chips)
    harness.use_compile_cache(cell.root)
    with open(cell.bench_dir / "traffic" / f"{args.mix}.json") as f:
        mix = json.load(f)
    arch = cell.arch
    params = weights.served_tree(args.seed, arch, cell.conf,
                                 psi.get_format(arch.bits),
                                 psi.QuantizedTensor)
    server = harness.make_server(cell, params)
    del params
    W = args.seconds
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        reqs = [Request(rid=r["rid"], prompt=r["prompt"],
                        max_new=r["max_new"], arrival_s=r["arrival_s"])
                for r in traffic.generate(mix, rate, W, args.seed + i,
                                          cell.conf["vocab_size"])]
        harness.warm(server, reqs)
        t0 = time.perf_counter()
        done, stats = server.serve(reqs, continuous=True, warmup=False)
        wall = time.perf_counter() - t0
        server.final_state = None
        ttft = np.asarray([r.ttft_s for r in done])
        queue = np.asarray([r.queue_s for r in done])
        order = np.argsort([r.arrival_s for r in done])
        q = max(len(done) // 4, 1)
        toks = sum(int(np.sum(np.asarray(r.token_s) <= W)) for r in done)
        print(json.dumps({
            "rate": rate, "requests": len(done), "wall_s": wall,
            "tokens_per_s": toks / W,
            "ttft_p50": float(np.percentile(ttft, 50)),
            "ttft_p90": float(np.percentile(ttft, 90)),
            "queue_p90": float(np.percentile(queue, 90)),
            "ttft_first_quarter": float(ttft[order[:q]].mean()),
            "ttft_last_quarter": float(ttft[order[-q:]].mean()),
            "queue_first_quarter": float(queue[order[:q]].mean()),
            "queue_last_quarter": float(queue[order[-q:]].mean()),
            "peak_blocks_in_use": stats.get("peak_blocks_in_use")}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
