"""Chip benchmark of the serving engine: one run of one cell.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the cell's TPU chips.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its limit.
Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 2.  ``--control 1`` also reports the reference's widest
gap one precision lower (the control of the correctness check).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench import harness
    cell = harness.load_cell(ROOT, args.workload)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START,
                                  control=bool(args.control))
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
