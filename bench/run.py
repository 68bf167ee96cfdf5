#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the repository root names the cells.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``, each compared number beside its
limit; the same numbers end standard error.  Exits non-zero, printing
no result, when JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the repository root holds the ``bench`` package and ``src`` the
# program; the script's own directory leaves the path, so that the
# benchmark's modules shadow no module of the standard library
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="copy the traced run's .xplane.pb here")
    args = ap.parse_args(argv)

    from bench import harness
    import repro.fwi.driver  # noqa: F401  (the program under test)

    bench = harness.benchmark()
    try:
        out = harness.result_line(
            bench, args.workload, seed=args.seed, seconds=args.seconds,
            traced=bool(args.trace), t_start=T_START,
            keep_trace=args.keep_trace)
    except harness.NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
