#!/usr/bin/env python3
"""Read the two ends a cell's correctness limit is set between.

    python3 bench/calibrate.py --workload <name> --seconds <s> \\
        --program-seeds 1 2 ... --control-seeds 7 8 9 --control-steps 8 64 <n>

In one process, on the machine it is started on: for each program seed
a whole run of the cell (set-up, a window of ``--seconds``, the check)
with the limit out of the way, printing the ``wavefield_gap`` it reads;
for each control seed the control, printing the gap the check would
read with the control in the program's place.  The control is the
reference computed in bfloat16, the precision below the float32 the
configurations state, from the same seeded fields; it is read after
each of ``--control-steps`` timesteps in one chained run, the last as
many as a run's window compares.  One JSON line per reading.  The
benchmark's own runs do not run this.
"""
import argparse
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def control_gaps(fwi: dict, conf: dict, mix: dict, seed: int,
                 steps: list[int], devices) -> dict[int, list[float]]:
    """{timesteps: the check's gaps with the bfloat16 reference as the
    program} after each count in ``steps``."""
    import jax.numpy as jnp

    from bench import harness, reference, state

    idx = harness.sample_shots(fwi["n_shots"], conf["check_shots"], seed)
    p, pp = state.initial_fields(
        seed, shots=fwi["n_shots"], nz=fwi["nz"], nx=fwi["nx"],
        init=mix["init"], dx=fwi["dx"], dt=fwi["dt"])
    low = high = tuple(jnp.take(a, jnp.asarray(idx), axis=0)
                       for a in (p, pp))
    del p, pp
    t0, done, out = int(mix["t0"]), 0, {}
    for n in sorted(steps):
        low = reference.propagate(fwi, *low, idx, t0 + done, n - done,
                                  dtype=jnp.bfloat16, devices=devices)
        high = reference.propagate(fwi, *high, idx, t0 + done, n - done,
                                   devices=devices)
        out[n] = harness.gaps(low, high)
        done = n
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-steps", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    from bench import harness
    from bench.compile_cache import enable_compilation_cache

    bench = harness.benchmark()
    c = harness.cell(bench, args.workload)
    # the control alone needs no more than one chip
    n = c["chips"] if args.program_seeds else 1
    devices = harness.chips(n)[:n]
    enable_compilation_cache()
    conf = harness.config_file(bench, c["config"])
    mix = harness.mix(c["traffic"])
    for seed in args.program_seeds:
        run, parts = harness.run_cell(
            conf["fwi"], conf, mix, seed=seed, seconds=args.seconds,
            traced=False, t_start=time.perf_counter(), devices=devices,
            limits={"wavefield_gap": math.inf})
        print(json.dumps({"workload": args.workload, "kind": "program",
                          "seed": seed,
                          "gap": parts["checks"]["wavefield_gap"]["value"],
                          "steps": parts["steps"],
                          "transitions": len(run.completed_transitions())}),
              flush=True)
    ref_devices = devices if conf["check_shots"] % len(devices) == 0 \
        else devices[:1]
    for seed in args.control_seeds:
        t = time.perf_counter()
        read = control_gaps(conf["fwi"], conf, mix, seed,
                            args.control_steps, ref_devices)
        for steps, gaps in read.items():
            print(json.dumps({"workload": args.workload, "kind": "control",
                              "seed": seed, "steps": steps,
                              "gap": float(np.max(gaps)),  # NaN stays NaN
                              "gaps": gaps,
                              "seconds": time.perf_counter() - t}),
                  flush=True)
    return 0


if __name__ == "__main__":
    # the repository root holds the ``bench`` package and ``src`` the
    # program; the script's own directory leaves the path
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
