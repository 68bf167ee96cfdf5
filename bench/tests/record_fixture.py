#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench_trace.py`` reads.

    python3 bench/tests/record_fixture.py [out_dir]   # on 4 TPU chips

Runs the harness's burst cycle at a small grid (512 x 512, 8 shots),
one stripe for 16 steps and four stripes for 32, traced for a fraction
of a second, and writes the trace to ``bench/tests/data/burst4.xplane.pb``
with a few facts about the run beside it in ``burst4.json`` (or into
``out_dir``, to be copied there).
"""
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

FWI = dict(nz=512, nx=512, dt=5e-4, dx=5.0, timesteps=600, n_shots=8,
           sponge_width=32, sponge_strength=0.0125, source_freq=12.0,
           receiver_depth=2)
CONF = dict(exchange_interval=4, scan_block=8, check_shots=4)
PHASES = [{"stripes": 1, "steps": 16}, {"stripes": 4, "steps": 32}]


def main(out: Path) -> int:
    import jax

    from bench import harness

    devices = harness.chips(4)
    mix = harness.mix("burst-cycle")
    mix["phases"] = PHASES
    keep = Path(tempfile.mkdtemp(prefix="fixture-"))
    run, parts = harness.run_cell(
        FWI, CONF, mix, seed=1234, seconds=0.1, traced=True,
        t_start=time.perf_counter(), devices=devices[:4],
        limits={"wavefield_gap": 1e-3}, keep_trace=keep)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(next(keep.glob("*.xplane.pb")), out / "burst4.xplane.pb")
    shutil.rmtree(keep)
    facts = {
        "device_kind": devices[0].device_kind,
        "jax": jax.__version__,
        "sessions": [[s.stripes, s.devices, s.t_begin, s.t_end]
                     for s in run.sessions],
        "transitions": len(run.completed_transitions()),
        "correct": parts["correct"],
        "gap": parts["checks"]["wavefield_gap"]["value"],
    }
    (out / "burst4.json").write_text(json.dumps(facts, indent=1) + "\n")
    print(json.dumps(facts))
    return 0


if __name__ == "__main__":
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main(Path(sys.argv[1]) if len(sys.argv) > 1 else HERE / "data"))
