# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper table/figure.

  bench_capacity_fit    — Fig. 4 / eqs. 6-7 (time-vs-cores log fits)
  bench_gamma_fit       — Fig. 5 / eq. 8 (time-vs-γ linear fit, REAL timing)
  bench_burst_deadline  — §3.3 core claim (static misses, adaptive meets)
  bench_overheads       — §3.3 message-size/monitor/checkpoint overheads
  bench_envs            — Tables 1-2 (platform + workload configuration)
  bench_kernels         — Pallas kernel µbenches (interpret mode)
  bench_roofline        — EXPERIMENTS §Roofline from dry-run artifacts
  bench_fleet_scenarios — autoscaler policy suite × fleet scenarios
                          (hit-rate / cloud cost / useful-work frac)
  bench_faults          — fault-storm robustness grid: hardened vs
                          unhardened loop under the same fault draws
                          (hit-rate / cost bound / preemption latency)
  bench_fleet_tournament— policy × scheduler × scenario tournament of
                          the multi-tenant queue layer (hit-rate /
                          cloud $ / fairness); ``--big`` adds the
                          thousand-job tier
  bench_real_elastic    — sim-vs-real elastic loop: the same squeeze
                          scenario through FleetSim and the real
                          orchestrator+FWISession; cost-aware vs
                          cost-blind planning brackets

Usage:
  python benchmarks/run.py [--only a,b,...] [--json PATH] [--big]

``--big`` adds the thousand-job ``fleet_tournament_big`` tier; it is
off by default because it takes minutes on CPU.

``--json`` additionally writes machine-readable results: one record per
row with the name/us_per_call/derived fields parsed apart, plus the
failure count — the schema the CI bench smoke pins.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback
from pathlib import Path

# 2 host devices so the sharded engine benches measure real parallelism
# (must precede the first jax import; no-op on real accelerators)
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=2"
    ).strip()

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

# Persistent XLA compilation cache (repro.launch.compile_cache): the
# harness compiles dozens of executables that are byte-identical run to
# run — warm re-runs (and the CI bench smoke) load them from disk
# instead of recompiling.  Hit/miss counts come from the cache's own
# on-disk entries: every served entry touches its ``*-atime`` marker,
# every compile writes a new ``*-cache`` file (jax has no public
# counter API on this version, so the preamble counts files).
from repro.launch.compile_cache import enable_compilation_cache  # noqa: E402


def _cache_counts(cache_dir: str, since: float) -> tuple[int, int]:
    """(hits, misses) since ``since``: touched atime markers vs new
    cache entries."""
    touched = misses = 0
    try:
        for f in os.listdir(cache_dir):
            p = os.path.join(cache_dir, f)
            if f.endswith("-atime") and os.path.getmtime(p) >= since:
                touched += 1
            elif f.endswith("-cache") and os.path.getmtime(p) >= since:
                misses += 1
    except OSError:
        pass
    # a fresh compile writes BOTH files, so its atime touch is not a hit
    return max(touched - misses, 0), misses

from benchmarks import (  # noqa: E402
    bench_burst_deadline,
    bench_capacity_fit,
    bench_envs,
    bench_faults,
    bench_fleet_scenarios,
    bench_fleet_tournament,
    bench_gamma_fit,
    bench_kernels,
    bench_overheads,
    bench_real_elastic,
    bench_roofline,
)

class _BigFleetTournament:
    """`--big` tier shim: thousand-job tournament (run_big())."""

    run = staticmethod(bench_fleet_tournament.run_big)


BENCHES = [
    ("envs", bench_envs),
    ("capacity_fit", bench_capacity_fit),
    ("gamma_fit", bench_gamma_fit),
    ("burst_deadline", bench_burst_deadline),
    ("fleet_scenarios", bench_fleet_scenarios),
    ("fleet_tournament", bench_fleet_tournament),
    ("faults", bench_faults),
    ("real_elastic", bench_real_elastic),
    ("overheads", bench_overheads),
    ("kernels", bench_kernels),
    ("roofline", bench_roofline),
]


def parse_row(row: str) -> dict:
    """'name,us,derived' -> record (derived may itself hold commas)."""
    parts = row.split(",", 2)
    name = parts[0]
    try:
        us = float(parts[1]) if len(parts) > 1 else 0.0
    except ValueError:
        us = 0.0
    return {
        "name": name,
        "us_per_call": us,
        "derived": parts[2] if len(parts) > 2 else "",
    }


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default="",
                    help="comma-separated bench names to run")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="write machine-readable results to PATH")
    ap.add_argument("--big", action="store_true",
                    help="include the thousand-job fleet_tournament_big "
                         "tier (minutes on CPU)")
    args = ap.parse_args(argv)
    only = {s for s in args.only.split(",") if s}
    benches = list(BENCHES)
    if args.big or "fleet_tournament_big" in only:
        benches.append(("fleet_tournament_big", _BigFleetTournament))
    unknown = only - {name for name, _ in benches}
    if unknown:
        ap.error(f"unknown bench(es): {sorted(unknown)}")
    selected = [(n, m) for n, m in benches if not only or n in only]

    cache_dir = enable_compilation_cache()
    import time as _time
    t_start = _time.time()
    n_existing = sum(1 for f in os.listdir(cache_dir)
                     if f.endswith("-cache")) if os.path.isdir(cache_dir) \
        else 0
    print(f"# jax compilation cache: {cache_dir} "
          f"({n_existing} entries on disk)", file=sys.stderr)

    print("name,us_per_call,derived")
    failures = 0
    results: dict[str, list[dict]] = {}
    errors: dict[str, str] = {}
    for name, mod in selected:
        try:
            rows = list(mod.run())
            results[name] = [parse_row(r) for r in rows]
            for row in rows:
                print(row, flush=True)
        except Exception as e:  # keep the harness going
            failures += 1
            errors[name] = repr(e)
            print(f"{name}.FAILED,0,{e!r}", flush=True)
            traceback.print_exc(file=sys.stderr)
    hits, misses = _cache_counts(cache_dir, t_start)
    print(f"# jax compilation cache: {hits} hits, {misses} misses "
          f"this run", file=sys.stderr)
    if args.json:
        doc = {
            "benches": results,
            "failures": failures,
            "errors": errors,
            "compilation_cache": {"dir": cache_dir, "hits": hits,
                                  "misses": misses},
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"json results -> {args.json}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
