#!/usr/bin/env bash
# Lint + tier-1 verification + engine/benchmark smokes.
#
# 1. the repo's tier-1 test command (ROADMAP.md): full pytest, -x -q
# 2. fused-engine smoke: the sharded scan runner's k=4 fused blocks
#    (interpret-mode Pallas AND the pure-XLA block body) must match the
#    one-step-per-block reference runner on a tiny config — a fast
#    end-to-end equivalence gate for the engine.
# 3. bench-schema smoke: `benchmarks/run.py --json` on a cheap bench
#    subset must produce the machine-readable schema (bench schema
#    breakage fails CI before it breaks the perf-trajectory tooling).
# 4. overheads smoke: the §3.3 overhead rows (message sizes, halo
#    plan counts) must produce output without raising.  Speed is
#    measured on the chip by bench/, not here.
# 5. fleet smoke: the autoscaler policy × scenario sweep must uphold
#    the paper's claim at fleet scale — the deadline-aware policy beats
#    no-burst on hit-rate in the overload scenario at lower cost than
#    always-burst, and retires the cloud pod once a spike clears.
# 5b. fleet-tournament smoke: the policy × scheduler × scenario grid
#    of the multi-tenant queue layer (DESIGN.md §16) must uphold the
#    §3.3 claim at fleet scale — some deadline-aware (scheduler,
#    policy) cell beats FIFO+no-burst on hit-rate while spending less
#    than FIFO+always-burst on the overload scenario — and conserve
#    every queued job.
# 5c. coverage floors: per-package statement-coverage gates from each
#    package's own test modules (pytest-cov when installed, stdlib
#    `trace` fallback otherwise — scripts/simcov.py): repro.sim >=90%,
#    repro.sim.faults >=90%, repro.kernels.stencil and repro.fwi.solver
#    >=85% (DESIGN.md §17).
# 5d. fault-storm smoke (DESIGN.md §19): the hardened `plan` loop must
#    keep its hit-rate >= the unhardened baseline under the SAME fault
#    draws at bounded cost (<=1.5x a fault-free run), the fault run
#    must be bit-deterministic per seed, and scavenger preemption must
#    admit the expired weighted job within one evaluation interval —
#    the acceptance rows also ride the bench-schema gate (faults bench).
# 6. real-elastic smoke: a small FWI config driven by the `react`
#    policy through the real orchestrator (2 host devices) must apply
#    at least one GROW and one RETIRE through real re-striping and keep
#    the final wavefield equal to an unscaled reference run — the
#    checkpoint/remesh/reshard invariance gate for the real-session
#    elastic loop (DESIGN.md §14).  The sim-vs-real bench rows
#    (cost-aware beats cost-blind at equal hit-rate) are asserted via
#    the bench-schema smoke, which also registers the new bench.
# 7. big-grid streaming smoke: a 2048² k=4 block of one shot through
#    the STREAMED Pallas kernel (interpret mode — real BlockSpec/DMA
#    semantics) under a forced small VMEM budget must be genuinely
#    multi-strip (no whole-height fallback) and match the XLA
#    reference; the strips mirror must stay BITWISE (DESIGN.md §15).
# 8. lint (runs FIRST, before the test tiers): scripts/lint.py --ci —
#    the repro-lint static-analysis suite (DESIGN.md §18): vmem-budget,
#    dma-pairing, sim-determinism, tracer-hygiene, design-citations
#    (the latter subsumes the old docs-consistency grep gate).  The
#    repo must lint clean, the JSON report must carry all five rules,
#    and the stage must finish in under 10 s.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

# Persistent XLA compilation cache: every python step below re-lowers
# the same executables; a workspace-local disk cache turns the repeat
# compiles into loads (benchmarks/run.py prints the hit/miss counts).
export JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache}"
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0
export JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES=-1

echo "== lint =="
LINT_T0=$SECONDS
python scripts/lint.py --ci --json /tmp/lint_ci.json
LINT_ELAPSED=$((SECONDS - LINT_T0))
python - <<'EOF'
import json

doc = json.load(open("/tmp/lint_ci.json"))
assert doc["version"] == 1, doc
assert doc["count"] == 0 and doc["findings"] == [], doc["findings"]
assert set(doc["rules"]) == {
    "vmem-budget", "dma-pairing", "sim-determinism",
    "tracer-hygiene", "design-citations",
}, doc["rules"]
print("lint json schema OK (5 rules, 0 findings)")
EOF
if [ "$LINT_ELAPSED" -ge 10 ]; then
    echo "lint stage took ${LINT_ELAPSED}s (budget: <10s)" >&2
    exit 1
fi

echo "== tier-1 tests =="
python -m pytest -x -q

echo "== fused-engine smoke =="
python - <<'EOF'
import jax, jax.numpy as jnp, numpy as np
from repro.fwi.solver import FWIConfig, ShotState, make_block_runner
from repro.fwi.domain import make_sharded_scan_runner, stripe_mesh

cfg = FWIConfig(nz=32, nx=64, timesteps=8, n_shots=1, sponge_width=4)
st = ShotState.init(cfg)
# the reference: one step per block on the XLA path
p, pp, ref_tr = make_block_runner(cfg, k=1, use_pallas=False)(
    st.p, st.p_prev, 0, 8)

for use_pallas, label, tol in ((False, "xla-block", 1.2e-38),
                               (True, "pallas-interpret", 1e-5)):
    run, place, k = make_sharded_scan_runner(
        cfg, stripe_mesh(1), k=4, use_pallas=use_pallas
    )
    a, b = place((st.p, st.p_prev))
    trs = []
    for bb in range(2):
        a, b, tr = run(a, b, bb * k, 1)
        trs.append(tr)
    tr = jnp.concatenate(trs, axis=1)
    perr = float(jnp.max(jnp.abs(np.asarray(a) - np.asarray(p))))
    terr = float(jnp.max(jnp.abs(np.asarray(tr) - np.asarray(ref_tr))))
    assert perr <= tol and terr <= tol, (label, perr, terr)
    print(f"fused-engine smoke [{label}]: max err p={perr:.2e} tr={terr:.2e}")
print("fused-engine smoke OK")
EOF

echo "== bench-schema smoke =="
python benchmarks/run.py --only envs,capacity_fit,real_elastic,faults \
    --json /tmp/bench_ci.json
python - <<'EOF'
import json

doc = json.load(open("/tmp/bench_ci.json"))
assert doc["failures"] == 0, doc["errors"]
assert set(doc["benches"]) == {
    "envs", "capacity_fit", "real_elastic", "faults",
}, doc["benches"].keys()
for name, rows in doc["benches"].items():
    assert rows, f"bench {name} produced no rows"
    for rec in rows:
        assert set(rec) == {"name", "us_per_call", "derived"}, rec
        assert isinstance(rec["us_per_call"], float)
# the sim-vs-real acceptance rows (DESIGN.md §14): cost-aware planning
# beats the cost-blind solve on $ at equal hit-rate in the superlinear
# scenario, and is never worse on the real orchestrator
by_name = {r["name"]: r for r in doc["benches"]["real_elastic"]}
assert by_name["real_elastic.costaware_cheaper_at_equal_hit"]["derived"] \
    == "1"
assert by_name["real_elastic.real_costaware_no_worse"]["derived"] == "1"
assert by_name["real_elastic.sim_vs_real"]["derived"].startswith(
    "hit_match=1")
# the §19 robustness acceptance rows: hardened hit-rate >= the
# unhardened baseline, cost bounded vs a fault-free run, and the
# preempted admission landing within one evaluation interval
by_name = {r["name"]: r for r in doc["benches"]["faults"]}
assert by_name["faults.hardened_hit_ge_baseline"]["derived"] == "1"
assert by_name["faults.hardened_cost_bounded"]["derived"] == "1"
assert by_name["faults.preempt_admit_latency_ok"]["derived"] == "1"
print("bench json schema OK (incl. real_elastic + faults rows)")
EOF

echo "== overheads smoke =="
python - <<'EOF'
import sys
sys.path.insert(0, ".")
from benchmarks import bench_overheads

rows = bench_overheads.run()
assert rows, "bench_overheads produced no rows"
for r in rows:
    print(r)
EOF

echo "== fleet smoke =="
python - <<'EOF'
import sys
sys.path.insert(0, ".")
from benchmarks import bench_fleet_scenarios

rows = bench_fleet_scenarios.run()
for r in rows:
    print(r)

def derived(name):
    return next(
        r.rsplit(",", 1)[1] for r in rows if r.startswith(name)
    )

assert derived("fleet.overload_plan_beats_noburst") == "1", \
    "deadline-aware policy must beat no-burst on the overload scenario"
assert derived("fleet.overload_plan_cheaper_than_always") == "1", \
    "deadline-aware policy must undercut always-burst on cloud cost"
assert derived("fleet.spike_cloud_retired_at_end") == "1", \
    "cloud pod must be retired once the transient spike clears"
EOF

echo "== fleet-tournament smoke =="
python - <<'EOF'
import sys
sys.path.insert(0, ".")
from benchmarks import bench_fleet_tournament

rows = bench_fleet_tournament.run()
for r in rows:
    print(r)

def derived(name):
    return next(
        r.rsplit(",", 1)[1] for r in rows if r.startswith(name)
    )

assert derived("fleet_tournament.aware_beats_fifo_noburst") == "1", \
    "some deadline-aware (scheduler, policy) cell must beat " \
    "FIFO+no-burst on hit-rate at lower cloud $ than FIFO+always-burst"
assert derived("fleet_tournament.jobs_conserved") == "1", \
    "every submitted job must end finished/running/queued in every cell"
EOF

echo "== fault-storm smoke =="
python - <<'EOF'
import dataclasses
import hashlib

from repro.sim import FleetSim, PlanAutoscaler
from repro.sim.scenarios import fault_storm, preemption_pressure

def digest(rec):
    return hashlib.sha256(
        repr(dataclasses.asdict(rec)).encode()
    ).hexdigest()

h = FleetSim(fault_storm(0, hardened=True), PlanAutoscaler, seed=0).run()
again = FleetSim(fault_storm(0, hardened=True), PlanAutoscaler,
                 seed=0).run()
assert digest(h) == digest(again), "fault run not bit-deterministic"
b = FleetSim(fault_storm(0, hardened=False), PlanAutoscaler,
             seed=0).run()
assert all(j.finished for j in h.jobs), "hardened run must finish"
assert h.hit_rate >= b.hit_rate, (h.hit_rate, b.hit_rate)
clean = dataclasses.replace(fault_storm(0, hardened=True),
                            faults=None, retry=None, name="clean")
c = FleetSim(clean, PlanAutoscaler, seed=0).run()
assert h.cloud_cost <= 1.5 * c.cloud_cost, (h.cloud_cost, c.cloud_cost)
sc = preemption_pressure(0)
p = FleetSim(sc, PlanAutoscaler, seed=0).run()
gold = next(j for j in p.jobs if j.name == "gold0")
admit = next(t for t, k, _ in gold.events if k == "admit")
limit = 60.0 + sc.starve_patience_s + sc.eval_interval_s
assert gold.met_deadline and admit <= limit, (admit, limit)
print(f"fault-storm smoke OK: hardened hit={h.hit_rate:.2f} >= "
      f"baseline {b.hit_rate:.2f}, cost {h.cloud_cost:.0f} <= "
      f"1.5x clean {c.cloud_cost:.0f}, preempt admit at {admit:.0f}s")
EOF

echo "== coverage floors =="
python scripts/simcov.py

echo "== real-elastic smoke =="
python - <<'EOF'
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import numpy as np
import jax.numpy as jnp

from repro.core import (
    BurstPlanner, DeadlinePredictor, ElasticOrchestrator,
    LogCapacityModel, OverheadModel, PodSpec, Resources, elastic_chips,
)
from repro.fwi.driver import TimeModel, elastic_stripes_for, \
    fwi_session_factory
from repro.fwi.solver import FWIConfig, run_forward
from repro.sim import ReactAutoscaler

cfg = FWIConfig(nz=32, nx=64, timesteps=80, n_shots=1, sponge_width=4)
W, K, LEGAL = 64.0, 1.4, [16, 32, 64]
cs = sorted(set(LEGAL) | {64})
planner = BurstPlanner(
    cluster_model=LogCapacityModel.fit(cs, [W / c for c in cs]),
    cloud_model=LogCapacityModel.fit(cs, [K * W / c for c in cs]),
    chips_cluster=64, legal_slices=LEGAL,
    overheads=OverheadModel(ckpt_s=3.0, provision_s=6.0, restart_s=3.0),
    price_per_chip_hour=3.0,
)
orch = ElasticOrchestrator(
    planner=planner, predictor=DeadlinePredictor(300.0),
    check_every=6, ckpt_every=24, eval_interval_s=6.0, cloud_slowdown=K,
)
base = fwi_session_factory(
    cfg, TimeModel(chip_seconds_per_step=W, jitter=0.01),
    stripes_for=elastic_stripes_for(1, 2),
    exchange_interval=4, scan_block=8,
)
sessions = []

def factory(res, start, restored):
    s = base(res, start, restored)
    sessions.append(s)
    return s

rec = orch.run(
    session_factory=factory,
    initial=Resources(pods=[PodSpec(chips=64, name="cluster")],
                      shares=[1.0]),
    steps_total=80, autoscaler=ReactAutoscaler(slowdown=K),
    deadline_changes=[(15.0, 70.0), (45.0, 300.0)],
)
kinds = [e.detail["kind"] for e in rec.events if e.kind == "scale"]
assert "grow" in kinds and "retire" in kinds, kinds
assert elastic_chips(rec.final_resources) == 0
assert max(s._n_stripes for s in sessions) == 2, "grow must re-stripe"
ref, _ = run_forward(cfg, steps=80)
last = sessions[-1]
assert last.t == 80, last.t
err = float(jnp.max(jnp.abs(np.asarray(last.p) - np.asarray(ref.p))))
assert err < 1e-8, f"wavefield checksum broke across scale events: {err}"
print(f"real-elastic smoke OK: scales={kinds} wavefield max err={err:.2e}")
EOF

echo "== big-grid streaming smoke =="
python - <<'EOF'
import jax, jax.numpy as jnp, numpy as np
from repro.kernels.stencil.kernel import (
    HALO, pick_bz_stream, should_stream, wave_block_shots_stream_pallas,
)
from repro.kernels.stencil.ref import wave_block_ref, wave_block_strips_ref

nz = nx = 2048
k, budget = 4, 4 * 1024 * 1024
assert should_stream(nz, nx, k, vmem_budget=budget)
bz = pick_bz_stream(nz, nx, k, vmem_budget=budget)
assert bz + 2 * k * HALO < nz, (bz, "whole-height fallback")
ks = jax.random.split(jax.random.key(0), 4)
p = jax.random.normal(ks[0], (nz, nx), jnp.float32)
pp = jax.random.normal(ks[1], (nz, nx), jnp.float32)
v = jax.random.uniform(ks[2], (nz, nx), jnp.float32, 0.05, 0.2)
s = jnp.clip(jax.random.uniform(ks[3], (nz, nx)), 0.9, 1.0)
srcv = jnp.linspace(0.5, 1.0, k)
ref = wave_block_ref(p, pp, v, s, srcv, 100, 200, receiver_row=7)
strips = wave_block_strips_ref(p, pp, v, s, srcv, 100, 200,
                               receiver_row=7, bz=bz)
for a, b in zip(ref, strips):
    assert np.array_equal(np.asarray(a), np.asarray(b)), "strips not bitwise"
out = wave_block_shots_stream_pallas(
    p[None], pp[None], v, s, srcv, jnp.array([100]), jnp.array([200]),
    receiver_row=7, bz=bz, vmem_budget=budget)
err = max(float(jnp.max(jnp.abs(a - b[0]))) for a, b in zip(ref, out))
assert err <= 1e-5, err
print(f"big-grid streaming smoke OK: 2048x2048 k=4 bz={bz} "
      f"({nz // bz} strips) max err={err:.2e}")
EOF

echo "CI OK"
