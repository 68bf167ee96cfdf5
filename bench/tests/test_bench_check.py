"""The comparison that decides ``correct`` fails what it must: the
bfloat16 control, and a run whose timed path is broken underneath.

These drive the rest of a run (set-up, window, check) at a small grid
on the CPU, skipping only the harness's look for a chip."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.calibrate import control_gaps

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
#: the tightest limit any cell sets; every fault must read above it
LIMIT = min(harness.check_limits(w["name"])["wavefield_gap"]
            for w in BENCH["workloads"])
FWI = dict(nz=48, nx=64, dt=5e-4, dx=5.0, timesteps=600, n_shots=4,
           sponge_width=8, sponge_strength=0.0125, source_freq=12.0,
           receiver_depth=2)
CONF = dict(exchange_interval=4, scan_block=8, check_shots=2)


def run_small(mix: dict, seed: int = 5):
    run, parts = harness.run_cell(
        FWI, CONF, mix, seed=seed, seconds=0.3, traced=False,
        t_start=time.perf_counter(), devices=jax.devices(),
        limits={"wavefield_gap": LIMIT})
    return parts


def test_control_reads_above_every_limit():
    mix = harness.mix("steady")
    for seed in (1, 2, 3):
        read = control_gaps(FWI, CONF, mix, seed, [8, 400],
                            jax.devices()[:1])
        assert max(read[400]) > 10 * LIMIT, read


def test_sound_run_is_correct():
    parts = run_small(harness.mix("steady"))
    assert parts["correct"] and parts["failed"] == 0
    assert parts["steps"] > 0 and len(parts["shots"]) == 2


def _unchanged(run):
    def broken(p, pp, t0, blocks):
        out = run(p, pp, t0, blocks)
        return (p, pp) + tuple(out[2:])
    return broken


def _half_batch(run):
    def broken(p, pp, t0, blocks):
        out = run(p, pp, t0, blocks)
        half = p.shape[0] // 2
        return (out[0].at[half:].set(p[half:]),
                out[1].at[half:].set(pp[half:])) + tuple(out[2:])
    return broken


def _altered(run):
    def broken(p, pp, t0, blocks):
        out = run(p, pp, t0, blocks)
        bump = 1e-2 * jnp.max(jnp.abs(out[0]))
        return (out[0].at[:, 20, 30].add(bump),) + tuple(out[1:])
    return broken


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    import repro.fwi.driver as driver

    real = driver.make_sharded_scan_runner

    def patched(*args, **kwargs):
        run, place, k = real(*args, **kwargs)
        return fault(run), place, k

    monkeypatch.setattr(driver, "make_sharded_scan_runner", patched)
    parts = run_small(harness.mix("steady"))
    assert not parts["correct"], parts["checks"]
    assert parts["failed"] >= 1


_NO_EXCHANGE = r"""
import os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path[:0] = sys.argv[1:3]
import jax, jax.numpy as jnp
from bench import harness
import repro.fwi.domain as domain
FWI = dict(nz=48, nx=64, dt=5e-4, dx=5.0, timesteps=600, n_shots=4,
           sponge_width=8, sponge_strength=0.0125, source_freq=12.0,
           receiver_depth=2)
CONF = dict(exchange_interval=4, scan_block=8, check_shots=2)
mix = harness.mix("burst-cycle")
mix["phases"] = [{"stripes": 1, "steps": 16}, {"stripes": 2, "steps": 48}]
if sys.argv[3] == "broken":
    def no_exchange(edges_r, edges_l, axis_name):
        return jnp.zeros_like(edges_r), jnp.zeros_like(edges_l)
    domain._exchange_halo = no_exchange
run, parts = harness.run_cell(
    FWI, CONF, mix, seed=11, seconds=0.5, traced=False,
    t_start=time.perf_counter(), devices=jax.devices(),
    limits={"wavefield_gap": float(sys.argv[4])})
print(parts["correct"], len(run.completed_transitions()),
      parts["checks"]["wavefield_gap"]["value"])
"""


@pytest.mark.parametrize("variant", ["sound", "broken"])
def test_exchange_left_out_is_not_correct(variant):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _NO_EXCHANGE, str(ROOT / "src"), str(ROOT),
         variant, repr(LIMIT)],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    correct, transitions, gap = out.stdout.split()
    assert int(transitions) >= 2
    assert correct == ("True" if variant == "sound" else "False"), gap
