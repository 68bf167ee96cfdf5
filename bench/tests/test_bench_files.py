"""Every cell, configuration, mix, check and metric of BENCHMARK.json
resolves to its own file, and the command refuses to run without a
TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]


def test_names_and_keys_keep_to_the_format():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = CELLS + METRICS + [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_configuration_loads_by_name(name):
    from repro.fwi.solver import FWIConfig

    conf = harness.config_file(BENCH, name)
    cfg = FWIConfig(**conf["fwi"])
    assert conf["precision"] == "float32"
    assert cfg.n_shots % conf["check_shots"] == 0
    assert conf["scan_block"] % conf["exchange_interval"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_its_mix_check_and_metrics(name):
    c = harness.cell(BENCH, name)
    mix = harness.mix(c["traffic"])
    stripes = {p["stripes"] for p in mix["phases"]}
    assert max(stripes) <= c["chips"]
    assert harness.check_limits(name)["wavefield_gap"] > 0
    for traced in (False, True):
        ms = harness.cell_metrics(BENCH, name, traced)
        assert ms, (name, traced)
        for m in ms:
            assert callable(harness.metric_reader(m["name"]))
    e2e = {m["name"] for m in harness.cell_metrics(BENCH, name, False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in harness.cell_metrics(BENCH, name, True):
        assert m["moves"] in e2e


@pytest.mark.parametrize("name", METRICS)
def test_metric_reader_loads_by_name(name):
    assert callable(harness.metric_reader(name))


def test_chips_refuses_a_cpu():
    with pytest.raises(harness.NoChip):
        harness.chips(1)


def _run(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", "0", "--seconds", "10", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_without_a_tpu_prints_no_result():
    out = _run(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_command_without_the_program_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "repro" in out.stderr
