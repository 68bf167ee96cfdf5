"""repro-lint suite (DESIGN.md §18).

Per-rule fixture pairs (a bad source that must be flagged, a good one
that must pass), suppression semantics, the symbolic evaluator, and —
the acceptance surface — real-source injection tests: deliberately
re-introducing a VMEM-formula drift, an unpaired DMA ``.start()``, a
double-buffer slot mismatch, an unsorted dict iteration in
``repro.sim``, or a ``.item()`` in traced code must each produce a
finding, while the pristine tree stays clean (so the CI lint stage
both passes today and would catch the regression).
"""
import ast
import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import (
    Analyzer,
    DesignCitationsRule,
    DmaPairingRule,
    Finding,
    SimDeterminismRule,
    SymEval,
    SymEvalError,
    TracerHygieneRule,
    VmemBudgetRule,
    analyze_source,
    default_rules,
    render_human,
    to_json,
)

ROOT = pathlib.Path(__file__).resolve().parents[1]
KERNEL = ROOT / "src" / "repro" / "kernels" / "stencil" / "kernel.py"
FLEET = ROOT / "src" / "repro" / "sim" / "fleet.py"
KREL = "src/repro/kernels/stencil/kernel.py"
FREL = "src/repro/sim/fleet.py"

ALL_RULE_IDS = {"vmem-budget", "dma-pairing", "sim-determinism",
                "tracer-hygiene", "design-citations"}


# ---------------------------------------------------------------------------
# vmem-budget fixtures
# ---------------------------------------------------------------------------

VMEM_RESIDENT = textwrap.dedent('''\
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ELEM = 4


    def resident_vmem_bytes(nz, nx, k, s=1, bz=None):
        return 5 * s * nz * nx * ELEM


    def stream_vmem_bytes(nz, nx, bz, k, s=1):
        return 5 * s * bz * nx * ELEM


    def _body(p_ref, pp_ref, v_ref, s_ref, o_ref):
        o_ref[...] = p_ref[...]


    def wave_block_shots_pallas(p, pp, v, sp):
        spec = pl.BlockSpec((ns, nz, nx), lambda: (0, 0, 0))
        return pl.pallas_call(
            _body,
            in_specs=[spec, spec, spec, spec],
            out_specs=spec,
            scratch_shapes=[],
        )(p, pp, v, sp)
''')

VMEM_STREAM = textwrap.dedent('''\
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ELEM = 4


    def resident_vmem_bytes(nz, nx, k, s=1, bz=None):
        return 5 * s * nz * nx * ELEM


    def stream_vmem_bytes(nz, nx, bz, k, s=1):
        return 5 * s * bz * nx * ELEM


    def _body(hbm_ref, o_ref, win):
        o_ref[...] = win[...]


    def wave_block_shots_stream_pallas(p, bz, vmem_budget):
        any_spec = pl.BlockSpec(memory_space=pltpu.ANY)
        return pl.pallas_call(
            _body,
            in_specs=[any_spec],
            out_specs=any_spec,
            scratch_shapes=[pltpu.VMEM((ns, 5 * bz, nx), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=vmem_budget,
            ),
        )(p)
''')

VMEM_DISPATCH = textwrap.dedent('''\
    def resident_vmem_bytes(nz, nx, k, s=1, bz=None):
        return 5 * s * nz * nx * 4


    def stream_vmem_bytes(nz, nx, bz, k, s=1):
        return 5 * s * bz * nx * 4


    def should_stream(nz, nx, k, vmem_budget, s=1):
        return resident_vmem_bytes(nz, nx, k, s=s) > vmem_budget
''')


def _vmem(src):
    return analyze_source(src, VmemBudgetRule(),
                          filename="src/repro/kernels/toy/kernel.py")


def test_vmem_mapped_wrapper_matching_formula_is_clean():
    assert _vmem(VMEM_RESIDENT) == []


def test_vmem_mapped_wrapper_drift_is_flagged():
    bad = VMEM_RESIDENT.replace("return 5 * s * nz * nx * ELEM",
                                "return 6 * s * nz * nx * ELEM")
    fs = _vmem(bad)
    assert len(fs) == 1
    assert fs[0].rule == "vmem-budget"
    assert "drift" in fs[0].message


def test_vmem_streamed_wrapper_with_limit_is_clean():
    assert _vmem(VMEM_STREAM) == []


def test_vmem_streamed_wrapper_without_limit_is_flagged():
    bad = VMEM_STREAM.replace(
        "        compiler_params=pltpu.CompilerParams(\n"
        "            vmem_limit_bytes=vmem_budget,\n"
        "        ),\n", "")
    fs = _vmem(bad)
    assert len(fs) == 1
    assert "vmem_limit_bytes" in fs[0].message


def test_vmem_unmapped_kernel_with_scratch_is_flagged():
    src = VMEM_RESIDENT.replace("wave_block_shots_pallas", "fancy_new_kernel") \
        .replace("scratch_shapes=[]",
                 "scratch_shapes=[pltpu.VMEM((128, 128), jnp.float32)]")
    fs = _vmem(src)
    assert len(fs) == 1
    assert "no capacity-formula mapping" in fs[0].message


def test_vmem_unmapped_kernel_without_scratch_is_clean():
    src = VMEM_RESIDENT.replace("wave_block_shots_pallas", "plain_kernel")
    assert _vmem(src) == []


def test_vmem_dispatch_rule_consistent_is_clean():
    assert _vmem(VMEM_DISPATCH) == []


def test_vmem_dispatch_rule_drift_is_flagged():
    bad = VMEM_DISPATCH.replace("> vmem_budget", "> 2 * vmem_budget")
    fs = _vmem(bad)
    assert len(fs) == 1
    assert "dispatch rule drifted" in fs[0].message


def test_vmem_rule_ignores_files_outside_kernels():
    fs = analyze_source(VMEM_RESIDENT.replace(
        "return 5 * s * nz * nx * ELEM", "return 6 * s * nz * nx * ELEM"),
        VmemBudgetRule(), filename="src/repro/fwi/solver.py")
    assert fs == []


# ---------------------------------------------------------------------------
# dma-pairing fixtures
# ---------------------------------------------------------------------------

DMA_GOOD = textwrap.dedent('''\
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu


    def _stream_kernel(hbm, out, sem):
        i = pl.program_id(0)
        n = pl.num_programs(0)

        def dma(slot, strip):
            return [pltpu.make_async_copy(hbm.at[strip], out.at[slot],
                                          sem.at[slot])]

        @pl.when(i == 0)
        def _warm():
            for c in dma(i % 2, i):
                c.start()

        @pl.when(i + 1 < n)
        def _prefetch():
            for c in dma((i + 1) % 2, i + 1):
                c.start()

        for c in dma(i % 2, i):
            c.wait()
''')


def _dma(src):
    return analyze_source(src, DmaPairingRule(),
                          filename="src/repro/kernels/toy/kernel.py")


def test_dma_correct_double_buffer_idiom_is_clean():
    assert _dma(DMA_GOOD) == []


def test_dma_start_without_wait_is_flagged():
    bad = DMA_GOOD.replace("    for c in dma(i % 2, i):\n"
                           "        c.wait()\n", "")
    fs = _dma(bad)
    assert len(fs) == 1
    assert "no matching `.wait()`" in fs[0].message


def test_dma_wait_without_start_is_flagged():
    bad = DMA_GOOD.replace("c.start()", "pass")
    fs = _dma(bad)
    assert len(fs) == 1
    assert "no matching `.start()`" in fs[0].message


def test_dma_all_waits_guarded_is_flagged():
    bad = DMA_GOOD.replace(
        "    for c in dma(i % 2, i):\n        c.wait()",
        "    @pl.when(i > 0)\n"
        "    def _late():\n"
        "        for c in dma(i % 2, i):\n"
        "            c.wait()")
    fs = _dma(bad)
    assert len(fs) == 1
    assert "guarded" in fs[0].message


def test_dma_slot_mismatch_is_flagged():
    bad = DMA_GOOD.replace("for c in dma((i + 1) % 2, i + 1):",
                           "for c in dma(i % 2, i + 1):")
    fs = _dma(bad)
    assert len(fs) == 1
    assert "slot mismatch" in fs[0].message


def test_dma_inline_handle_pairing():
    src = textwrap.dedent('''\
        from jax.experimental.pallas import tpu as pltpu


        def _kernel(hbm, out, sem):
            copy = pltpu.make_async_copy(hbm, out, sem)
            copy.start()
    ''')
    fs = _dma(src)
    assert len(fs) == 1 and "no matching `.wait()`" in fs[0].message
    assert _dma(src + "    copy.wait()\n") == []


# ---------------------------------------------------------------------------
# sim-determinism fixtures
# ---------------------------------------------------------------------------

SIM_BAD = textwrap.dedent('''\
    import random
    import numpy as np
    import time


    def tally(usage, members):
        for t, u in usage.items():
            _ = (t, u)
        picks = {m for m in members}
        order = [m.name for m in picks]
        ranks = {m: 0 for m in picks}
        mat = list(picks)
        jitter = np.random.rand()
        rng = np.random.default_rng()
        now = time.time()
        tag = id(usage)
        members.sort(key=id)
        return order, ranks, mat, jitter, rng, now, tag
''')

SIM_GOOD = textwrap.dedent('''\
    import numpy as np


    def tally(usage, members):
        for t, u in sorted(usage.items()):
            _ = (t, u)
        picks = {m for m in members}
        total = sum(m.cost for m in picks)
        order = sorted(m.name for m in picks)
        rng = np.random.default_rng(1234)
        return total, order, rng.normal()
''')


def _sim(src, filename="src/repro/sim/toy.py"):
    return analyze_source(src, SimDeterminismRule(), filename=filename)


def test_sim_determinism_flags_every_entropy_source():
    fs = _sim(SIM_BAD)
    assert len(fs) == 10
    blob = "\n".join(f.message for f in fs)
    for frag in ("for-loop over a dict view", "comprehension over a set",
                 "materializes a set/dict view", "stdlib `random`",
                 "global numpy RNG", "without a seed", "wall clock",
                 "id() is a CPython address", "key=id"):
        assert frag in blob, frag


def test_sim_determinism_ordered_idioms_are_clean():
    assert _sim(SIM_GOOD) == []


def test_sim_determinism_only_applies_under_sim():
    assert _sim(SIM_BAD, filename="src/repro/fwi/driver.py") == []


# ---------------------------------------------------------------------------
# tracer-hygiene fixtures
# ---------------------------------------------------------------------------

TRACER_BAD = textwrap.dedent('''\
    import functools

    import jax
    import numpy as np
    from jax.experimental import pallas as pl


    def _helper(x):
        return x.item()


    @functools.partial(jax.jit, static_argnames=("steps",))
    def run(x, steps):
        y = _helper(x)
        z = float(x)
        print(z)
        w = np.asarray(x)
        return y + w + float(steps)


    def _kernel(x_ref, o_ref):
        print("inside kernel")


    def call_kernel(x):
        return pl.pallas_call(_kernel, out_shape=x)(x)
''')

TRACER_GOOD = textwrap.dedent('''\
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np


    def _staging(cfg):
        return np.asarray(cfg.grid)


    @functools.partial(jax.jit, static_argnames=("bz",))
    def run(x, bz):
        k = int(x.shape[0])
        s = float(bz)
        return x * k * s


    def outer(x):
        tbl = _staging(x)
        return run(jnp.asarray(tbl), bz=4)
''')


def _tracer(src):
    return analyze_source(src, TracerHygieneRule(),
                          filename="src/repro/fwi/toy.py")


def test_tracer_hygiene_flags_syncs_in_reachable_functions():
    fs = _tracer(TRACER_BAD)
    assert len(fs) == 5
    blob = "\n".join(f.message for f in fs)
    assert "`.item()` in traced `_helper`" in blob
    assert "`float()` on a traced value in `run`" in blob
    assert "`print()` in traced `run`" in blob
    assert "`np.asarray` in traced `run`" in blob
    assert "`print()` in traced `_kernel`" in blob


def test_tracer_hygiene_builders_and_static_casts_are_clean():
    assert _tracer(TRACER_GOOD) == []


def test_tracer_hygiene_scan_and_lambda_roots():
    src = textwrap.dedent('''\
        import jax


        def _step(carry, x):
            jax.debug.print("t={}", x)
            val = carry.item()
            return val, x


        def _helper2(x):
            return float(x)


        def drive(xs):
            return jax.lax.scan(_step, 0.0, xs)


        def apply(xs):
            return jax.vmap(lambda x: _helper2(x) + 1)(xs)
    ''')
    fs = _tracer(src)
    assert len(fs) == 2
    blob = "\n".join(f.message for f in fs)
    assert "`.item()` in traced `_step`" in blob          # scan body
    assert "`float()` on a traced value in `_helper2`" in blob  # via vmap
    # jax.debug.print is NOT print() — it must stay unflagged
    assert "debug" not in blob


# ---------------------------------------------------------------------------
# design-citations
# ---------------------------------------------------------------------------

def test_design_citations_resolution(tmp_path):
    (tmp_path / "DESIGN.md").write_text(
        "# §1 — intro\n\n## §2.5 — tiling\n")
    good = '"""Implements the plan (DESIGN.md §1, §2.5)."""\n'
    assert analyze_source(good, DesignCitationsRule(),
                          filename="src/x.py", root=tmp_path) == []
    bad = 'X = 1\n"""See DESIGN.md §9 for details."""\n'
    fs = analyze_source(bad, DesignCitationsRule(),
                        filename="src/x.py", root=tmp_path)
    assert len(fs) == 1
    assert fs[0].line == 2 and "no §9 heading" in fs[0].message


def test_design_citations_skips_when_design_missing(tmp_path):
    fs = analyze_source('"""DESIGN.md §9"""\n', DesignCitationsRule(),
                        filename="src/x.py", root=tmp_path / "nowhere")
    assert fs == []


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

def test_suppression_on_flagged_line():
    src = "import random  # lint: disable=sim-determinism -- fixture\n"
    assert _sim(src) == []


def test_suppression_comment_block_covers_next_code_line():
    src = ("# lint: disable=sim-determinism -- justification that\n"
           "# spans two comment lines\n"
           "import random\n")
    assert _sim(src) == []


def test_suppression_for_other_rule_does_not_apply():
    src = "import random  # lint: disable=tracer-hygiene\n"
    assert len(_sim(src)) == 1


def test_suppression_all_keyword():
    src = "import random  # lint: disable=all\n"
    assert _sim(src) == []


# ---------------------------------------------------------------------------
# symbolic evaluator
# ---------------------------------------------------------------------------

def test_symeval_calls_and_builtins():
    tree = ast.parse(textwrap.dedent('''\
        K = 3


        def f(a, b=2, *, c=5):
            d = a + b
            if d > 4:
                return d * c
            return -d


        def g(x):
            return min(x, K) + max(x, K) + int(x / 2) + abs(-x)


        def h():
            raise ValueError("nope")
    '''))
    ev = SymEval(tree)
    assert ev.call("f", [3]) == 25
    assert ev.call("f", [1]) == -3
    assert ev.call("f", [1], {"b": 9}) == 50
    assert ev.call("g", [4]) == 13
    assert ev.eval(ast.parse("K + 1", mode="eval").body) == 4
    with pytest.raises(SymEvalError):
        ev.call("h")                  # raise reached
    with pytest.raises(SymEvalError):
        ev.call("missing")
    with pytest.raises(SymEvalError):
        ev.call("f", [])              # missing required arg


def test_symeval_scope_env_and_defaults():
    tree = ast.parse(textwrap.dedent('''\
        def wrap(n, bz=8, budget=None):
            k = n // 2
            total = k * bz
            pick = 16 if budget is None else budget
    '''))
    fdef = tree.body[0]
    ev = SymEval(tree, env={"n": 10}, scope=fdef)
    name = lambda s: ast.parse(s, mode="eval").body  # noqa: E731
    assert ev.eval(name("total")) == 40       # env n, default bz
    assert ev.eval(name("pick")) == 16        # is-None conditional
    assert ev.eval(name("n > 4 and bz")) == 8
    with pytest.raises(SymEvalError):
        ev.eval(name("unknown_name"))
    with pytest.raises(SymEvalError):
        ev.eval(name("[x for x in (1, 2)]"))  # outside the subset


# ---------------------------------------------------------------------------
# real-source injections (the acceptance surface)
# ---------------------------------------------------------------------------

def test_real_kernels_are_clean_under_every_rule():
    src = KERNEL.read_text()
    for rule in (VmemBudgetRule(), DmaPairingRule(), TracerHygieneRule()):
        assert analyze_source(src, rule, filename=KREL) == []


def test_injected_vmem_formula_drift_is_caught():
    src = KERNEL.read_text()
    old = "2 * 2 * s * bz * nx"
    assert old in src
    fs = analyze_source(src.replace(old, "2 * 3 * s * bz * nx"),
                        VmemBudgetRule(), filename=KREL)
    # both formula-mapped wrappers drift from the edited formula
    assert len(fs) == 2
    assert all("drift" in f.message for f in fs)


def test_injected_should_stream_drift_is_caught():
    src = KERNEL.read_text()
    old = "return resident_vmem_bytes(nz, nx, k, s=s) > budget"
    assert old in src
    fs = analyze_source(
        src.replace(old,
                    "return resident_vmem_bytes(nz, nx, k, s=s) "
                    "> 2 * budget"),
        VmemBudgetRule(), filename=KREL)
    assert len(fs) == 1 and "dispatch rule drifted" in fs[0].message


def test_injected_unpaired_dma_start_is_caught():
    src = KERNEL.read_text()
    old = ("    slot = i % 2\n"
           "    for c in dma(slot, i):           "
           "# wait for our window to land\n"
           "        c.wait()")
    assert old in src
    fs = analyze_source(src.replace(old, "    slot = i % 2", 1),
                        DmaPairingRule(), filename=KREL)
    assert len(fs) == 1 and "no matching `.wait()`" in fs[0].message


def test_injected_dma_slot_mismatch_is_caught():
    src = KERNEL.read_text()
    old = "for c in dma((i + 1) % 2, i + 1):"
    assert src.count(old) == 1        # the one streamed kernel
    fs = analyze_source(src.replace(old, "for c in dma(i % 2, i + 1):"),
                        DmaPairingRule(), filename=KREL)
    assert len(fs) == 1
    assert all("slot mismatch" in f.message for f in fs)


def test_injected_dict_iteration_in_sim_is_caught():
    src = FLEET.read_text()
    assert analyze_source(src, SimDeterminismRule(), filename=FREL) == []
    old = "for j in self.jobs:"
    assert old in src
    fs = analyze_source(
        src.replace(old, "for _t, _u in usage.items():", 1),
        SimDeterminismRule(), filename=FREL)
    assert len(fs) == 1 and "dict view" in fs[0].message


def test_injected_item_in_traced_kernel_is_caught():
    src = KERNEL.read_text()
    old = "prevd = cur * sw"
    assert old in src
    fs = analyze_source(src.replace(old, "prevd = (cur * sw).item()", 1),
                        TracerHygieneRule(), filename=KREL)
    assert len(fs) == 1 and "`.item()`" in fs[0].message


# ---------------------------------------------------------------------------
# framework plumbing, whole-repo run, CLI
# ---------------------------------------------------------------------------

def test_finding_render_and_json_roundtrip():
    f = Finding("a.py", 3, 0, "some-rule", "msg")
    assert f.render() == "a.py:3:0: some-rule msg"
    assert render_human([f]) == f.render()
    doc = json.loads(to_json([f], rules=["some-rule"]))
    assert doc["version"] == 1 and doc["count"] == 1
    assert doc["findings"][0] == {"file": "a.py", "line": 3, "col": 0,
                                  "rule": "some-rule", "message": "msg"}


def test_analyzer_whole_repo_is_clean():
    analyzer = Analyzer(default_rules(), ROOT)
    ctxs = analyzer.load(["src", "examples"])
    assert len(ctxs) > 50
    assert {r.name for r in analyzer.rules} == ALL_RULE_IDS
    findings = analyzer.run(ctxs)
    assert findings == [], render_human(findings)


def test_analyzer_loads_single_file():
    analyzer = Analyzer([DmaPairingRule()], ROOT)
    ctxs = analyzer.load([str(KERNEL)])
    assert [c.rel for c in ctxs] == [KREL]
    assert analyzer.run(ctxs) == []


def test_cli_clean_repo_and_json_schema(tmp_path):
    out = tmp_path / "lint.json"
    proc = subprocess.run(
        [sys.executable, "scripts/lint.py", "--ci", "--json", str(out)],
        cwd=ROOT, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "repro-lint:" in proc.stdout
    doc = json.loads(out.read_text())
    assert doc["version"] == 1 and doc["count"] == 0
    assert doc["findings"] == []
    assert set(doc["rules"]) == ALL_RULE_IDS


def test_cli_fails_on_injected_violation(tmp_path):
    bad_dir = tmp_path / "sim"
    bad_dir.mkdir()
    (bad_dir / "toy.py").write_text("import random\n")
    proc = subprocess.run(
        [sys.executable, "scripts/lint.py", "--rules", "sim-determinism",
         str(bad_dir)],
        cwd=ROOT, capture_output=True, text=True)
    assert proc.returncode == 1
    assert "sim-determinism" in proc.stdout


def test_cli_list_rules_and_unknown_rule(tmp_path):
    proc = subprocess.run(
        [sys.executable, "scripts/lint.py", "--list-rules"],
        cwd=ROOT, capture_output=True, text=True)
    assert proc.returncode == 0
    for rule in ALL_RULE_IDS:
        assert rule in proc.stdout
    proc = subprocess.run(
        [sys.executable, "scripts/lint.py", "--rules", "bogus"],
        cwd=ROOT, capture_output=True, text=True)
    assert proc.returncode == 2
    assert "unknown rule" in proc.stderr
