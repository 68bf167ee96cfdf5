"""vmem-budget: tie pallas_call VMEM bytes to the capacity formulas.

The stencil engine's tiling decisions (stream vs resident, strip
height, shot tile) all plan against two analytic formulas —
``resident_vmem_bytes`` / ``stream_vmem_bytes`` — that live NEXT TO the
kernels they describe but, before this rule, were only tied to them by
prose (DESIGN.md §15/§17).  This rule closes the loop statically:

* every ``pl.pallas_call`` under ``kernels/`` gets its VMEM footprint
  extracted symbolically — BlockSpec block shapes (a constant index
  map is fetched once, ×1; a moving map is double-buffered by the
  Pallas pipeline, ×2; ``memory_space=ANY`` stays in HBM, ×0) plus
  ``pltpu.VMEM`` scratch shapes (DMA semaphores are free) at 4 B/elem
  (the engine is f32);
* kernels in ``WRAPPER_FORMULAS`` are evaluated at sample points and
  compared against their formula — drift beyond ``REL_TOL`` (the
  formulas deliberately ignore the tiny scalar source blocks) is a
  finding;
* ``should_stream`` must equal ``resident_vmem_bytes(...) > budget``
  at every sample point (the auto-dispatch contract);
* a streamed kernel (any HBM/ANY input) must pin
  ``vmem_limit_bytes`` compiler params somewhere in its wrapper;
* an UNMAPPED pallas_call that uses VMEM scratch or HBM streaming is
  itself a finding — new capacity-relevant kernels must either get a
  formula mapping or a justified suppression.

Everything is evaluated from the AST (``symeval``) — no jax import.
"""
from __future__ import annotations

import ast
import pathlib
from typing import Iterator

from repro.analysis.core import FileContext, Finding
from repro.analysis.symeval import SymEval, SymEvalError

RULE = "vmem-budget"

#: f32 engine — all counted blocks/scratch are 4-byte elements
ELEM_BYTES = 4

#: relative drift tolerance: the formulas round off the (1, k)/(S, 2)
#: scalar source blocks (~tens of bytes against MBs of windows)
REL_TOL = 0.01

#: concrete sample points the symbolic totals are compared at; all
#: satisfy the kernels' own invariants (nz % bz == 0, trapezoid fits)
SAMPLES = (
    {"nz": 512, "nx": 256, "bz": 32, "k": 4, "ns": 3},
    {"nz": 1024, "nx": 128, "bz": 64, "k": 2, "ns": 2},
)

#: extra absolute budgets the should_stream consistency is probed at
#: (the rule also probes resident_bytes ± 10%, which straddles the
#: decision boundary whatever the formula's scale is)
BUDGET_SAMPLES = (1024 * 1024, 16 * 1024 * 1024)

#: wrapper function -> (formula name, sample -> formula kwargs)
WRAPPER_FORMULAS = {
    "wave_block_shots_pallas": ("resident_vmem_bytes", lambda e: {
        "nz": e["nz"], "nx": e["nx"], "k": e["k"], "bz": e["bz"],
        "s": e["ns"]}),
    "wave_block_shots_stream_pallas": ("stream_vmem_bytes", lambda e: {
        "nz": e["nz"], "nx": e["nx"], "bz": e["bz"], "k": e["k"],
        "s": e["ns"]}),
}

FORMULA_NAMES = ("resident_vmem_bytes", "stream_vmem_bytes")


def _attr_name(func: ast.expr) -> str:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return ""


def _is_pallas_call(node: ast.Call) -> bool:
    return _attr_name(node.func) == "pallas_call"


def _kw(call: ast.Call, name: str) -> ast.expr | None:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _local_assigns(fdef: ast.FunctionDef) -> dict[str, ast.expr]:
    out: dict[str, ast.expr] = {}
    for st in fdef.body:
        if (isinstance(st, ast.Assign) and len(st.targets) == 1
                and isinstance(st.targets[0], ast.Name)):
            out.setdefault(st.targets[0].id, st.value)
    return out


def _spec_bytes(spec: ast.expr, locals_: dict[str, ast.expr],
                ev: SymEval) -> int:
    """VMEM bytes one BlockSpec pins: block elems × 4 B × pipeline
    multiplier (constant index map ×1, moving ×2, ANY memory ×0)."""
    if isinstance(spec, ast.Name) and spec.id in locals_:
        spec = locals_[spec.id]
    if not (isinstance(spec, ast.Call)
            and _attr_name(spec.func) == "BlockSpec"):
        raise SymEvalError("spec is not a BlockSpec call")
    if not spec.args:                       # memory_space=ANY: HBM-resident
        return 0
    shape = ev.eval(spec.args[0])
    if not isinstance(shape, tuple):
        raise SymEvalError("BlockSpec shape is not a tuple")
    elems = 1
    for d in shape:
        elems *= int(d)
    mult = 2                                # moving: pipeline double-buffers
    if len(spec.args) > 1 and isinstance(spec.args[1], ast.Lambda):
        body = spec.args[1].body
        if isinstance(body, ast.Tuple) and all(
                isinstance(e, ast.Constant) for e in body.elts):
            mult = 1                        # constant map: fetched once
    return elems * ELEM_BYTES * mult


def _scratch_bytes(node: ast.expr, ev: SymEval) -> int:
    """Bytes of one scratch_shapes entry (semaphores are free)."""
    if not isinstance(node, ast.Call):
        raise SymEvalError("unrecognized scratch entry")
    name = _attr_name(node.func)
    if name == "VMEM":
        shape = ev.eval(node.args[0])
        elems = 1
        for d in shape:
            elems *= int(d)
        return elems * ELEM_BYTES
    if name == "DMA" or name == "SemaphoreType":
        return 0
    raise SymEvalError(f"unrecognized scratch entry {name!r}")


def _spec_list(call: ast.Call, key: str,
               locals_: dict[str, ast.expr]) -> list[ast.expr]:
    node = _kw(call, key)
    if node is None:
        return []
    if isinstance(node, ast.Name) and node.id in locals_:
        node = locals_[node.id]
    if isinstance(node, (ast.List, ast.Tuple)):
        return list(node.elts)
    return [node]                           # single un-listed spec


def _has_any_spec(specs: list[ast.expr],
                  locals_: dict[str, ast.expr]) -> bool:
    for spec in specs:
        if isinstance(spec, ast.Name) and spec.id in locals_:
            spec = locals_[spec.id]
        if (isinstance(spec, ast.Call)
                and _attr_name(spec.func) == "BlockSpec"
                and not spec.args):
            return True
    return False


def _mentions_vmem_limit(fdef: ast.FunctionDef) -> bool:
    for node in ast.walk(fdef):
        if isinstance(node, ast.keyword) and node.arg == "vmem_limit_bytes":
            return True
        if isinstance(node, ast.Constant) and node.value == "vmem_limit_bytes":
            return True
    return False


class VmemBudgetRule:
    """Cross-file pass: formulas from the stencil module, pallas_calls
    from every module under ``kernels/``."""

    name = RULE

    def run(self, ctxs: list[FileContext],
            root: pathlib.Path) -> Iterator[Finding]:
        kernel_ctxs = [c for c in ctxs if "kernels" in c.parts]
        formula_ctx = next(
            (c for c in kernel_ctxs
             if all(f in SymEval(c.tree).functions for f in FORMULA_NAMES)),
            None,
        )
        if formula_ctx is not None:
            yield from self._check_should_stream(formula_ctx)
        for ctx in kernel_ctxs:
            for fdef in [n for n in ctx.tree.body
                         if isinstance(n, ast.FunctionDef)]:
                for node in ast.walk(fdef):
                    if isinstance(node, ast.Call) and _is_pallas_call(node):
                        yield from self._check_site(
                            ctx, fdef, node, formula_ctx)

    # -- per-site ----------------------------------------------------------

    def _check_site(self, ctx: FileContext, fdef: ast.FunctionDef,
                    call: ast.Call,
                    formula_ctx: FileContext | None) -> Iterator[Finding]:
        locals_ = _local_assigns(fdef)
        in_specs = _spec_list(call, "in_specs", locals_)
        out_specs = _spec_list(call, "out_specs", locals_)
        scratch = _spec_list(call, "scratch_shapes", locals_)
        streams = _has_any_spec(in_specs + out_specs, locals_)
        mapped = fdef.name in WRAPPER_FORMULAS

        if not mapped:
            if scratch or streams:
                yield Finding(
                    ctx.rel, call.lineno, call.col_offset, RULE,
                    f"pallas_call in `{fdef.name}` uses VMEM scratch or "
                    f"HBM streaming but has no capacity-formula mapping "
                    f"(WRAPPER_FORMULAS) — add one or suppress with a "
                    f"justification",
                )
            return

        if streams and not _mentions_vmem_limit(fdef):
            yield Finding(
                ctx.rel, call.lineno, call.col_offset, RULE,
                f"streamed pallas_call in `{fdef.name}` (ANY-memory "
                f"inputs) does not pin vmem_limit_bytes compiler params",
            )

        if formula_ctx is None:
            yield Finding(
                ctx.rel, call.lineno, call.col_offset, RULE,
                f"`{fdef.name}` is formula-mapped but no module in the "
                f"file set defines {'/'.join(FORMULA_NAMES)}",
            )
            return

        formula, kwargs_of = WRAPPER_FORMULAS[fdef.name]
        for sample in SAMPLES:
            try:
                ev = SymEval(ctx.tree, env=dict(sample), scope=fdef)
                kernel_bytes = sum(
                    _spec_bytes(s, locals_, ev)
                    for s in in_specs + out_specs
                ) + sum(_scratch_bytes(s, ev) for s in scratch)
                fev = SymEval(formula_ctx.tree)
                formula_bytes = fev.call(formula, kwargs=kwargs_of(sample))
            except SymEvalError as e:
                yield Finding(
                    ctx.rel, call.lineno, call.col_offset, RULE,
                    f"could not evaluate `{fdef.name}` VMEM bytes vs "
                    f"{formula} at {sample}: {e}",
                )
                return
            drift = abs(kernel_bytes - formula_bytes)
            if drift > REL_TOL * formula_bytes:
                yield Finding(
                    ctx.rel, call.lineno, call.col_offset, RULE,
                    f"`{fdef.name}` VMEM bytes drift from {formula} at "
                    f"{sample}: kernel={kernel_bytes} formula="
                    f"{formula_bytes} ({drift} B, tol "
                    f"{REL_TOL:.0%})",
                )
                return

    # -- dispatch-rule consistency -----------------------------------------

    def _check_should_stream(self,
                             ctx: FileContext) -> Iterator[Finding]:
        ev = SymEval(ctx.tree)
        if "should_stream" not in ev.functions:
            return
        line = ev.functions["should_stream"].lineno
        for sample in SAMPLES:
            try:
                resident = ev.call("resident_vmem_bytes", kwargs={
                    "nz": sample["nz"], "nx": sample["nx"],
                    "k": sample["k"], "s": sample["ns"],
                })
            except SymEvalError as e:
                yield Finding(
                    ctx.rel, line, 0, RULE,
                    f"could not evaluate resident_vmem_bytes at "
                    f"{sample}: {e}",
                )
                return
            budgets = (int(resident * 0.9), int(resident * 1.1),
                       *BUDGET_SAMPLES)
            for budget in budgets:
                try:
                    got = ev.call("should_stream", kwargs={
                        "nz": sample["nz"], "nx": sample["nx"],
                        "k": sample["k"], "vmem_budget": budget,
                        "s": sample["ns"],
                    })
                except SymEvalError as e:
                    yield Finding(
                        ctx.rel, line, 0, RULE,
                        f"could not evaluate should_stream consistency "
                        f"at {sample}, budget={budget}: {e}",
                    )
                    return
                if bool(got) != (resident > budget):
                    yield Finding(
                        ctx.rel, line, 0, RULE,
                        f"should_stream({sample}, budget={budget}) = "
                        f"{got} but resident_vmem_bytes = {resident} "
                        f"(> budget is {resident > budget}) — dispatch "
                        f"rule drifted from the capacity model",
                    )
                    return
