"""Run one benchmark cell: set up, measure a window, check the result.

A cell is a configuration (``configs/<name>.json``: the ``FWIConfig``
fields and the engine's settings) under a traffic mix
(``traffic/<name>.json``: the phases of stripes the job runs through,
the orchestrator's settings and the initial wavefields).  The harness
drives the program's own entry, ``ElasticOrchestrator.run`` over
sessions from ``repro.fwi.driver.fwi_session_factory``, and hands each
job's first session the seeded wavefields through the factory's
``restored`` argument, the entry a restart uses.

Set-up makes the wavefields, then runs the mix's cycle once with every
phase one block long, so that every runner the window uses is compiled
and every transition has run once.  The window runs one job from the
seeded fields, longer than any window, and closes it at the first block
boundary at or after ``seconds``, other than the first block on a new
mesh.  Afterwards the final wavefields of a sample of the job's shots,
drawn from the seed, are compared with the plain reference
(``reference.py``) over the same timesteps: that comparison decides
``correct``.

The spans the harness opens around the program's calls are kept on the
host clock always, and written into the profiler's trace as
``bench.<what>`` annotations when the run is traced.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, state, work
from bench import trace as tr

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

#: the burst planner of the deployment the orchestrator plans for: a
#: 64-chip cluster pod, legal slices of 16-128 chips, a cloud 1.4x
#: slower (the settings of the repository's real-elastic scenario)
PLANNER = dict(work_chip_s=64.0, cloud_slowdown=1.4,
               legal=(16, 32, 64, 128), cluster_chips=64)

#: the length of the window's job: more steps than any window reaches,
#: so that the window's close ends it
JOB_STEPS = 10 ** 7


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class WindowClosed(Exception):
    """Raised out of the orchestrator's loop when the window is over."""


# ------------------------------------------------------------- files


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_file(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def mix(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def check_limits(workload: str) -> dict:
    """{number name: its limit} for the cell's comparison."""
    checks = load_json(BENCH / "checks" / f"{workload}.json")
    return {k: float(v["limit"]) for k, v in checks.items()}


def metric_reader(name: str):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, workload: str, traced: bool) -> list[dict]:
    """The metrics a run of ``workload`` reports: the end-to-end ones,
    or the per-layer ones when traced; a metric with a ``workloads``
    list only in the cells it names."""
    kind = "per_layer" if traced else "end_to_end"
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def chips(n: int) -> list:
    """The TPU devices, or NoChip when there are none or too few."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devs[0].platform!r}, not a TPU")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} TPU chips, JAX found {len(devs)}")
    return devs


# ------------------------------------------------------------ records


@dataclasses.dataclass
class SessionRecord:
    """One session of the window: its mesh and the work it did."""
    stripes: int
    devices: list[int]
    created: float           # host clock at the factory call
    t_begin: int             # physical timestep when placed
    t_end: int               # physical timestep after its last block
    ended: float | None = None


@dataclasses.dataclass
class Transition:
    """A change of mesh: checkpoint, factory, first block on it."""
    stripes_from: int
    stripes_to: int
    ckpt: tuple[float, float]
    factory: tuple[float, float] | None = None
    first_block_end: float | None = None


class Recorder:
    """Spans on the host clock, also written as profiler annotations
    when ``annotate`` is set."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.spans: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        ann = (jax.profiler.TraceAnnotation(tr.SPAN_PREFIX + name)
               if self.annotate else contextlib.nullcontext())
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))


class Job:
    """The factory and session wrappers of one orchestrated job."""

    def __init__(self, base_factory, initial: list, rec: Recorder, *,
                 deadline: float | None):
        self.base = base_factory
        #: a one-item list holding the seeded state; the first session
        #: takes it out, so that nothing else keeps those fields alive
        self.initial = initial
        self.rec = rec
        self.deadline = deadline
        self.sessions: list[SessionRecord] = []
        self.transitions: list[Transition] = []
        self.current: _Timed | None = None
        self.closed = False          # the window closed inside this job

    def factory(self, res, start_step, restored):
        if restored is None:
            restored = self.initial.pop()
        t0 = time.perf_counter()
        with self.rec.span("factory"):
            inner = self.base(res, start_step, restored)
        t1 = time.perf_counter()
        record = SessionRecord(
            stripes=inner.mesh.devices.size,
            devices=[d.id for d in inner.mesh.devices.flat],
            created=t0, t_begin=inner.t, t_end=inner.t)
        if self.sessions:
            self.sessions[-1].ended = t0
        self.sessions.append(record)
        pending = self.transitions[-1] if self.transitions else None
        if pending is not None and pending.factory is None:
            pending.factory = (t0, t1)
            pending.stripes_to = record.stripes
        if self.current is not None:
            self.current.inner = None        # free the old mesh's fields
        self.current = _Timed(self, inner, start_step, record)
        return self.current


class _Timed:
    """A session whose blocks are spanned and whose window can close."""

    def __init__(self, job: Job, inner, start_step: int,
                 record: SessionRecord):
        self.job = job
        self.inner = inner
        self.start_step = start_step
        self.record = record
        self.first = True

    def run_step(self, step: int) -> float:
        if (step - self.start_step) % self.inner.block:
            return self.inner.run_step(step)
        job = self.job
        if (not self.first and job.deadline is not None
                and time.perf_counter() >= job.deadline):
            raise WindowClosed
        with job.rec.span("dispatch"):
            dt = self.inner.run_step(step)
        self.record.t_end = self.inner.t
        if self.first and job.transitions \
                and job.transitions[-1].first_block_end is None:
            job.transitions[-1].first_block_end = time.perf_counter()
        self.first = False
        return dt

    def checkpoint(self, step: int):
        t0 = time.perf_counter()
        with self.job.rec.span("checkpoint"):
            snap = self.inner.checkpoint(step)
        self.job.transitions.append(Transition(
            stripes_from=self.record.stripes, stripes_to=0,
            ckpt=(t0, time.perf_counter())))
        return snap


class PhaseSchedule:
    """Autoscaler that walks the mix's phases by step count: each phase
    holds a number of stripes for a number of steps, and the cycle
    repeats.  Moving to more stripes than now is a GROW, back to the
    first phase's a RETIRE, to fewer otherwise a SHRINK; the elastic
    pod holds the stripes beyond the first phase's."""

    name = "phase-schedule"

    def __init__(self, phases: list[dict]):
        self.phases = phases
        self.cycle = sum(p["steps"] for p in phases)
        self.base = phases[0]["stripes"]
        self.stripes = self.base

    def phase_at(self, step: int) -> dict:
        pos = step % self.cycle
        for p in self.phases:
            if pos < p["steps"]:
                return p
            pos -= p["steps"]
        raise AssertionError("unreachable")

    def decide(self, ctx):
        from repro.core.orchestrator import HOLD, ScaleAction

        want = self.phase_at(ctx.step)["stripes"]
        if want == self.stripes:
            return HOLD
        kind = ("retire" if want == self.base
                else "grow" if want > self.stripes else "shrink")
        self.stripes = want
        return ScaleAction(kind, chips=want - self.base, slowdown=1.0,
                           reason=f"phase of {want} stripes")

    def stripes_for(self, res) -> int:
        return self.stripes


def run_job(cfg, conf: dict, mix_: dict, phases: list[dict],
            initial: list, rec: Recorder, *, deadline: float | None,
            steps_total: int) -> Job:
    """One job through ``ElasticOrchestrator.run`` from the state in the
    one-item list ``initial``; returns its records once it finished or
    its window closed."""
    from repro.core import (
        BurstPlanner, DeadlinePredictor, ElasticOrchestrator,
        LogCapacityModel, OverheadModel, PodSpec, Resources,
    )
    from repro.fwi.driver import TimeModel, fwi_session_factory

    w, k_cloud = PLANNER["work_chip_s"], PLANNER["cloud_slowdown"]
    cs = sorted(set(PLANNER["legal"]) | {PLANNER["cluster_chips"]})
    planner = BurstPlanner(
        cluster_model=LogCapacityModel.fit(cs, [w / c for c in cs]),
        cloud_model=LogCapacityModel.fit(cs, [k_cloud * w / c for c in cs]),
        chips_cluster=PLANNER["cluster_chips"],
        legal_slices=list(PLANNER["legal"]),
        overheads=OverheadModel(ckpt_s=5.0, provision_s=10.0,
                                restart_s=5.0),
        price_per_chip_hour=3.0, cost_weight=0.5,
    )
    block = conf["scan_block"]
    orch = ElasticOrchestrator(
        planner=planner, predictor=DeadlinePredictor(mix_["deadline_s"]),
        check_every=block, ckpt_every=mix_["ckpt_every"],
    )
    schedule = PhaseSchedule(phases) if len(phases) > 1 else None
    base_stripes = phases[0]["stripes"]
    tm = mix_["time_model"]
    base = fwi_session_factory(
        cfg, TimeModel(chip_seconds_per_step=tm["chip_seconds_per_step"],
                       jitter=tm["jitter"]),
        stripes_for=(schedule.stripes_for if schedule
                     else lambda res: base_stripes),
        exchange_interval=conf["exchange_interval"], scan_block=block,
    )
    job = Job(base, initial, rec, deadline=deadline)
    try:
        orch.run(
            session_factory=job.factory,
            initial=Resources(pods=[PodSpec(chips=base_stripes,
                                            name="cluster")],
                              shares=[1.0]),
            steps_total=steps_total, autoscaler=schedule,
        )
    except WindowClosed:
        job.closed = True
    job.sessions[-1].ended = time.perf_counter()
    return job


# ---------------------------------------------------------------- run


@dataclasses.dataclass
class Run:
    """What a metric reader gets: the cell's settings, the window's
    records, and the trace when the run was traced."""
    fwi: dict
    conf: dict
    mix: dict
    device_kind: str
    setup_s: float
    window: tuple[float, float]            # host clock, seconds
    sessions: list[SessionRecord]
    transitions: list[Transition]
    spans: list[tuple[str, float, float]]
    trace: tr.Trace | None = None
    trace_window: tuple[float, float] | None = None   # trace clock, ns

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def k(self) -> int:
        return self.conf["exchange_interval"]

    def points(self) -> int:
        """Grid-point updates in the window, over every stripe."""
        f = self.fwi
        return sum(s.t_end - s.t_begin for s in self.sessions) \
            * f["n_shots"] * f["nz"] * f["nx"]

    def least_bytes(self) -> float:
        """The least HBM bytes of the window's timesteps."""
        f = self.fwi
        steps = sum(s.t_end - s.t_begin for s in self.sessions)
        return steps / self.k * work.block_bytes(f["nz"], f["nx"],
                                                 f["n_shots"])

    def completed_transitions(self) -> list[Transition]:
        return [t for t in self.transitions
                if t.first_block_end is not None]

    def to_trace(self, t: float) -> float:
        """A host-clock time on the trace's clock (ns), aligned by the
        window span."""
        return self.trace_window[0] + (t - self.window[0]) * 1e9

    def held(self) -> dict[int, list[tuple[float, float]]]:
        """Per device, the trace-clock intervals in which a session of
        the window held it."""
        out: dict[int, list] = {}
        for s in self.sessions:
            lo = self.to_trace(s.created)
            hi = self.to_trace(s.ended)
            for d in s.devices:
                out.setdefault(d, []).append((lo, hi))
        return {d: tr.union(v) for d, v in out.items()}


def sample_shots(n_shots: int, n: int, seed: int) -> np.ndarray:
    """One shot from each of ``n`` equal groups, drawn from the seed."""
    rng = np.random.default_rng([seed, 0x5eed])
    group = n_shots // n
    return np.arange(n) * group + rng.integers(0, group, size=n)


def gaps(final: tuple, ref: tuple) -> list[float]:
    """max |program - reference| / max |reference| for each shot and
    field (p, then p_prev)."""
    out = []
    for got, want in zip(final, ref):
        got = jax.device_put(got, want.sharding)
        num = jnp.max(jnp.abs(got - want), axis=(1, 2))
        den = jnp.max(jnp.abs(want), axis=(1, 2))
        out.extend(np.asarray(num / den).tolist())
    return out


def compare(fwi: dict, final: tuple, initial: tuple, shots, t0: int,
            steps: int, devices) -> list[float]:
    """``gaps`` of ``final`` against the reference run from
    ``initial`` over ``steps`` timesteps."""
    ref = reference.propagate(fwi, *initial, shots, t0, steps,
                              devices=devices)
    return gaps(final, ref)


class CompileCounter:
    """Counts JAX's compilations (tracing, lowering, XLA) as they end."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.count += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self)


def run_cell(fwi: dict, conf: dict, mix_: dict, *, seed: int,
             seconds: float, traced: bool, t_start: float,
             devices: list, limits: dict, keep_trace: Path | None = None):
    """Set up, measure the window, compare; returns (Run, parts) where
    the parts are ``correct``, ``attempted``, ``failed``, ``checks``,
    ``memory_peak_bytes`` and what was compared."""
    from repro.fwi.solver import FWIConfig

    cfg = FWIConfig(**fwi)
    t0 = int(mix_["t0"])
    phases = mix_["phases"]
    block = conf["scan_block"]
    n_check = conf["check_shots"]

    def seeded() -> dict:
        p, pp = state.initial_fields(
            seed, shots=cfg.n_shots, nz=cfg.nz, nx=cfg.nx,
            init=mix_["init"], dx=cfg.dx, dt=cfg.dt)
        jax.block_until_ready(pp)
        return {"p": p, "p_prev": pp, "t": t0}

    # set-up: the cycle once, every phase one block long
    marks = {"start": time.perf_counter()}
    warm = [dict(p, steps=block) for p in phases]
    fields = seeded()
    marks["fields"] = time.perf_counter()
    run_job(cfg, conf, mix_, warm, [fields], Recorder(False),
            deadline=None, steps_total=block * (len(warm) + 1))
    marks["warm"] = time.perf_counter()
    initial = [seeded()]
    fields = None
    gc.collect()

    compiles = CompileCounter()
    rec = Recorder(traced)
    trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) \
        if traced else None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    w0 = time.perf_counter()
    with rec.span("window"):
        job = run_job(cfg, conf, mix_, phases, initial, rec,
                      deadline=w0 + seconds, steps_total=JOB_STEPS)
    w1 = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    if not job.closed:
        raise RuntimeError(f"the job ended before {seconds} s")
    final = job.current.inner
    job.current.inner = None
    steps = final.t - t0
    in_window = compiles.count
    compiles.close()
    # the CPU backend, which tests drive, reports no memory statistics
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)

    run = Run(fwi=fwi, conf=conf, mix=mix_,
              device_kind=devices[0].device_kind, setup_s=w0 - t_start,
              window=(w0, w1), sessions=job.sessions,
              transitions=job.transitions, spans=rec.spans)
    if traced:
        xplane = next(trace_dir.rglob("*.xplane.pb"))
        if keep_trace is not None:
            keep_trace.mkdir(parents=True, exist_ok=True)
            shutil.copy(xplane, keep_trace / xplane.name)
        run.trace = tr.load(xplane)
        shutil.rmtree(trace_dir, ignore_errors=True)
        (lo, hi), = run.trace.span(tr.SPAN_PREFIX + "window")
        run.trace_window = (lo, hi)

    # the check, once the program's state is freed: a sample of the
    # job's shots against the reference from the seeded fields
    idx = jnp.asarray(sample_shots(cfg.n_shots, n_check, seed))
    got = tuple(jnp.take(a, idx, axis=0) for a in (final.p, final.p_prev))
    job = final = None
    gc.collect()
    limit = limits["wavefield_gap"]
    ref_devices = devices if n_check % len(devices) == 0 else devices[:1]
    c0 = time.perf_counter()
    start = seeded()
    start = tuple(jnp.take(start[k], idx, axis=0) for k in ("p", "p_prev"))
    g = compare(fwi, got, start, idx, t0, steps, ref_devices)
    # a shot fails where either field's gap is over (or NaN)
    failed = sum(not (max(g[i], g[i + n_check]) <= limit)
                 for i in range(n_check))
    gap = float(np.max(g))              # NaN, if any, stays NaN
    parts = dict(correct=bool(gap <= limit), attempted=n_check,
                 failed=int(failed), memory_peak_bytes=peak,
                 checks={"wavefield_gap": {"value": gap, "limit": limit}},
                 shots=np.asarray(idx).tolist(), steps=steps,
                 check_s=time.perf_counter() - c0,
                 setup={"to_run_cell_s": marks["start"] - t_start,
                        "fields_s": marks["fields"] - marks["start"],
                        "warm_cycle_s": marks["warm"] - marks["fields"],
                        "rest_s": w0 - marks["warm"]},
                 compiles_in_window=in_window)
    return run, parts


def result_line(bench: dict, workload: str, *, seed: int, seconds: float,
                traced: bool, t_start: float, require_chip: bool = True,
                keep_trace: Path | None = None) -> dict:
    """The benchmark's result for one run of ``workload``."""
    c = cell(bench, workload)
    devices = chips(c["chips"]) if require_chip else jax.devices()
    from bench.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    conf = config_file(bench, c["config"])
    mix_ = mix(c["traffic"])
    run, parts = run_cell(
        conf["fwi"], conf, mix_, seed=seed, seconds=seconds, traced=traced,
        t_start=t_start, devices=devices[:c["chips"]],
        limits=check_limits(workload), keep_trace=keep_trace)
    return assemble(bench, workload, run, parts, devices, traced)


def assemble(bench: dict, workload: str, run: Run, parts: dict,
             devices: list, traced: bool) -> dict:
    metrics = {}
    for m in cell_metrics(bench, workload, traced):
        value = metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": parts["memory_peak_bytes"]}
    out = {"correct": parts["correct"], "attempted": parts["attempted"],
           "failed": parts["failed"], "metrics": metrics, "device": device}
    f = run.fwi
    out["work"] = {
        "timesteps": sum(s.t_end - s.t_begin for s in run.sessions),
        "grid_point_updates": run.points(),
        "window_s": run.window_s,
        "transitions": len(run.completed_transitions()),
        "ops_per_byte": work.ops_per_byte(f["n_shots"], run.k),
        "checked_shots": parts["shots"], "check_s": parts["check_s"],
        "setup": parts["setup"],
        "compiles_in_window": parts["compiles_in_window"],
    }
    if traced:
        lo, hi = run.trace_window
        used = sorted({d for s in run.sessions for d in s.devices}
                      | {d.id for d in devices})
        busy = [tr.total(tr.intersect(run.trace.busy(d), [(lo, hi)]))
                for d in used]
        device["busy_s"] = sum(busy) / len(busy) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = {
            "device_ops": tr.top_ops(run.trace, (lo, hi), used),
            "idle_gaps": tr.idle_by_span(run.trace, run.held()),
        }
    out["checks"] = parts["checks"]
    return out


def print_result(out: dict) -> None:
    """Each compared number beside its limit as the last lines of
    standard error, then the result as the last line of standard
    output."""
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
