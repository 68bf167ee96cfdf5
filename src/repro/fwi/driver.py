"""Self-adaptive FWI driver — the paper end-to-end on the real solver.

An FWISession runs the striped sharded solver over the current stripe
count and emulates the slower burst environment by stretching the
measured time with the configured K for the share of stripes placed
there (per-step synchronization means the step takes the slowest
environment's time — paper step 8).  The ElasticOrchestrator drives
monitoring → prediction → burst exactly as for LM training;
CHECKPOINT/RESHARD are real: fields are pulled to host and re-placed
under the new stripe mesh.

The session holds its fields on the runner's padded grid
(``stripe_geometry``: a ragged survey grid gets zero rows and columns
the kernel can tile); everything that leaves it — ``p`` and ``p_prev``,
the ``checkpoint()`` dict — is on the logical grid, so a RESHARD onto
another stripe count pads afresh for its own.

Measurement is AMORTIZED over a scan block: the session dispatches one
jitted ``make_sharded_scan_runner`` call covering ``scan_block``
timesteps (temporally blocked at ``exchange_interval`` steps per halo
exchange) and reports wall/steps for each logical step inside the block.
Single-step dispatch timings on the seed were dominated by Python/jit
dispatch, not solver time — exactly the overhead the scan-fused engine
removes.  Model arrays and compiled runners are memoized (solver.py /
domain.py lru_caches), so a RESHARD rebuild re-traces nothing that was
already compiled for an equal mesh.
"""
from __future__ import annotations

import dataclasses
import itertools
import signal
import time

import jax
import numpy as np

from repro.checkpoint.manager import (
    CheckpointManager,
    install_preemption_hook,
)
from repro.core.orchestrator import Resources, Session, elastic_chips
from repro.core.spans import span
from repro.fwi.domain import (
    crop,
    effective_block,
    make_sharded_scan_runner,
    stripe_mesh,
)
from repro.fwi.solver import FWIConfig, ShotState
from repro.kernels.stencil.ops import (
    autotune_bz_k,
    pick_bz_block,
    pick_k,
    resolve_use_pallas,
)


@dataclasses.dataclass
class TimeModel:
    """How a step's wall time is derived (DESIGN.md §10).

    measure=True: real wall clock of the sharded solver on this host,
    scaled by the *modeled* parallel speedup (CPU has one core; stripes
    over host devices don't speed up wall time) and stretched by the
    burst environment's K on its work share.
    """

    chip_seconds_per_step: float | None = None  # None -> measure
    congestion: dict[int, float] = dataclasses.field(default_factory=dict)
    congestion_until: int = 10 ** 9
    congestion_from: int = 0
    congestion_factor: float = 1.0
    jitter: float = 0.01
    #: platform-model rate-law exponent (t ∝ 1/chips**alpha), matching
    #: SimWorkload.scaling_alpha so the sim-vs-real harness can run the
    #: same scenario through both worlds (DESIGN.md §14)
    scaling_alpha: float = 1.0


#: one id per FWISession, shared by every span the session records
_session_ids = itertools.count(1)


class FWISession(Session):
    def __init__(
        self,
        cfg: FWIConfig,
        res: Resources,
        start_step: int,
        restored,
        *,
        time_model: TimeModel,
        rng: np.random.Generator,
        n_stripes: int | None = None,
        exchange_interval: int | None = 4,
        scan_block: int = 8,
        use_pallas: bool | None = None,
        autotune: bool = False,
    ):
        self.cfg = cfg
        self.res = res
        self.tm = time_model
        self.rng = rng
        n = n_stripes or min(len(jax.devices()), max(res.total_chips, 1))
        self.session = next(_session_ids)
        with span("fwi.remesh", session=self.session, stripes=n) as remesh:
            self.mesh = stripe_mesh(n)
            use_pallas = resolve_use_pallas(use_pallas)
            bz = None
            if autotune and use_pallas:
                # joint (strip height, block length) tuned at the
                # PER-STRIPE width the engine actually runs (not the
                # global NX); memoized per (shape, backend) so a RESHARD
                # rebuild does not re-time.  If the stripe clamp shrinks
                # the tuned k, re-derive bz for the clamped k instead of
                # keeping the strip that won jointly with the larger one.
                bz, exchange_interval = autotune_bz_k(cfg.nz,
                                                      -(-cfg.nx // n))
                keff = effective_block(cfg, n, exchange_interval)
                if keff != exchange_interval:
                    exchange_interval = keff
                    bz = pick_bz_block(cfg.nz, keff)
            elif exchange_interval is None:
                exchange_interval = pick_k(cfg.nz)
            self.runner, place, self.k = make_sharded_scan_runner(
                cfg, self.mesh, k=exchange_interval, use_pallas=use_pallas,
                bz=bz,
            )
            # the runner's grid, its interior kernel's tiling and the
            # halo schedule it resolved for this stripe count
            g = place.geometry
            remesh.attrs.update(rows=g.rows, lanes=g.lanes, stream=g.stream,
                                shot_tile=g.shot_tile, bz=g.bz, win=g.win,
                                schedule=place.schedule)
        # timesteps per measured dispatch (multiple of the exchange
        # interval so every block is fully temporally blocked)
        self.block = max(scan_block // self.k, 1) * self.k
        with span("fwi.place", session=self.session,
                  devices=[d.id for d in self.mesh.devices.flat]) as sp:
            if restored is not None:
                fields = (restored["p"], restored["p_prev"])
                self.t = int(restored["t"])
            else:
                st = ShotState.init(cfg)
                fields, self.t = (st.p, st.p_prev), st.t
            sp.attrs["bytes"] = sum(f.nbytes for f in fields)
            #: (p, p_prev) on the runner's padded grid
            self.carry = place(fields)
            sp.attrs["padded_bytes"] = sum(f.nbytes for f in self.carry)
        # logical steps already covered by the last dispatched block —
        # carried through checkpoints so a mid-block RESHARD resumes the
        # remaining steps instead of re-dispatching (physical timesteps
        # then exceed logical steps only by the final block's tail)
        self._pending = int(restored.get("pending", 0)) \
            if restored is not None else 0
        self._amortized = float(restored.get("amortized_s", 0.0)) \
            if restored is not None else 0.0
        # fleet signature of the Resources the amortized step time was
        # measured under; a RESHARD onto a different fleet must not feed
        # the predictor the OLD fleet's step time, so a mismatch
        # rescales the estimate by the modeled effective-throughput
        # ratio until the next dispatched block re-measures it
        self._n_stripes = n
        self._res_sig = (
            n, tuple((p.chips, round(p.slowdown, 9)) for p in res.pods)
        )
        self._eff = sum(
            p.chips / max(p.slowdown, 1e-9) for p in res.pods
        )
        if restored is not None and self._amortized > 0.0:
            old_sig = restored.get("res_sig")
            old_eff = float(restored.get("amortized_eff", 0.0))
            if (old_sig is not None and old_sig != self._res_sig
                    and old_eff > 0.0 and self._eff > 0.0):
                self._amortized *= old_eff / self._eff

    @property
    def p(self):
        """The wavefield on the logical grid."""
        return crop(self.cfg, self.carry[0])

    @property
    def p_prev(self):
        """The damped previous wavefield on the logical grid."""
        return crop(self.cfg, self.carry[1])

    def _advance_block(self) -> float:
        """Dispatch one scan block; returns amortized wall s/step."""
        blocks = self.block // self.k
        steps = blocks * self.k
        with span("fwi.dispatch", session=self.session,
                  steps=steps) as dispatch:
            p, pp, _ = self.runner(*self.carry, self.t, blocks)
        with span("fwi.wait", session=self.session) as wait:
            jax.block_until_ready(p)
        self.carry = (p, pp)
        self.t += steps
        return (wait.t1 - dispatch.t0) / steps

    def run_step(self, step: int) -> float:
        if self._pending <= 0:
            self._amortized = self._advance_block()
            self._pending = self.block
        self._pending -= 1
        wall = self._amortized
        if self.tm.chip_seconds_per_step is not None:
            # platform-model time: work split over pods, slowest wins
            times = []
            for pod, share in zip(self.res.pods, self.res.shares):
                if share <= 0:
                    continue
                t = (self.tm.chip_seconds_per_step * share
                     / pod.chips ** self.tm.scaling_alpha
                     * pod.slowdown)
                if (pod.name == "cluster"
                        and self.tm.congestion_from <= step
                        < self.tm.congestion_until):
                    t *= self.tm.congestion_factor
                times.append(t)
            dt = max(times)
        else:
            dt = wall
            k_max = max(
                (p.slowdown for p, s in zip(self.res.pods, self.res.shares)
                 if s > 0), default=1.0,
            )
            if k_max > 1.0:
                time.sleep(wall * (k_max - 1.0))
                dt = wall * k_max
        return dt * (1.0 + self.tm.jitter * abs(self.rng.standard_normal()))

    def checkpoint(self, step: int):
        fields = {}
        with span("fwi.checkpoint", session=self.session,
                  stripes=self._n_stripes):
            for name in ("p", "p_prev"):
                x = getattr(self, name)
                with span("fwi.fetch", bytes=x.nbytes):
                    fields[name] = np.asarray(x)
        return {
            **fields,
            "t": self.t,
            "pending": self._pending,
            "amortized_s": self._amortized,
            "res_sig": self._res_sig,
            "amortized_eff": self._eff,
        }


def save_session_snapshot(manager: CheckpointManager, steps_done: int,
                          snap: dict) -> None:
    """Persist an FWISession.checkpoint() dict through the
    CheckpointManager (DESIGN.md §19): wavefields go as array leaves
    (checksummed per leaf), scalars and the resource signature ride in
    the manifest's ``extra``.  Blocks until the write is durable — a
    preemption snapshot that is still in a queue when the process dies
    never happened."""
    arrays = {"p": snap["p"], "p_prev": snap["p_prev"]}
    n, pods = snap["res_sig"]
    extra = {
        "t": int(snap["t"]),
        "pending": int(snap["pending"]),
        "amortized_s": float(snap["amortized_s"]),
        "amortized_eff": float(snap["amortized_eff"]),
        "res_sig": [n, [list(x) for x in pods]],
        "steps_done": int(steps_done),
    }
    manager.save(steps_done, arrays, extra=extra, wait=True)


def load_session_snapshot(manager: CheckpointManager,
                          step: int | None = None) -> tuple[dict, int]:
    """Inverse of save_session_snapshot: returns ``(restored,
    steps_done)`` where ``restored`` feeds FWISession(...) directly.
    JSON round-trips the resource signature as nested lists; it is
    rebuilt as nested *tuples* here because FWISession compares it with
    ``!=`` against a tuple-of-tuples signature (DESIGN.md §19)."""
    state, extra = manager.restore({"p": 0, "p_prev": 0}, step=step)
    n, pods = extra["res_sig"]
    restored = {
        "p": np.asarray(state["p"]),
        "p_prev": np.asarray(state["p_prev"]),
        "t": int(extra["t"]),
        "pending": int(extra["pending"]),
        "amortized_s": float(extra["amortized_s"]),
        "amortized_eff": float(extra["amortized_eff"]),
        "res_sig": (n, tuple(tuple(x) for x in pods)),
    }
    return restored, int(extra["steps_done"])


class PreemptionGuard:
    """SIGTERM → durable snapshot → clean exit, torn-state-free
    (DESIGN.md §19).

    Python signal handlers run *between bytecodes*, so a handler that
    called ``session.checkpoint()`` directly could observe a session
    mid-update (``_advance_block`` assigns ``p``/``p_prev`` and ``t``
    in separate stores).  The guard instead has the driver loop
    ``publish()`` a coherent snapshot at each step boundary — one
    STORE_SUBSCR into a single slot, atomic with respect to signal
    delivery — and the SIGTERM handler persists whatever snapshot was
    last published.  The restart path resumes from it bit-consistently
    via load_session_snapshot.
    """

    def __init__(self, manager: CheckpointManager, *,
                 exit_code: int = 143):
        self.manager = manager
        self.exit_code = exit_code
        self._slot: list = [None]    # (steps_done, checkpoint dict)
        self._prev_handler = None

    def publish(self, session: Session, steps_done: int) -> None:
        """Record the step-boundary snapshot the handler may persist.
        Call from the driver loop after each completed step."""
        self._slot[0] = (steps_done, session.checkpoint(steps_done))

    def install(self) -> "PreemptionGuard":
        self._prev_handler = install_preemption_hook(
            self._save, exit_code=self.exit_code
        )
        return self

    def uninstall(self) -> None:
        if self._prev_handler is not None:
            signal.signal(signal.SIGTERM, self._prev_handler)
            self._prev_handler = None

    def _save(self) -> None:
        snap = self._slot[0]
        if snap is None:
            return
        steps_done, state = snap
        save_session_snapshot(self.manager, steps_done, state)


def elastic_stripes_for(base_stripes: int = 1, grown_stripes: int = 2):
    """``stripes_for`` mapping for the real elastic loop (DESIGN.md
    §14): while an elastic (cloud/burst) pod is attached the domain is
    re-striped across ``grown_stripes`` devices, and a RETIRE collapses
    it back — so every policy-driven GROW/SHRINK exercises the real
    ckpt → remesh → reshard path, not just a share re-split."""

    def stripes(res: Resources) -> int:
        return grown_stripes if elastic_chips(res) > 0 else base_stripes

    return stripes


def fwi_session_factory(cfg: FWIConfig, time_model: TimeModel,
                        *, seed: int = 0, stripes_for=None,
                        exchange_interval: int | None = 4,
                        scan_block: int = 8,
                        use_pallas: bool | None = None,
                        autotune: bool = False):
    rng = np.random.default_rng(seed)

    def factory(res: Resources, start_step: int, restored) -> FWISession:
        n = stripes_for(res) if stripes_for else None
        return FWISession(
            cfg, res, start_step, restored,
            time_model=time_model, rng=rng, n_stripes=n,
            exchange_interval=exchange_interval, scan_block=scan_block,
            use_pallas=use_pallas, autotune=autotune,
        )

    return factory
