"""Elastic LM training with a REAL mid-run burst (paper Fig. 1 end-to-end).

Launches with 8 placeholder host devices (launcher-style script — tests
and benches still see 1 device).  A granite-family model trains on a
"cluster" of 4 chips; at step 40 an injected congestion (time stretch)
slows it down; the monitor detects the regime change, the planner solves
eqs. 1-3 for the burst size, and the orchestrator checkpoints, rebuilds
the mesh as (pod=2, data, model), reshards the state onto 8 chips and
resumes — the same training run, now spanning both "environments".

    PYTHONPATH=src python examples/elastic_burst_demo.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import RunConfig, get_config, smoke_config  # noqa: E402
from repro.configs.shapes import ShapeConfig  # noqa: E402
from repro.core import (  # noqa: E402
    BurstPlanner,
    DeadlinePredictor,
    ElasticOrchestrator,
    LogCapacityModel,
    OverheadModel,
    PodSpec,
    Resources,
)
from repro.data.pipeline import SyntheticLMPipeline  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.optim import constant, make_optimizer  # noqa: E402
from repro.runtime.train_step import (  # noqa: E402
    build_train_step,
    state_schema,
    state_shardings,
)
from repro.sharding.rules import (  # noqa: E402
    abstract_params,
    init_params,
    make_rules,
)

CFG = smoke_config(get_config("granite-8b"))
RUN = RunConfig(loss_chunk=32)
SHAPE = ShapeConfig("demo", "train", 64, 8)
OPT = make_optimizer("adamw", constant(1e-3))
SCH = state_schema(CFG, RUN, OPT)
PIPE = SyntheticLMPipeline(CFG, SHAPE)

STEPS = 120
CONGESTION_FROM = 40
CONGESTION = 2.5          # injected slowdown of the "cluster"

# What is REAL here: the training math, the mid-run checkpoint, the mesh
# rebuild (2,2) -> (2,2,2) and the reshard-on-restore.  What is MODELED:
# step wall time (this host has one core, so 8 placeholder devices cannot
# speed anything up) — reported step times follow the platform model
# W·share/(chips/slowdown), exactly like the FWI driver (DESIGN.md §10).


class LMSession:
    """Real JAX training session over the current Resources."""

    work_chip_s: float = 4.0  # chip-seconds per step (modeled platform)

    def __init__(self, res: Resources, start_step: int, restored):
        self.res = res
        n_pods = len(res.pods)
        if n_pods == 1:
            mesh = make_mesh((2, 2), ("data", "model"))
        else:
            mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        self.rules = make_rules(mesh, "train")
        self.shardings = state_shardings(SCH, self.rules, RUN)
        self.step_fn = jax.jit(build_train_step(CFG, RUN, OPT, self.rules))
        if restored is None:
            params = jax.device_put(
                init_params(SCH["params"], jax.random.key(0)),
                self.shardings["params"],
            )
            self.state = {
                "params": params, "opt": OPT.init(params),
                "step": jnp.zeros((), jnp.int32),
            }
        else:
            # reshard-on-restore: host snapshot -> new mesh layout
            self.state = jax.tree.map(
                lambda a, s: jax.device_put(jnp.asarray(a), s),
                restored, self.shardings,
            )
        self.mesh_desc = dict(mesh.shape)

    def run_step(self, step: int) -> float:
        batch = PIPE.batch_at(step)
        self.state, metrics = self.step_fn(self.state, batch)
        self.last_loss = float(metrics["loss"])  # blocks (real compute)
        # platform-modeled step time (per-step sync: slowest pod wins)
        times = []
        for pod, share in zip(self.res.pods, self.res.shares):
            if share <= 0:
                continue
            t = self.work_chip_s * share / pod.chips * pod.slowdown
            if pod.name == "cluster" and step >= CONGESTION_FROM:
                t *= CONGESTION
            times.append(t)
        return max(times)

    def checkpoint(self, step: int):
        return jax.tree.map(lambda x: np.asarray(x), self.state)


def main():
    print(f"devices: {len(jax.devices())}")
    work = LMSession.work_chip_s
    t_step = work / 4
    chips = [1, 2, 4, 8]
    cluster = LogCapacityModel.fit(chips, [work / c for c in chips])
    cloud = LogCapacityModel.fit(chips, [1.25 * work / c for c in chips])
    deadline = t_step * STEPS * 1.6
    print(f"modeled step {t_step * 1000:.0f} ms on 4 chips -> deadline "
          f"{deadline:.1f}s for {STEPS} steps")

    planner = BurstPlanner(
        cluster_model=cluster, cloud_model=cloud, chips_cluster=4,
        legal_slices=[1, 2, 4],
        overheads=OverheadModel(ckpt_s=t_step, provision_s=4 * t_step,
                                restart_s=4 * t_step),
    )
    orch = ElasticOrchestrator(
        planner=planner, predictor=DeadlinePredictor(deadline),
        check_every=6, ckpt_every=30, max_bursts=1,
    )

    def factory(res, start_step, restored):
        sess = LMSession(res, start_step, restored)
        print(f"  [session] pods={[p.chips for p in res.pods]} "
              f"mesh={sess.mesh_desc} from step {start_step}")
        return sess

    rec = orch.run(
        session_factory=factory,
        initial=Resources(pods=[PodSpec(4, name="cluster")], shares=[1.0]),
        steps_total=STEPS,
    )
    print(f"elapsed {rec.elapsed_s:.1f}s vs deadline {deadline:.1f}s "
          f"-> met={rec.met_deadline}")
    for e in rec.events:
        if e.kind != "ckpt":
            print(f"  step {e.step}: {e.kind} {e.detail}")
    assert rec.met_deadline, "demo expects the burst to rescue the deadline"
    print("elastic_burst_demo OK")


if __name__ == "__main__":
    main()
