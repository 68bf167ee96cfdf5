"""Checkpoint manager: roundtrip, atomicity, GC, resume, reshard hook."""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.checkpoint.manager import CheckpointManager


def _abstract(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree
    )


def test_roundtrip_and_extra(tmp_path):
    state = {
        "params": {"w": jnp.arange(12.0).reshape(3, 4),
                   "b": jnp.ones((4,), jnp.bfloat16)},
        "opt": {"m": jnp.zeros((3, 4)), "count": jnp.asarray(3)},
    }
    m = CheckpointManager(tmp_path, async_save=False)
    m.save(5, state, extra={"data_step": 5})
    restored, extra = m.restore(_abstract(state))
    assert extra == {"data_step": 5}
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@given(
    shapes=st.lists(
        st.tuples(st.integers(1, 5), st.integers(1, 5)),
        min_size=1, max_size=4,
    ),
    step=st.integers(0, 10 ** 6),
)
@settings(max_examples=15, deadline=None)
def test_roundtrip_property(tmp_path_factory, shapes, step):
    tmp = tmp_path_factory.mktemp("ckpt")
    rng = np.random.default_rng(0)
    state = {
        f"t{i}": jnp.asarray(rng.standard_normal(s).astype(np.float32))
        for i, s in enumerate(shapes)
    }
    m = CheckpointManager(tmp, async_save=False)
    m.save(step, state)
    restored, _ = m.restore(_abstract(state), step=step)
    for k in state:
        np.testing.assert_array_equal(state[k], restored[k])


def test_async_save_and_gc(tmp_path):
    m = CheckpointManager(tmp_path, async_save=True, keep=2)
    state = {"x": jnp.ones((8, 8))}
    for s in (10, 20, 30, 40):
        m.save(s, state)
    m.wait()
    assert m.all_steps() == [30, 40]  # keep=2


def test_atomicity_no_partial_dirs(tmp_path):
    m = CheckpointManager(tmp_path, async_save=False)
    m.save(1, {"x": jnp.ones((512, 512))})
    for p in tmp_path.glob("*.tmp"):
        pytest.fail(f"left-over tmp dir {p}")
    manifest = json.loads(
        (tmp_path / "step_00000001" / "manifest.json").read_text()
    )
    assert manifest["step"] == 1 and "x" in manifest["leaves"]


def test_restore_latest_and_missing_leaf_error(tmp_path):
    m = CheckpointManager(tmp_path, async_save=False)
    m.save(7, {"a": jnp.zeros((2,))})
    with pytest.raises(KeyError):
        m.restore(_abstract({"a": jnp.zeros((2,)),
                             "missing": jnp.zeros((3,))}))


def test_restore_with_shardings_places_on_device(tmp_path):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    sh = NamedSharding(mesh, P())
    state = {"w": jnp.arange(16.0).reshape(4, 4)}
    m = CheckpointManager(tmp_path, async_save=False)
    m.save(1, state)
    restored, _ = m.restore(_abstract(state), shardings={"w": sh})
    assert restored["w"].sharding == sh


def test_train_resume_bit_exact(tmp_path):
    """Training N steps straight == training k, checkpoint, resume N-k."""
    from repro.configs import RunConfig, get_config, smoke_config
    from repro.data.pipeline import SyntheticLMPipeline
    from repro.configs.shapes import ShapeConfig
    from repro.optim import constant, make_optimizer
    from repro.runtime.train_step import build_train_step, state_schema
    from repro.sharding.rules import abstract_params, init_params

    cfg = smoke_config(get_config("yi-6b"))
    run = RunConfig(loss_chunk=32)
    shape = ShapeConfig("t", "train", 32, 2)
    opt = make_optimizer("adamw", constant(1e-3))
    sch = state_schema(cfg, run, opt)
    step_fn = jax.jit(build_train_step(cfg, run, opt))

    def fresh():
        params = init_params(sch["params"], jax.random.key(0))
        return {"params": params, "opt": opt.init(params),
                "step": jnp.zeros((), jnp.int32)}

    pipe = SyntheticLMPipeline(cfg, shape)
    s_full = fresh()
    for i in range(6):
        s_full, _ = step_fn(s_full, pipe.batch_at(i))

    m = CheckpointManager(tmp_path, async_save=False)
    s_part = fresh()
    for i in range(3):
        s_part, _ = step_fn(s_part, pipe.batch_at(i))
    m.save(3, s_part, extra={"data_step": 3})
    restored, extra = m.restore(abstract_params(sch))
    for i in range(int(extra["data_step"]), 6):
        restored, _ = step_fn(restored, pipe.batch_at(i))

    for a, b in zip(jax.tree.leaves(s_full), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)
        )
