"""Logical-axis sharding rules with divisibility fallback.

Every parameter / activation dimension carries a *logical* axis name
("batch", "heads", "mlp", ...).  A rule table maps logical names to an
ordered list of candidate mesh axes; the first candidate whose size divides
the dimension (and is not already taken by another dim of the same tensor)
wins, otherwise the dim is replicated.  This is the t5x/MaxText pattern and
is what lets one model definition serve the (16,16) single-pod mesh, the
(2,16,16) multi-pod mesh, CPU smoke tests (1 device) and elastic re-meshes
without edits.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# Rule tables
# ---------------------------------------------------------------------------

# Training rules.  Order within each entry = preference order.  A tuple
# entry like ("pod", "data") means "shard over the product of these axes"
# (all must exist in the mesh; divisibility checked on the product).
#
# "embed" is the *parameter* d_model axis: sharded over "data" for training
# (FSDP/ZeRO-3 weight sharding — XLA inserts the per-layer all-gather),
# replicated for serving (decode is memory-bound; re-gathering weights
# every step would swamp ICI).  "d_model" is the *activation* embedding
# axis: always replicated on "model" (Megatron-style TP).
TRAIN_RULES: dict[str, tuple[Any, ...]] = {
    # activations / data
    "batch": (("pod", "data"), ("data",), ("pod",)),
    "seq": (),                      # replicated by default (activations)
    # sequence-sharded residual stream between layers (Megatron-SP):
    # off by default; enabled per-run (RunConfig.seq_shard) — shrinks the
    # remat stash by the model-parallel degree at the cost of AG/RS
    # around each mixer (hillclimb A, dsv3 memory iteration)
    "seq_res": (),
    "kv_seq": (("model",),),        # decode KV cache sequence dim
    "kv_seq_long": (("data", "model"), ("model",),),  # batch-1 long decode
    "d_model": (),                  # Megatron: activations replicated on model
    # parameters
    "embed": (("data",),),          # FSDP weight shard (train)
    "heads": (("model",),),
    "kv_heads": (("model",),),      # falls back to replicate when kv<model
    "mlp": (("model",),),           # FFN hidden
    "vocab": (("model",),),
    "experts": (("model",),),
    # EP layout (hillclimb A): experts over "data" (classic MoE a2a:
    # token-major -> expert-major over the same shards), contraction dim
    # of the expert matmuls over "model".  Realized with an explicit
    # shard_map (models/moe.apply_moe_ep) after two GSPMD-constraint
    # formulations were refuted — the partitioner lowered the reshard as
    # replicate/all-gather instead of all-to-all (EXPERIMENTS.md §Perf).
    "experts_ep": (("data",), ("model",)),
    "ep_embed": (("model",),),
    "expert_cap": (),
    "layers": (),                   # stacked-scan leading dim
    "ssm_inner": (("model",),),     # mamba d_inner
    "ssm_heads": (("model",),),
    "ssm_state": (),
    "conv_w": (),
    "kv_lora": (),                  # MLA latent dim (small; replicated)
    "q_lora": (),
    "rope": (),
    "head_dim": (),
    "frames": (),                   # audio encoder stub frames
    # optimizer-state extra sharding (ZeRO-1): tried on top of param rules
    "zero1": (("data",),),
}

# Serving rules: weights resident (no FSDP gather); giant MoE expert banks
# spread EP over (pod, data) with TP on the expert hidden dim.
SERVE_RULES: dict[str, tuple[Any, ...]] = {
    **TRAIN_RULES,
    "embed": (),
    "experts": (("pod", "data"), ("data",), ("model",)),
}

DEFAULT_RULES = TRAIN_RULES


def make_rules(mesh: Mesh, phase: str = "train",
               flat_dp: bool = False) -> "AxisRules":
    """flat_dp: treat "model" as a second data axis — for archs whose
    head count does not divide the model axis (whisper: 20 heads vs 16)
    where tensor parallelism would otherwise replicate the attention
    compute on every model rank (hillclimb B)."""
    table = dict(TRAIN_RULES if phase == "train" else SERVE_RULES)
    if flat_dp:
        table["batch"] = (
            ("pod", "data", "model"), ("pod", "data"), ("data", "model"),
            ("data",),
        )
        table["heads"] = ()
        table["kv_heads"] = ()
        table["mlp"] = ()
        table["ssm_inner"] = ()
        table["ssm_heads"] = ()
    return AxisRules(mesh, table)


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """A rule table bound to a mesh."""

    mesh: Mesh
    rules: dict[str, tuple[Any, ...]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_RULES)
    )

    def mesh_axis_size(self, axes: Sequence[str]) -> int:
        n = 1
        for a in axes:
            n *= self.mesh.shape.get(a, 1)
        return n

    def resolve_dim(self, logical: str | None, size: int, taken: set[str]):
        """Pick mesh axes for one dim, honoring divisibility + exclusivity."""
        if logical is None:
            return None
        for cand in self.rules.get(logical, ()):
            axes = (cand,) if isinstance(cand, str) else tuple(cand)
            if any(a in taken for a in axes):
                continue
            if any(a not in self.mesh.shape for a in axes):
                continue
            n = self.mesh_axis_size(axes)
            if n > 1 and size % n == 0:
                taken.update(axes)
                return axes if len(axes) > 1 else axes[0]
            if n == 1:
                continue
        return None

    def spec(self, logical_axes: Sequence[str | None], shape: Sequence[int]) -> P:
        if len(logical_axes) != len(shape):
            raise ValueError(
                f"logical axes {logical_axes} rank != shape {shape} rank"
            )
        taken: set[str] = set()
        parts = [
            self.resolve_dim(name, dim, taken)
            for name, dim in zip(logical_axes, shape)
        ]
        # trim trailing Nones (canonical form)
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)

    def sharding(self, logical_axes, shape) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical_axes, shape))

    def zero1_spec(self, logical_axes: Sequence[str | None],
                   shape: Sequence[int]) -> P:
        """Param spec + an extra 'data' split on the largest still-unsharded
        divisible dim (ZeRO-1 optimizer-state sharding)."""
        base = self.spec(logical_axes, shape)
        parts = list(base) + [None] * (len(shape) - len(base))
        taken = {a for p in parts if p for a in ((p,) if isinstance(p, str) else p)}
        if "data" in taken or "data" not in self.mesh.shape:
            return base
        dsize = self.mesh.shape["data"]
        order = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in order:
            if parts[i] is None and shape[i] % dsize == 0 and shape[i] >= dsize:
                parts[i] = "data"
                break
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)

    def zero1_sharding(self, logical_axes, shape) -> NamedSharding:
        return NamedSharding(self.mesh, self.zero1_spec(logical_axes, shape))


# ---------------------------------------------------------------------------
# Thread-local rule context (used by model code for activation constraints)
# ---------------------------------------------------------------------------

_CTX = threading.local()


@contextlib.contextmanager
def axis_rules(rules: AxisRules | None):
    prev = getattr(_CTX, "rules", None)
    _CTX.rules = rules
    try:
        yield
    finally:
        _CTX.rules = prev


def current_rules() -> AxisRules | None:
    return getattr(_CTX, "rules", None)


def mesh_and_manual():
    """(abstract mesh, its Manual-typed axis names) of the current trace
    context — inside a shard_map region the manual axes are the ones a
    sharding constraint must leave out."""
    am = jax.sharding.get_abstract_mesh()
    manual = {
        name for name, t in zip(am.axis_names, am.axis_types)
        if t == jax.sharding.AxisType.Manual
    }
    return am, manual


def shard(x: jax.Array, *logical_axes: str | None) -> jax.Array:
    """Constrain an activation's sharding; no-op outside an axis_rules ctx.

    Inside a shard_map manual region (e.g. the compressed cross-pod step,
    manual over "pod") the constraint is rebuilt on the context's abstract
    mesh with Manual axes dropped — constraining a manual axis is an error
    and those dims are already physically local.
    """
    rules = current_rules()
    if rules is None:
        return x
    spec = rules.spec(logical_axes, x.shape)
    am, manual = mesh_and_manual()
    if manual:
        parts = []
        for p_ in tuple(spec):
            if p_ is None:
                parts.append(None)
                continue
            axes = (p_,) if isinstance(p_, str) else tuple(p_)
            axes = tuple(a for a in axes if a not in manual)
            parts.append(
                None if not axes else (axes[0] if len(axes) == 1 else axes)
            )
        while parts and parts[-1] is None:
            parts.pop()
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(am, P(*parts))
        )
    return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))


# ---------------------------------------------------------------------------
# Parameter schema: declare once, materialize many ways
# ---------------------------------------------------------------------------

InitFn = Callable[[jax.Array, tuple[int, ...], Any], jax.Array]


def _fan_in_init(key, shape, dtype, fan_axis=-2, scale=1.0):
    fan_in = shape[fan_axis] if len(shape) >= 2 else shape[-1]
    std = scale / (fan_in ** 0.5)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _zeros_init(key, shape, dtype):
    del key
    return jnp.zeros(shape, dtype)


def _ones_init(key, shape, dtype):
    del key
    return jnp.ones(shape, dtype)


def _normal_init(std: float):
    def init(key, shape, dtype):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    return init


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Metadata-only description of one parameter tensor."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    dtype: Any = jnp.float32
    init: InitFn = _fan_in_init

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes}")

    @property
    def size(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n


def param(shape, axes, dtype=jnp.float32, init: InitFn = _fan_in_init) -> ParamSpec:
    return ParamSpec(tuple(shape), tuple(axes), dtype, init)


def zeros_param(shape, axes, dtype=jnp.float32) -> ParamSpec:
    return ParamSpec(tuple(shape), tuple(axes), dtype, _zeros_init)


def scale_param(shape, axes, dtype=jnp.float32) -> ParamSpec:
    return ParamSpec(tuple(shape), tuple(axes), dtype, _ones_init)


def normal_param(shape, axes, std, dtype=jnp.float32) -> ParamSpec:
    return ParamSpec(tuple(shape), tuple(axes), dtype, _normal_init(std))


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _tree_map_specs(fn, schema):
    return jax.tree.map(fn, schema, is_leaf=is_spec)


def stack_schema(schema, n: int, axis_name: str | None = "layers"):
    """Add a leading stacked-layers dim to every spec in a schema."""

    def stk(s: ParamSpec) -> ParamSpec:
        def init(key, shape, dtype, _inner=s.init):
            keys = jax.random.split(key, shape[0])
            return jax.vmap(lambda k: _inner(k, shape[1:], dtype))(keys)

        return ParamSpec((n,) + s.shape, (axis_name,) + s.axes, s.dtype, init)

    return _tree_map_specs(stk, schema)


def init_params(schema, key: jax.Array):
    """Materialize real parameter values from a schema."""
    leaves, treedef = jax.tree.flatten(schema, is_leaf=is_spec)
    keys = jax.random.split(key, max(len(leaves), 1))
    vals = [s.init(k, s.shape, s.dtype) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(schema):
    """ShapeDtypeStructs for dry-run lowering — no allocation."""
    return _tree_map_specs(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), schema
    )


def param_axes(schema):
    return _tree_map_specs(lambda s: s.axes, schema)


def param_shardings(schema, rules: AxisRules):
    return _tree_map_specs(lambda s: rules.sharding(s.axes, s.shape), schema)


def param_pspecs(schema, rules: AxisRules):
    return _tree_map_specs(lambda s: rules.spec(s.axes, s.shape), schema)


def zero1_shardings(schema, rules: AxisRules):
    return _tree_map_specs(
        lambda s: rules.zero1_sharding(s.axes, s.shape), schema
    )


def zero1_pspecs(schema, rules: AxisRules):
    return _tree_map_specs(lambda s: rules.zero1_spec(s.axes, s.shape), schema)


def count_params(schema) -> int:
    leaves = jax.tree.leaves(schema, is_leaf=is_spec)
    return sum(s.size for s in leaves)


def cast_schema(schema, dtype):
    return _tree_map_specs(
        lambda s: ParamSpec(s.shape, s.axes, dtype, s.init), schema
    )
