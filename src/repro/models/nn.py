"""Minimal functional NN substrate: param specs, init, logical sharding axes.

No flax/haiku in this environment — and a framework this size benefits from a
transparent, pytree-native param system anyway (same philosophy as MaxText's
"params are just a dict" but with t5x-style logical axis annotations).

A model is described by a tree of :class:`ParamSpec` leaves. From that single
tree we derive, without duplication:
  * concrete initialized params            (``init_params``)
  * abstract params for ``.lower()``       (``abstract_params``)
  * per-leaf ``NamedSharding``             (``param_shardings``)

Logical axis names (e.g. ``"embed"``, ``"heads"``, ``"vocab"``) are resolved to
physical mesh axes through prioritized rules with divisibility fallback, so the
same model definition shards correctly on a 16x16 pod and a 2x16x16 multi-pod
mesh, or degrades to replication on a single CPU device for smoke tests.
"""

from __future__ import annotations

import contextvars
import dataclasses
import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Pytree = Any


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Declarative description of one parameter tensor."""

    shape: tuple[int, ...]
    dtype: Any = jnp.bfloat16
    # one logical axis name (or None) per dim, e.g. ("embed", "heads", "head_dim")
    axes: tuple[str | None, ...] = ()
    init: str = "normal"  # normal | zeros | ones | embed | scaled(fan_in)
    scale: float = 1.0

    def __post_init__(self):
        if self.axes and len(self.axes) != len(self.shape):
            raise ValueError(
                f"axes {self.axes} rank mismatch with shape {self.shape}"
            )


def _fan_in(shape: tuple[int, ...]) -> int:
    # all-but-last dims feed in for our [in..., out] weight convention
    return max(1, math.prod(shape[:-1]))


def _init_leaf(key: jax.Array, spec: ParamSpec) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "embed":
        return (jax.random.normal(key, spec.shape, jnp.float32) * spec.scale).astype(
            spec.dtype
        )
    # truncated-normal fan-in scaling (He-ish), the MaxText default
    std = spec.scale / math.sqrt(_fan_in(spec.shape))
    return (
        jax.random.truncated_normal(key, -2.0, 2.0, spec.shape, jnp.float32) * std
    ).astype(spec.dtype)


def is_spec(x: Any) -> bool:
    return isinstance(x, ParamSpec)


def init_params(rng: jax.Array, specs: Pytree) -> Pytree:
    """Materialize a spec tree into concrete arrays (unsharded)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(rng, len(leaves))
    return jax.tree.unflatten(
        treedef, [_init_leaf(k, s) for k, s in zip(keys, leaves)]
    )


def abstract_params(specs: Pytree) -> Pytree:
    """ShapeDtypeStruct stand-ins — used by the dry-run (never allocates)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs, is_leaf=is_spec
    )


# ---------------------------------------------------------------------------
# Logical -> physical axis resolution
# ---------------------------------------------------------------------------

# Priority-ordered candidate mesh axes per logical axis. First candidate whose
# size divides the dim and that is not already claimed by another dim wins.
# ("pod","data") tuple entries mean "shard over the product of those axes".
DEFAULT_RULES: dict[str, Sequence[Any]] = {
    "batch": [("pod", "data"), "data"],
    "embed": [None],                      # replicated unless FSDP rules used
    "embed_fsdp": [("pod", "data"), "data", None],  # ZeRO-3 weight shard
    "heads": ["model"],
    "kv_heads": ["model", None],
    "head_dim": [None],
    # cache-only fallback: when kv_heads < model size (GQA on wide TP), shard
    # the cache's head_dim — keeps a 405B 32k-decode KV cache at ~2GB/chip
    # without forcing weight resharding inside the flash loops
    "cache_head_dim": ["model", None],
    "kv_lora_w": [None],
    "mlp": ["model"],
    "experts": ["model"],
    "expert_mlp": [None],
    "vocab": ["model"],
    "kv_lora": ["model", None],   # MLA latent cache shards on model
    "q_lora": ["model", None],
    "seq": [None],
    "seq_sp": ["model", None],    # sequence parallelism (Megatron-SP)
    "store": [("pod", "data"), "data"],   # semantic-histogram embedding store rows
    "cache_batch": [("pod", "data"), "data"],
    "layers": [None],
    "conv": [None],
    "state": [None],
    "ssm_heads": ["model", None],
    "sample": ["data", None],
}


def _axis_size(mesh: Mesh, axis: Any) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return math.prod(mesh.shape[a] for a in axis if a in mesh.shape)
    return mesh.shape.get(axis, 0)


def _axis_names(axis: Any) -> tuple[str, ...]:
    if axis is None:
        return ()
    return tuple(axis) if isinstance(axis, tuple) else (axis,)


def resolve_pspec(
    shape: tuple[int, ...],
    axes: tuple[str | None, ...],
    mesh: Mesh,
    rules: dict[str, Sequence[Any]] | None = None,
) -> P:
    """Resolve logical axes to a PartitionSpec with divisibility fallback."""
    rules = rules or DEFAULT_RULES
    if not axes:
        axes = (None,) * len(shape)
    taken: set[str] = set()
    out: list[Any] = []
    for dim, name in zip(shape, axes):
        placed = None
        if name is not None:
            for cand in rules.get(name, [None]):
                if cand is None:
                    break
                names = _axis_names(cand)
                if any(n not in mesh.shape for n in names):
                    continue
                if any(n in taken for n in names):
                    continue
                size = _axis_size(mesh, cand)
                if size > 0 and dim % size == 0:
                    placed = cand
                    taken.update(names)
                    break
        out.append(placed)
    return P(*out)


def param_shardings(
    specs: Pytree, mesh: Mesh, rules: dict[str, Sequence[Any]] | None = None
) -> Pytree:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, resolve_pspec(s.shape, s.axes, mesh, rules)),
        specs,
        is_leaf=is_spec,
    )


def logical_constraint(
    x: jax.Array,
    axes: tuple[str | None, ...],
    mesh: Mesh | None = None,
    rules: dict[str, Sequence[Any]] | None = None,
) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op outside a mesh context."""
    mesh = mesh or _current_mesh()
    if mesh is None or mesh.empty:
        return x
    spec = resolve_pspec(x.shape, axes, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


_MESH_CTX: contextvars.ContextVar = contextvars.ContextVar("repro_mesh", default=None)


def mesh_context(mesh: Mesh):
    """Make ``mesh`` visible to logical_constraint during tracing."""
    import contextlib

    @contextlib.contextmanager
    def _ctx():
        tok = _MESH_CTX.set(mesh)
        try:
            yield mesh
        finally:
            _MESH_CTX.reset(tok)

    return _ctx()


def _current_mesh() -> Mesh | None:
    m = _MESH_CTX.get()
    if m is not None and not m.empty:
        return m
    # the mesh jax.set_mesh installed, as tracing sees it
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


# ---------------------------------------------------------------------------
# Spec constructors (thin sugar used across all model files)
# ---------------------------------------------------------------------------


def dense(shape, axes, dtype=jnp.bfloat16, scale=1.0) -> ParamSpec:
    return ParamSpec(tuple(shape), dtype, tuple(axes), "normal", scale)


def embedding(shape, axes, dtype=jnp.bfloat16, scale=1.0) -> ParamSpec:
    return ParamSpec(tuple(shape), dtype, tuple(axes), "embed", scale)


def zeros(shape, axes, dtype=jnp.bfloat16) -> ParamSpec:
    return ParamSpec(tuple(shape), dtype, tuple(axes), "zeros")


def ones(shape, axes, dtype=jnp.bfloat16) -> ParamSpec:
    return ParamSpec(tuple(shape), dtype, tuple(axes), "ones")


def stack_specs(specs: Pytree, n: int, axis_name: str = "layers") -> Pytree:
    """Prepend a stacking dim (for scan-over-layers) to every leaf spec."""

    def _stack(s: ParamSpec) -> ParamSpec:
        return ParamSpec(
            (n, *s.shape), s.dtype, (axis_name, *(s.axes or (None,) * len(s.shape))),
            s.init, s.scale,
        )

    return jax.tree.map(_stack, specs, is_leaf=is_spec)


def count_params(specs: Pytree) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=is_spec)
    return sum(math.prod(s.shape) for s in leaves)


def tree_bytes(specs: Pytree) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=is_spec)
    return sum(math.prod(s.shape) * jnp.dtype(s.dtype).itemsize for s in leaves)
