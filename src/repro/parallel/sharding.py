"""Logical-axis sharding: model code names axes, a rules table maps them to
mesh axes, and a divisibility guard drops any mapping that does not divide.

Why the guard: the production mesh is fixed at (data=16, model=16) [+pod=2],
but the assigned architectures have head counts (28, 25, 96/kv8), expert
counts (60, 40) and vocabs that are not all divisible by 16.  Rather than
hand-casing every arch, ``logical_spec`` checks divisibility per tensor and
falls back to replication on that axis — e.g. qwen2's 28 Q-heads replicate
over ``model`` while its head_dim (128) takes the TP sharding instead (see
"heads"/"head_dim" both mapping to "model": the first divisible one wins,
axes are never used twice).

Logical axes used by the model code:
  batch     -> ("pod", "data")   data parallel (pod folds into DP)
  fsdp      -> "data"            parameter/optimizer sharding (ZeRO-3)
  model/tp  -> "model"           tensor parallel (d_ff, heads, vocab, experts)
  seq       -> sequence parallel axis (activations, long-context)
"""
from __future__ import annotations

import threading
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

AxisName = str | tuple[str, ...] | None

DEFAULT_RULES: dict[str, AxisName] = {
    "batch": ("pod", "data"),
    "fsdp": "data",
    "embed": None,           # d_model on activations: replicated
    "mlp": "model",          # d_ff
    "heads": "model",        # attention / ssm heads
    "head_dim": "model",     # fallback TP axis when heads don't divide
    "kv_heads": "model",
    "vocab": "model",
    "experts": "model",      # EP when divisible, else falls back
    "expert_mlp": "model",   # TP inside experts (used when EP doesn't divide)
    "seq": "data",           # sequence parallelism (activations only)
    "seq_sp": "model",       # Megatron-style SP: residual stream S over TP
    "cache_seq": None,
    "conv": None,
    "state": None,
}


class LogicalRules(threading.local):
    def __init__(self):
        self.rules = dict(DEFAULT_RULES)


_RULES = LogicalRules()


def set_rules(rules: dict[str, AxisName]) -> None:
    _RULES.rules = dict(rules)


def get_rules() -> dict[str, AxisName]:
    return _RULES.rules


def _mesh_axis_sizes() -> dict[str, int]:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return {}
    return dict(mesh.shape)


def logical_spec(shape: Sequence[int], logical: Sequence[str | None]) -> P:
    """Map logical axis names to a PartitionSpec, enforcing divisibility and
    never assigning the same mesh axis twice (first divisible dim wins).
    Tuple rules (e.g. batch -> ("pod", "data")) keep whichever member axes
    exist in the current mesh."""
    sizes = _mesh_axis_sizes()
    used: set[str] = set()
    out: list[AxisName] = []
    for dim, name in zip(shape, logical):
        axis = _RULES.rules.get(name) if name else None
        if axis is None:
            out.append(None)
            continue
        axes = tuple(a for a in ((axis,) if isinstance(axis, str) else axis)
                     if sizes.get(a))
        n = 1
        for a in axes:
            n *= sizes[a]
        if not axes or dim % n or any(a in used for a in axes):
            out.append(None)
            continue
        used.update(axes)
        out.append(axes[0] if len(axes) == 1 else axes)
    return P(*out)


def constrain(x: jax.Array, logical: Sequence[str | None]) -> jax.Array:
    """with_sharding_constraint by logical names; no-op outside a mesh
    context (smoke tests run unsharded on one CPU device)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return x
    spec = logical_spec(x.shape, logical)
    return jax.lax.with_sharding_constraint(x, spec)


def _is_names(v) -> bool:
    return isinstance(v, tuple) and all(isinstance(e, (str, type(None))) for e in v)


def spec_tree(logical_tree, params):
    """Map a pytree of logical-name tuples (mirroring ``params``) to
    PartitionSpecs.  ``params`` may hold ShapeDtypeStructs (abstract init)."""
    return jax.tree.map(
        lambda names, p: logical_spec(p.shape, names),
        logical_tree, params, is_leaf=_is_names)
