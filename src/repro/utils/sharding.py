"""Logical-axis sharding rules (MaxText-style), shape-aware.

Model code annotates every parameter / activation dimension with a *logical*
axis name ("embed", "mlp", "heads", "experts", "batch", ...).  A rule table
maps logical axes onto physical mesh axes.  ``make_spec`` resolves the
mapping *per concrete shape*: a mesh axis is only used if the dimension is
divisible by its size and the mesh axis has not already been consumed by an
earlier dimension of the same tensor (PartitionSpec axes must be unique).

This keeps a single rule table valid across all 10 architectures — e.g.
``kv_heads -> model`` silently degrades to replication for gemma3's single
KV head instead of failing to lower.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Logical axis -> tuple of mesh axes (tried in order, first fit wins).
# `None` (or missing) means replicate.
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    # activations
    "batch": ("pod", "data"),          # pod composes with data for batch sharding
    "seq": (),                          # sequence is replicated in training
    "cache_seq": ("data",),             # long-context decode shards the KV cache
    "frames": (),
    # params
    "embed": ("data",),                 # FSDP: shard the d_model dim of weights
    "vocab": ("model",),
    "mlp": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "qk_dim": (),
    "head_dim": (),
    "experts": ("model",),
    "expert_embed": ("data",),          # FSDP for expert weights (own axis)
    "expert_mlp": (),                   # per-expert ffn dim (experts already on model)
    "layers": (),                       # scan-stacked layer dim is never sharded
    "ssm_state": (),
    "conv": (),
    "lora": (),
    "classes": (),
    "summary_dim": (),
    "clients": ("pod", "data"),         # FL-layer: client axis shards like batch
    "centroids": (),
}


# The registry's drift scan (src/repro/stream/registry.py) partitions
# client-row arenas over a dedicated 1-D `fleet` mesh axis instead of the
# model axes.
FLEET_RULES: dict[str, tuple[str, ...]] = {"clients": ("fleet",)}


def fleet_mesh(n_devices: int | None = None) -> Mesh:
    """1-D mesh over the local devices with a single ``fleet`` axis.

    ``n_devices`` is clamped to what the host actually has, so configs
    written for a 4-device CI host degrade to a 1-device mesh on a laptop
    instead of failing.
    """
    devs = jax.devices()
    n = len(devs) if n_devices is None else max(1, min(n_devices, len(devs)))
    return Mesh(np.asarray(devs[:n]), ("fleet",))


def _mesh_axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def make_spec(
    logical_axes: Sequence[str | None],
    shape: Sequence[int],
    mesh: Mesh,
    rules: Mapping[str, tuple[str, ...]] | None = None,
) -> P:
    """Resolve logical axes to a PartitionSpec valid for `shape` on `mesh`."""
    rules = dict(DEFAULT_RULES, **(rules or {}))
    sizes = _mesh_axis_sizes(mesh)
    used: set[str] = set()
    out: list = []
    assert len(logical_axes) == len(shape), (logical_axes, shape)
    for name, dim in zip(logical_axes, shape):
        if name is None:
            out.append(None)
            continue
        candidates = rules.get(name, ())
        picked: list[str] = []
        remaining = dim
        for ax in candidates:
            if ax in used or ax not in sizes:
                continue
            if remaining % sizes[ax] == 0 and remaining >= sizes[ax]:
                picked.append(ax)
                used.add(ax)
                remaining //= sizes[ax]
        if len(picked) == 0:
            out.append(None)
        elif len(picked) == 1:
            out.append(picked[0])
        else:
            out.append(tuple(picked))
    # PartitionSpec trims trailing Nones automatically.
    return P(*out)


def make_sharding(logical_axes, shape, mesh, rules=None) -> NamedSharding:
    return NamedSharding(mesh, make_spec(logical_axes, shape, mesh, rules))


def tree_shardings(spec_tree, shape_tree, mesh, rules=None):
    """Map parallel pytrees of logical-axes tuples and shapes to NamedShardings.

    `spec_tree` leaves are tuples of logical axis names; `shape_tree` leaves are
    anything with `.shape` (arrays or ShapeDtypeStructs).
    """
    def _one(axes, arr):
        return make_sharding(axes, arr.shape, mesh, rules)

    return jax.tree.map(
        _one, spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """A named bundle of rule overrides — used by the perf hillclimb to try
    alternative sharding layouts without touching model code."""
    name: str
    overrides: dict

    def merged(self) -> dict:
        return dict(DEFAULT_RULES, **self.overrides)
