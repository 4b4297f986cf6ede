"""Shared model-building utilities: params-with-sharding-specs, norms, acts.

Parameters are plain dict pytrees.  Every init function returns BOTH the
parameter tree and a parallel tree of *logical* sharding specs — tuples of
logical axis names resolved against the physical mesh at launch time
(launch/mesh.py):

    logical axis    16x16 mesh            2x16x16 mesh
    "fsdp"      ->  "data"                "data"
    "tp"        ->  "model"               "model"
    "ep"        ->  "model"               "model"
    "batch"     ->  ("data",)             ("pod", "data")
    "seq"       ->  "model" (MoE blocks)  "model"

Models never mention physical axis names, so the same definition lowers on a
single CPU device (smoke tests), one pod, or two pods.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------------------
# logical -> physical axis resolution
# ---------------------------------------------------------------------------

def logical_to_physical(mesh_axis_names: Sequence[str]):
    """Return a resolver mapping logical spec tuples -> PartitionSpec."""
    has_pod = "pod" in mesh_axis_names
    table = {
        None: None,
        "fsdp": "data",
        "tp": "model",
        "ep": "model",
        "seq": "model",
        "batch": ("pod", "data") if has_pod else ("data",),
    }

    def resolve(logical: Optional[Tuple]) -> P:
        if logical is None:
            return P()
        return P(*[table[a] for a in logical])

    return resolve


def spec_tree_to_shardings(spec_tree, mesh, shape_tree=None):
    """Resolve logical specs to NamedShardings.

    When `shape_tree` is given, axes whose sizes do not divide the mesh axis
    product are dropped (replicated) — e.g. seamless's vocab 256,206 cannot be
    16-way sharded.
    """
    from jax.sharding import NamedSharding
    resolve = logical_to_physical(mesh.axis_names)
    is_leaf = lambda x: x is None or isinstance(x, tuple)

    if shape_tree is None:
        return jax.tree.map(lambda spec: NamedSharding(mesh, resolve(spec)),
                            spec_tree, is_leaf=is_leaf)

    def one(spec, arr):
        pspec = resolve(spec)
        entries = []
        for dim, entry in enumerate(pspec):
            if entry is None:
                entries.append(None)
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            n = math.prod(mesh.shape[a] for a in axes)
            entries.append(entry if arr.shape[dim] % n == 0 else None)
        return NamedSharding(mesh, P(*entries))

    return jax.tree.map(one, spec_tree, shape_tree, is_leaf=is_leaf)


# ---------------------------------------------------------------------------
# initializers (params + logical specs built together)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ParamAndSpec:
    params: Any
    specs: Any


def dense_init(key, shape, spec, dtype=jnp.bfloat16, scale: Optional[float] = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    w = (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
    return w, spec


def zeros_init(shape, spec, dtype=jnp.bfloat16):
    return jnp.zeros(shape, dtype), spec


def embed_init(key, vocab, d, spec=("tp", "fsdp"), dtype=jnp.bfloat16):
    w = (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)
    return w, spec


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

def rms_norm(x, gamma, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * gamma


def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu2":   # Nemotron/Minitron squared ReLU
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


def rotary_cos_sin(positions, head_dim: int, theta: float = 1e4):
    """positions (...,) int32 -> (cos, sin) of shape (..., head_dim // 2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary(x, cos, sin):
    """x (..., head_dim); cos/sin broadcastable (..., head_dim//2)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def shard(x, *logical):
    """with_sharding_constraint by logical axes.

    Requires an active `jax.set_mesh(mesh)` scope to take effect; outside one
    (unit tests on a single device) it is a no-op.  Dimensions that do not
    divide the mesh axis product are left unconstrained — forcing e.g. a
    16-way split onto 8 KV heads makes GSPMD fall back to full
    rematerialization (replicate + reshard), which is both a memory and a
    collective disaster.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.shape:
        return x
    resolve = logical_to_physical(mesh.axis_names)
    spec = resolve(tuple(logical))
    entries = []
    for dim, entry in enumerate(spec):
        if entry is None:
            entries.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = math.prod(mesh.shape[a] for a in axes)
        entries.append(entry if x.shape[dim] % n == 0 else None)
    return jax.lax.with_sharding_constraint(x, P(*entries))
