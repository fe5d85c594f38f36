"""Shared layer primitives + parameter-table machinery.

Every block defines a *parameter table*: a nested dict mapping name ->
``PSpec(shape, logical_axes, init)``. From one table we derive real params
(`init_params`), abstract params for the dry-run (`abstract_params`), and
sharding specs (`param_axes`) — so the three can never drift apart.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class PSpec:
    shape: tuple
    axes: tuple  # logical axis names (len == len(shape))
    init: str = "normal"  # normal | zeros | ones | lambda_init
    scale: Optional[float] = None  # None -> 1/sqrt(fan_in)

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_pspec(x):
    return isinstance(x, PSpec)


@partial(jax.jit, static_argnums=(1, 2))
def _init_leaf(k, spec, dtype):
    """One parameter leaf, drawn in float32 and cast inside one program,
    so the float32 draw is a fused temporary rather than a second whole
    copy of the leaf next to the weights already placed."""
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "lambda_init":
        # RG-LRU Lambda: a in [0.9, 0.999] -> softplus-inverse param
        u = jax.random.uniform(k, spec.shape, jnp.float32, 0.9, 0.999)
        return jnp.log(jnp.expm1(-jnp.log(u) / 8.0)).astype(dtype)  # c = 8
    if spec.init == "dt_bias":
        u = jax.random.uniform(k, spec.shape, jnp.float32, 1e-3, 1e-1)
        return jnp.log(jnp.expm1(u)).astype(dtype)
    if spec.init == "a_log":
        n = int(np.prod(spec.shape)) if spec.shape else 1
        return jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)
                       ).reshape(spec.shape).astype(dtype)
    scale = spec.scale
    if scale is None:
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        scale = fan_in ** -0.5
    return (jax.random.normal(k, spec.shape, jnp.float32) * scale).astype(dtype)


def init_params(table, key, dtype):
    """Real parameters for ``table``, placed where ``key`` lives."""
    leaves, treedef = jax.tree.flatten(table, is_leaf=_is_pspec)
    keys = jax.random.split(key, len(leaves))
    dtype = jnp.dtype(dtype)
    return jax.tree.unflatten(
        treedef, [_init_leaf(k, spec, dtype) for k, spec in zip(keys, leaves)])


def abstract_params(table, dtype):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(dtype)),
        table, is_leaf=_is_pspec)


def param_axes(table):
    return jax.tree.map(lambda s: s.axes, table, is_leaf=_is_pspec)


def stack_table(table, n, axis_name="layers"):
    """Prepend a stacked-layer dimension to every entry of a table."""
    return jax.tree.map(
        lambda s: PSpec((n,) + s.shape, (axis_name,) + s.axes, s.init, s.scale),
        table, is_leaf=_is_pspec)


# ---------------------------------------------------------------- primitives

def rms_norm(x, w, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def rope_freqs(head_dim, theta):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta):
    """x: (..., S, H, D); positions: (..., S) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, d/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, d/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def swiglu_table(d_model, d_ff, ff_axis="ff"):
    return {
        "w_gate": PSpec((d_model, d_ff), (None, ff_axis)),
        "w_up": PSpec((d_model, d_ff), (None, ff_axis)),
        "w_down": PSpec((d_ff, d_model), (ff_axis, None)),
    }


def swiglu_apply(p, x):
    g = jnp.einsum("...d,df->...f", x, p["w_gate"])
    u = jnp.einsum("...d,df->...f", x, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, p["w_down"])
