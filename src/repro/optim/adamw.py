"""AdamW in pure JAX, with ZeRO-1 optimizer-state sharding.

Optimizer moments are f32 regardless of param dtype (bf16-param training
keeps full-precision statistics).  ``zero1_specs`` extends each param's
PartitionSpec by sharding the first *unsharded, divisible* dimension over
the data axes — the moments (2 x f32 per param) dominate optimizer memory,
so this is where ZeRO-1 pays.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import TrainConfig


@dataclasses.dataclass(frozen=True)
class AdamWState:
    step: Any
    m: Any
    v: Any


jax.tree_util.register_dataclass(
    AdamWState, data_fields=["step", "m", "v"], meta_fields=[]
)


def init(params) -> AdamWState:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return AdamWState(
        step=jnp.zeros((), jnp.int32),
        m=jax.tree.map(zeros, params),
        v=jax.tree.map(zeros, params),
    )


def abstract_state(params) -> AdamWState:
    z = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    return AdamWState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        m=jax.tree.map(z, params),
        v=jax.tree.map(z, params),
    )


def lr_schedule(cfg: TrainConfig, step):
    """Linear warmup then cosine decay to 10%."""
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / max(cfg.warmup_steps, 1), 1.0)
    frac = jnp.clip(
        (step - cfg.warmup_steps) / max(cfg.total_steps - cfg.warmup_steps, 1), 0, 1
    )
    cos = 0.1 + 0.45 * (1 + jnp.cos(jnp.pi * frac))
    return cfg.learning_rate * warm * cos


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(tree))
    )


@jax.named_scope("optimizer")
def apply_updates(params, grads, state: AdamWState, cfg: TrainConfig):
    """Returns (new_params, new_state, metrics).  The whole update, global
    norm and clip included, runs under the ``optimizer`` name scope, so
    that a compiled step's optimizer operations carry it in op_name."""
    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9)) if cfg.grad_clip else 1.0
    step = state.step + 1
    lr = lr_schedule(cfg, step)
    b1, b2 = cfg.beta1, cfg.beta2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * clip
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        u = (m / bc1) / (jnp.sqrt(v / bc2) + cfg.eps)
        if p.ndim >= 2:  # decoupled weight decay on matrices only
            u = u + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * u).astype(p.dtype), m, v

    flat = jax.tree.map(upd, params, grads, state.m, state.v)
    new_params = jax.tree.map(lambda t: t[0], flat, is_leaf=lambda x: isinstance(x, tuple))
    new_m = jax.tree.map(lambda t: t[1], flat, is_leaf=lambda x: isinstance(x, tuple))
    new_v = jax.tree.map(lambda t: t[2], flat, is_leaf=lambda x: isinstance(x, tuple))
    return new_params, AdamWState(step, new_m, new_v), {"lr": lr, "grad_norm": gnorm}


def zero1_specs(param_specs, params_abstract, dp_axes: tuple, mesh_shape: dict):
    """ZeRO-1: shard each moment over the data axes on the first dimension
    that is unsharded and divisible by the data-parallel extent."""
    dp = 1
    for a in dp_axes:
        dp *= mesh_shape[a]

    def one(spec: P, aval):
        entries = list(spec) + [None] * (len(aval.shape) - len(spec))
        for i, (e, n) in enumerate(zip(entries, aval.shape)):
            if e is None and n % dp == 0 and n > 0:
                entries[i] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
                return P(*entries)
        return P(*entries)

    moments = jax.tree.map(one, param_specs, params_abstract)
    return AdamWState(step=P(), m=moments, v=moments)
