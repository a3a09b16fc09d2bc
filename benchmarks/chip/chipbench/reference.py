"""A plain float32 training step: the reference of every comparison.

The model is the family's (``families/<family>.py``: ``init_params`` and
``loss_fn``), which follows the configuration file alone and imports
nothing of the program.  The step around it is the same for every
family: AdamW with global-norm clipping, linear warm-up and cosine decay,
weight decay on leaves of two or more dimensions.

The step's gradient is summed over blocks of at most the family's
``BLOCK_ROWS`` examples, so that the reference fits beside nothing else
on one chip at the batch the window trains on; the loss is the mean over
all of them, which holds as long as every example weighs the same in the
family's loss.

``precision="fp8"`` is the control: every operand of a convolution or a
matrix product, and every gradient flowing back out of one, is rounded to
float8 e4m3 with a per-tensor scale, the step below the bfloat16 that the
configuration computes in.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from chipbench import cell

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


@dataclasses.dataclass
class Side:
    """What one side of the comparison produced over the first steps."""

    losses: list  # loss of each step
    grad: dict  # leaf -> norm of the first step's gradient after clipping
    update: dict  # leaf -> norm of the parameters' change after the steps
    grad_raw: dict = None  # leaf -> norm of the first unclipped gradient


@jax.custom_vjp
def _fp8(x):
    return _round_fp8(x)


def _round_fp8(x):
    scale = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return ((x * scale).astype(F8).astype(jnp.float32) / scale).astype(x.dtype)


_fp8.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (_round_fp8(g),))


def lr_at(opt: dict, step):
    """Learning rate of update ``step`` (1-based)."""
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / max(opt["warmup_steps"], 1), 1.0)
    frac = jnp.clip((step - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1), 0, 1)
    return opt["learning_rate"] * warm * (0.1 + 0.45 * (1 + jnp.cos(jnp.pi * frac)))


def leaves(tree) -> dict:
    """Every leaf of a tree by its ``/``-joined path (``layers/attn/wq``;
    a flat dict's leaves keep their keys)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(path, simple=True, separator="/"): leaf
            for path, leaf in flat}


def norms(tree) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in leaves(tree).items()}


def blocks(batch: int, block_rows: int) -> int:
    """How many equal blocks of at most ``block_rows`` examples a batch is
    summed over."""
    n = -(-batch // block_rows)
    if batch % n:
        raise ValueError(f"a batch of {batch} examples does not split into "
                         f"{n} equal blocks of at most {block_rows}")
    return n


def rows(batch) -> int:
    """Examples in a batch: the leading axis of its leaves."""
    return jax.tree.leaves(batch)[0].shape[0]


def make_step(config: dict, precision: str):
    """One jitted reference step: ``(params, m, v, t, batch) ->
    (params, m, v, t, loss, clipped gradient norms, raw gradient norms)``."""
    family = cell.family(config)
    opt = config["optimizer"]
    q = _fp8 if precision == "fp8" else (lambda a: a)

    def block_sum(params, block):
        # Sum, not mean, over the block: the blocks add up to the batch.
        return family.loss_fn(params, block, config, q) * rows(block)

    @jax.jit
    def step(params, m, v, t, batch):
        n_rows = rows(batch)
        n = blocks(n_rows, family.BLOCK_ROWS)

        def add(acc, block):
            loss, grads = jax.value_and_grad(block_sum)(params, block)
            return jax.tree.map(jnp.add, acc, (loss, grads)), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(jnp.zeros_like, params))
        (loss, grads), _ = lax.scan(
            add, zero, jax.tree.map(lambda a: a.reshape(n, -1, *a.shape[1:]),
                                    batch))
        loss = loss / n_rows
        grads = jax.tree.map(lambda g: g / n_rows, grads)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree.leaves(grads)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
        t = t + 1
        lr = lr_at(opt, t)
        b1, b2 = opt["beta1"], opt["beta2"]
        bc1 = 1 - b1 ** t.astype(jnp.float32)
        bc2 = 1 - b2 ** t.astype(jnp.float32)

        def adamw(p, g, m, v):
            g = g * clip
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            u = (m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
            if p.ndim >= 2:
                u = u + opt["weight_decay"] * p
            return p - lr * u, m, v, g

        out = jax.tree.map(adamw, params, grads, m, v)
        part = lambda i: jax.tree.map(lambda o: o[i], out,
                                      is_leaf=lambda o: isinstance(o, tuple))
        return (part(0), part(1), part(2), t, loss, norms(part(3)),
                norms(grads))

    return step


def train(config: dict, batches, seed: int, precision: str = "float32",
          device=None) -> Side:
    """The reference's first ``len(batches)`` steps from the seed's
    weights, on ``device`` (the default device when None)."""
    device = device or jax.devices()[0]
    step = make_step(config, precision)
    with jax.default_device(device), jax.default_matmul_precision("highest"):
        params = p0 = cell.family(config).init_params(config, seed)
        m = jax.tree.map(jnp.zeros_like, params)
        v = m
        t = jnp.zeros((), jnp.int32)
        losses, grad, grad_raw = [], None, None
        for b in batches:
            params, m, v, t, loss, gclip, graw = step(
                params, m, v, t, {k: jnp.asarray(a) for k, a in b.items()})
            losses.append(loss)
            if grad is None:
                grad, grad_raw = gclip, graw
        update = norms(jax.tree.map(jnp.subtract, params, p0))
    get = lambda d: {k: float(x) for k, x in d.items()}
    return Side([float(x) for x in losses], get(grad), get(update),
                get(grad_raw))
