"""A plain float32 CNN training step: the reference of every comparison.

It follows the configuration file alone and imports nothing of the
program: 3x3 same-padded convolutions (``lax.conv_general_dilated``) with
bias, ReLU and 2x2 max-pool, two dense layers, mean cross-entropy, and
AdamW with global-norm clipping, linear warm-up and cosine decay, weight
decay on matrices only.  Weights are drawn from the seed with the
initialisation the configuration states (``normal / sqrt(fan_in)`` per
leaf, the leaf's key folded from the CRC-32 of its name; biases zero).

The step's gradient is summed over blocks of at most ``BLOCK_ROWS``
images, so that the reference fits beside nothing else on one chip at
the batch the window trains on; the loss is the mean over all of them.

``precision="fp8"`` is the control: every convolution and matmul operand,
and every gradient flowing back out of one, is rounded to float8 e4m3
with a per-tensor scale, the step below the bfloat16 that the
configuration computes in.
"""

from __future__ import annotations

import dataclasses
import math
import zlib

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.work import stage_shapes

HIGHEST = lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
BLOCK_ROWS = 1024  # images of one block of the reference's gradient


@dataclasses.dataclass
class Side:
    """What one side of the comparison produced over the first steps."""

    losses: list  # loss of each step
    grad: dict  # leaf -> norm of the first step's gradient after clipping
    update: dict  # leaf -> norm of the parameters' change after the steps
    grad_raw: dict = None  # leaf -> norm of the first unclipped gradient


def init_params(config: dict, seed: int) -> dict:
    key = jax.random.PRNGKey(seed)
    params = {}
    for name, _, w, _ in stage_shapes(config, 1):
        fan_in = w[2] if name.startswith("conv") else w[0]
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        params[name] = jax.random.normal(k, w, jnp.float32) * (1.0 / math.sqrt(fan_in))
        bias = f"bias{name[4:]}" if name.startswith("conv") else f"{name}_b"
        params[bias] = jnp.zeros((w[-1],), jnp.float32)
    return params


@jax.custom_vjp
def _fp8(x):
    return _round_fp8(x)


def _round_fp8(x):
    scale = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return ((x * scale).astype(F8).astype(jnp.float32) / scale).astype(x.dtype)


_fp8.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (_round_fp8(g),))


def logits(params, images, config: dict, precision: str = "float32"):
    q = _fp8 if precision == "fp8" else (lambda a: a)
    pool = config["pool"]
    x = images
    for i in range(config["conv_layers"]):
        w = params[f"conv{i}"]
        pad = w.shape[0] // 2
        x = lax.conv_general_dilated(
            q(x), q(w), (1, 1), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
        x = jnp.maximum(x + params[f"bias{i}"], 0.0)
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, pool, pool, 1),
                              (1, pool, pool, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    x = jnp.dot(q(x), q(params["fc1"]), precision=HIGHEST) + params["fc1_b"]
    x = jnp.maximum(x, 0.0)
    return jnp.dot(q(x), q(params["fc2"]), precision=HIGHEST) + params["fc2_b"]


def loss_fn(params, images, labels, config: dict, precision: str):
    x = logits(params, images, config, precision)
    lse = jax.nn.logsumexp(x, -1)
    return jnp.mean(lse - jnp.take_along_axis(x, labels[:, None], -1)[:, 0])


def lr_at(opt: dict, step):
    """Learning rate of update ``step`` (1-based)."""
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / max(opt["warmup_steps"], 1), 1.0)
    frac = jnp.clip((step - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1), 0, 1)
    return opt["learning_rate"] * warm * (0.1 + 0.45 * (1 + jnp.cos(jnp.pi * frac)))


def norms(tree) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def blocks(batch: int) -> int:
    """How many equal blocks of at most ``BLOCK_ROWS`` images a batch is
    summed over."""
    n = -(-batch // BLOCK_ROWS)
    if batch % n:
        raise ValueError(f"a batch of {batch} images does not split into "
                         f"{n} equal blocks of at most {BLOCK_ROWS}")
    return n


def make_step(config: dict, precision: str):
    """One jitted reference step: ``(params, m, v, t, images, labels) ->
    (params, m, v, t, loss, clipped gradient norms, raw gradient norms)``."""
    opt = config["optimizer"]

    def block_sum(params, images, labels):
        # Sum, not mean, over the block: the blocks add up to the batch.
        return loss_fn(params, images, labels, config, precision) * len(labels)

    @jax.jit
    def step(params, m, v, t, images, labels):
        n = blocks(len(labels))

        def add(acc, block):
            loss, grads = jax.value_and_grad(block_sum)(params, *block)
            return jax.tree.map(jnp.add, acc, (loss, grads)), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(jnp.zeros_like, params))
        (loss, grads), _ = lax.scan(
            add, zero, (images.reshape(n, -1, *images.shape[1:]),
                        labels.reshape(n, -1)))
        loss = loss / len(labels)
        grads = jax.tree.map(lambda g: g / len(labels), grads)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
        clip = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
        t = t + 1
        lr = lr_at(opt, t)
        b1, b2 = opt["beta1"], opt["beta2"]
        bc1 = 1 - b1 ** t.astype(jnp.float32)
        bc2 = 1 - b2 ** t.astype(jnp.float32)
        new_p, new_m, new_v, clipped = {}, {}, {}, {}
        for k, p in params.items():
            g = grads[k] * clip
            clipped[k] = g
            new_m[k] = b1 * m[k] + (1 - b1) * g
            new_v[k] = b2 * v[k] + (1 - b2) * g * g
            u = (new_m[k] / bc1) / (jnp.sqrt(new_v[k] / bc2) + opt["eps"])
            if p.ndim >= 2:
                u = u + opt["weight_decay"] * p
            new_p[k] = p - lr * u
        return new_p, new_m, new_v, t, loss, norms(clipped), norms(grads)

    return step


def train(config: dict, batches, seed: int, precision: str = "float32",
          device=None) -> Side:
    """The reference's first ``len(batches)`` steps from the seed's
    weights, on ``device`` (the default device when None)."""
    device = device or jax.devices()[0]
    step = make_step(config, precision)
    with jax.default_device(device), jax.default_matmul_precision("highest"):
        params = p0 = init_params(config, seed)
        m = {k: jnp.zeros_like(p) for k, p in params.items()}
        v = dict(m)
        t = jnp.zeros((), jnp.int32)
        losses, grad, grad_raw = [], None, None
        for b in batches:
            params, m, v, t, loss, gclip, graw = step(
                params, m, v, t, jnp.asarray(b["images"]),
                jnp.asarray(b["labels"]))
            losses.append(loss)
            if grad is None:
                grad, grad_raw = gclip, graw
        update = norms({k: params[k] - p0[k] for k in params})
    get = lambda d: {k: float(x) for k, x in d.items()}
    return Side([float(x) for x in losses], get(grad), get(update),
                get(grad_raw))
