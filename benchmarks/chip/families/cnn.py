"""The CNN family: conv stages of one 3x3 conv + bias + ReLU + 2x2
max-pool each, then two FC layers, trained on image batches.

The reference (``init_params``, ``logits``, ``loss_fn``) follows the
configuration file alone and imports nothing of the program: 3x3
same-padded convolutions (``lax.conv_general_dilated``) with bias, ReLU
and 2x2 max-pool, two dense layers, mean cross-entropy.  Weights are drawn
from the seed with the initialisation the configuration states
(``normal / sqrt(fan_in)`` per leaf, the leaf's key folded from the CRC-32
of its name; biases zero).

The work counts (``passes``) take each stage's forward, input gradient and
weight gradient as the work the layer defines, whatever algorithm the
program picks for it: a 3x3 convolution is ``2 * out_pixels * 9 * c_in *
c_out`` operations in every pass, and its least HBM traffic is reading the
operands and writing the result once.  The first stage's input gradient
is never computed (nothing upstream of the images is trained), so it is
not counted.  Bias, ReLU, pooling, the loss and the optimizer are not
counted as model operations.
"""

from __future__ import annotations

import math
import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.work import Pass

HIGHEST = lax.Precision.HIGHEST
BLOCK_ROWS = 1024  # images of one block of the reference's gradient
STAGE_PATTERN = re.compile(r"(?:^|[/(])((?:conv|fc)\d+)(?:[/)]|$)")


def check_program(config: dict) -> None:
    """Refuse to run when the program's registry entry for the arch is
    not the configuration the file states."""
    from repro.launch import train as launcher
    from repro.models import cnn

    cfg = launcher.get_config(config["arch"])
    have = {"conv_layers": cfg.n_layers, "first_channels": cfg.d_model,
            "fc_hidden": cfg.d_ff, "classes": cfg.vocab,
            "image_size": cnn.IMG, "in_channels": cnn.IN_CH, "filter": cnn.F,
            "last_channels": cfg.d_model * 2 ** (cfg.n_layers - 1)}
    wrong = {k: (v, config[k]) for k, v in have.items() if v != config[k]}
    if wrong:
        raise SystemExit(f"the program's {config['arch']} is not the "
                         f"configuration's (program, file): {wrong}")


def launcher_args(config: dict, traffic: dict) -> list:
    return []


def make_batch(seed: int, index: int, config: dict, traffic: dict) -> dict:
    """One batch: ``images`` f32 [batch, image, image, channels] and
    ``labels`` int32 [batch], drawn from ``(seed, index)`` alone.

    The arithmetic is that of the program's synthetic image source
    (standard normal pixels, each image's mean shifted by its class), kept
    here so that the benchmark owns its inputs."""
    batch, image = int(traffic["batch"]), config["image_size"]
    classes = config["classes"]
    rng = np.random.default_rng(np.random.SeedSequence([seed, index]))
    labels = rng.integers(0, classes, size=(batch,)).astype(np.int32)
    images = rng.standard_normal(
        (batch, image, image, config["in_channels"])).astype(np.float32)
    images += (labels / max(1, classes - 1) - 0.5)[:, None, None, None]
    return {"images": images, "labels": labels}


def tokens(batch: dict) -> None:
    return None


def stages(config: dict) -> list:
    return ([(f"conv{i}", "conv") for i in range(config["conv_layers"])]
            + [("fc1", "fc"), ("fc2", "fc")])


def stage_shapes(config: dict, batch: int):
    """``(name, x_shape, w_shape, y_shape)`` of every stage; conv shapes
    are NHWC / HWIO and ``y`` is the pooled output."""
    h = config["image_size"]
    c_in, c_out = config["in_channels"], config["first_channels"]
    f, pool = config["filter"], config["pool"]
    for i in range(config["conv_layers"]):
        yield (f"conv{i}", (batch, h, h, c_in), (f, f, c_in, c_out),
               (batch, h // pool, h // pool, c_out))
        h //= pool
        c_in, c_out = c_out, c_out * 2
    flat = h * h * c_in
    yield "fc1", (batch, flat), (flat, config["fc_hidden"]), (
        batch, config["fc_hidden"])
    yield "fc2", (batch, config["fc_hidden"]), (
        config["fc_hidden"], config["classes"]), (batch, config["classes"])


def passes(config: dict, traffic: dict, batch: int,
           act_bytes: int = 2) -> list[Pass]:
    """Every pass of one training step on ``batch`` images, with operands
    and results of ``act_bytes`` bytes per element."""
    out = []
    for name, x, w, y in stage_shapes(config, batch):
        if name.startswith("conv"):
            b, h, wd, _ = x
            flops = 2.0 * b * h * wd * math.prod(w)  # stride 1, same padding
            kinds = (("fwd", (x, w, y)), ("dgrad", (y, w, x)),
                     ("wgrad", (x, y, w)))
        else:
            flops = 2.0 * x[0] * math.prod(w)
            kinds = (("fwd", (x, w, y)), ("dx", (y, w, x)), ("dw", (x, y, w)))
        for kind, operands in kinds:
            if name == "conv0" and kind == "dgrad":
                continue
            nbytes = act_bytes * sum(math.prod(s) for s in operands)
            out.append(Pass(name, kind, flops, float(nbytes)))
    return out


def step_flops(config: dict, traffic: dict, batch: int) -> float:
    """Model operations of one training step (forward, input and weight
    gradients, no recompute)."""
    return sum(p.flops for p in passes(config, traffic, batch))


def param_count(config: dict) -> int:
    """Weights and biases of every stage."""
    return sum(math.prod(w) + w[-1] for _, _, w, _ in stage_shapes(config, 1))


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


def logits(params, images, config: dict, q=lambda a: a):
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


def loss_fn(params, batch: dict, config: dict, q):
    x = logits(params, batch["images"], config, q)
    labels = batch["labels"]
    lse = jax.nn.logsumexp(x, -1)
    return jnp.mean(lse - jnp.take_along_axis(x, labels[:, None], -1)[:, 0])
