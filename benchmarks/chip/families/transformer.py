"""The dense decoder family: token embedding, pre-norm blocks of GQA
attention and a SwiGLU MLP, a final norm and a tied or untied head,
trained on next-token batches.

The configuration file gives the sizes under the Hugging Face names
(``hidden_size``, ``intermediate_size``, ``num_hidden_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``vocab_size``, ``rms_norm_eps``, ``rope_theta``, ``tie_word_embeddings``,
``attention_bias``, ``hidden_act``), and two switches of the block:
``qk_norm`` (an RMSNorm over each head's queries and keys, before RoPE)
and ``scale_embed`` (the embedding times ``sqrt(hidden_size)``).

The reference is written from the published equations, each piece a
function of its own so that a family with other blocks can import it
(``rms_norm``, ``rope``, ``attention``, ``swiglu``):

* RMSNorm ``x / sqrt(mean(x^2) + eps) * g``;
* RoPE on the two halves of each head (``rotate_half``), frequency
  ``theta^(-2i / head_dim)`` at position ``i`` of the half;
* causal softmax attention with ``1 / sqrt(head_dim)`` scaling, query
  head ``h`` reading key/value head ``h // (heads / kv_heads)``;
* ``down(silu(x @ gate) * (x @ up))``;
* mean cross-entropy of every next token (every label is a token: the
  batches mask none).

Departures from the published description, each the program's, so that
the two compare leaf by leaf:

* the norms keep their gain as an offset from one, ``g = 1 + w``, with
  ``w`` initialised to zero;
* the layers' weights are stacked on a leading layer axis
  (``layers/attn/wq`` is ``[layers, hidden, heads, head_dim]``), so weight
  decay, which the step applies to every leaf of two or more dimensions,
  reaches the stacked norm gains too;
* initialisation: every matrix ``normal / sqrt(fan_in)`` with the fan-in
  the program's layer library states (the hidden size for the q/k/v,
  gate and up projections and for an untied head, the number of query
  heads for ``wo``, the intermediate size for ``down``), the embedding
  ``normal(0, 1)``, biases and norm offsets zero; each leaf's key folded
  from the CRC-32 of its ``/``-joined path;
* no sliding window and one RoPE base for every layer: a configuration
  with either is refused (``check_program``), since the planned step
  supports neither.

The program runs this family without per-stage scopes, so ``stages`` is
empty and no stage's device time or roofline is read; ``passes`` still
counts every GEMM and the attention of the step for ``step_mfu``.
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
BLOCK_ROWS = 64  # sequences of one block of the reference's gradient
STAGE_PATTERN = re.compile(r"(?!)")  # the program scopes no stage yet
_ACT = {"silu": jax.nn.silu}


def head_dim(config: dict) -> int:
    return config.get("head_dim") or (config["hidden_size"]
                                      // config["num_attention_heads"])


def check_program(config: dict) -> None:
    """Refuse to run when the program's registry entry for the arch is
    not the configuration the file states, or is not of this family."""
    from repro.launch import train as launcher

    cfg = launcher.get_config(config["arch"])
    if cfg.family not in ("dense", "transformer"):
        raise SystemExit(f"the program's {config['arch']} is of the family "
                         f"{cfg.family!r}, not a dense decoder")
    have = {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
            "intermediate_size": cfg.d_ff,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.resolved_head_dim, "vocab_size": cfg.vocab,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "tie_word_embeddings": cfg.tie_embeddings,
            "attention_bias": cfg.qkv_bias, "qk_norm": cfg.qk_norm,
            "scale_embed": cfg.scale_embed, "hidden_act": cfg.act,
            "sliding_window": cfg.local_window,
            "global_every": cfg.global_every or None}
    want = dict(config, head_dim=head_dim(config), sliding_window=None,
                global_every=None)
    wrong = {k: (v, want[k]) for k, v in have.items() if v != want[k]}
    if wrong:
        raise SystemExit(f"the program's {config['arch']} is not the "
                         f"configuration's (program, file): {wrong}")


def launcher_args(config: dict, traffic: dict) -> list:
    return ["--seq", str(traffic["seq"])]


def make_batch(seed: int, index: int, config: dict, traffic: dict) -> dict:
    """One batch: ``tokens`` and ``labels`` int32 [batch, seq], the labels
    the next tokens, drawn from ``(seed, index)`` alone.

    The arithmetic is that of the program's synthetic token source
    (Zipfian unigrams over at most 50,000 ids, each token mixed with half
    the one before it), kept here so that the benchmark owns its
    inputs."""
    vocab = config["vocab_size"]
    rng = np.random.default_rng(np.random.SeedSequence([seed, index]))
    probs = 1.0 / np.arange(1, min(vocab, 50000) + 1) ** 1.1
    base = rng.choice(len(probs), p=probs / probs.sum(),
                      size=(int(traffic["batch"]), int(traffic["seq"]) + 1))
    mixed = (base + np.roll(base, 1, axis=1) // 2) % vocab
    return {"tokens": mixed[:, :-1].astype(np.int32),
            "labels": mixed[:, 1:].astype(np.int32)}


def tokens(batch: dict) -> int:
    return int(batch["labels"].size)


def stages(config: dict) -> list:
    return []


def _defs(config: dict) -> dict:
    """Every leaf's shape and the fan-in of its initial scale (0 for
    zeros, 1 for the embedding's unit normal), as the program lays the
    tree out."""
    n, d, ff = (config["num_hidden_layers"], config["hidden_size"],
                config["intermediate_size"])
    hq, hkv, dh = (config["num_attention_heads"],
                   config["num_key_value_heads"], head_dim(config))
    v = config["vocab_size"]
    attn = {"wq": ((n, d, hq, dh), d), "wk": ((n, d, hkv, dh), d),
            "wv": ((n, d, hkv, dh), d), "wo": ((n, hq, dh, d), hq)}
    if config["attention_bias"]:
        attn.update(bq=((n, hq, dh), 0), bk=((n, hkv, dh), 0),
                    bv=((n, hkv, dh), 0))
    if config["qk_norm"]:
        attn.update(q_norm=((n, dh), 0), k_norm=((n, dh), 0))
    defs = {"embed": ((v, d), 1), "final_norm": ((d,), 0),
            "layers": {"ln1": ((n, d), 0), "ln2": ((n, d), 0), "attn": attn,
                       "mlp": {"w_gate": ((n, d, ff), d),
                               "w_up": ((n, d, ff), d),
                               "w_down": ((n, ff, d), ff)}}}
    if not config["tie_word_embeddings"]:
        defs["w_out"] = ((d, v), d)
    return defs


def _is_def(x) -> bool:
    return isinstance(x, tuple) and isinstance(x[0], tuple)


def init_params(config: dict, seed: int) -> dict:
    key = jax.random.PRNGKey(seed)

    def leaf(path, spec):
        shape, fan_in = spec
        if not fan_in:
            return jnp.zeros(shape, jnp.float32)
        name = jax.tree_util.keystr(path, simple=True, separator="/")
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        return jax.random.normal(k, shape, jnp.float32) * (
            1.0 / math.sqrt(fan_in))

    return jax.tree_util.tree_map_with_path(leaf, _defs(config),
                                            is_leaf=_is_def)


def param_count(config: dict) -> int:
    return sum(math.prod(shape) for shape, _ in
               jax.tree.leaves(_defs(config), is_leaf=_is_def))


def rms_norm(x, w, eps: float):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def rope(x, theta: float):
    """``x`` [batch, seq, heads, head_dim] rotated by its position."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p: dict, x, config: dict, q=lambda a: a):
    """Causal GQA self-attention of ``x`` [batch, seq, hidden]."""
    eps = config["rms_norm_eps"]
    proj = lambda w: jnp.einsum("bsd,dhk->bshk", q(x), q(w), precision=HIGHEST)
    qh, kh, vh = proj(p["wq"]), proj(p["wk"]), proj(p["wv"])
    if config["attention_bias"]:
        qh, kh, vh = qh + p["bq"], kh + p["bk"], vh + p["bv"]
    if config["qk_norm"]:
        qh, kh = rms_norm(qh, p["q_norm"], eps), rms_norm(kh, p["k_norm"], eps)
    qh, kh = rope(qh, config["rope_theta"]), rope(kh, config["rope_theta"])
    group = qh.shape[2] // kh.shape[2]
    kh, vh = jnp.repeat(kh, group, axis=2), jnp.repeat(vh, group, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", q(qh), q(kh), precision=HIGHEST)
    s = s / math.sqrt(qh.shape[-1])
    n = x.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", q(jax.nn.softmax(s, -1)), q(vh),
                   precision=HIGHEST)
    return jnp.einsum("bqhk,hkd->bqd", q(o), q(p["wo"]), precision=HIGHEST)


def swiglu(p: dict, x, act: str = "silu", q=lambda a: a):
    dot = lambda a, w: jnp.dot(q(a), q(w), precision=HIGHEST)
    h = _ACT[act](dot(x, p["w_gate"])) * dot(x, p["w_up"])
    return dot(h, p["w_down"])


def logits(params, tokens, config: dict, q=lambda a: a):
    """``tokens`` [batch, seq] -> logits [batch, seq, vocab]."""
    eps = config["rms_norm_eps"]
    x = params["embed"][tokens]
    if config["scale_embed"]:
        x = x * math.sqrt(config["hidden_size"])

    def block(x, lp):
        x = x + attention(lp["attn"], rms_norm(x, lp["ln1"], eps), config, q)
        h = rms_norm(x, lp["ln2"], eps)
        return x + swiglu(lp["mlp"], h, config["hidden_act"], q), None

    x, _ = lax.scan(block, x, params["layers"])
    x = rms_norm(x, params["final_norm"], eps)
    head = (params["embed"].T if config["tie_word_embeddings"]
            else params["w_out"])
    return jnp.dot(q(x), q(head), precision=HIGHEST)


def loss_fn(params, batch: dict, config: dict, q):
    x = logits(params, batch["tokens"], config, q)
    labels = batch["labels"]
    lse = jax.nn.logsumexp(x, -1)
    return jnp.mean(lse - jnp.take_along_axis(x, labels[..., None], -1)[..., 0])


def passes(config: dict, traffic: dict, batch: int,
           act_bytes: int = 2) -> list[Pass]:
    """Every pass of one training step on ``batch`` sequences: each GEMM's
    forward, input and weight gradient (the layers' summed over the
    depth), and causal attention's forward and backward (half of the
    score matrix; the backward is twice the forward's two products).  The
    embedding's gather, the norms, RoPE, the loss and the optimizer are
    not counted."""
    n, d, ff = (config["num_hidden_layers"], config["hidden_size"],
                config["intermediate_size"])
    hq, hkv, dh = (config["num_attention_heads"],
                   config["num_key_value_heads"], head_dim(config))
    seq = int(traffic["seq"])
    m = batch * seq
    gemms = {"qkv": (n, d, (hq + 2 * hkv) * dh), "wo": (n, hq * dh, d),
             "mlp_up": (n, d, 2 * ff), "mlp_down": (n, ff, d),
             "logits": (1, d, config["vocab_size"])}
    out = []
    for name, (times, k, cols) in gemms.items():
        x, w, y = m * k, k * cols, m * cols
        for kind in ("fwd", "dx", "dw"):
            out.append(Pass(name, kind, times * 2.0 * m * k * cols,
                            times * act_bytes * float(x + w + y)))
    pairs = seq * (seq + 1) / 2
    fwd = 2 * 2.0 * batch * hq * dh * pairs
    q_size, kv_size = m * hq * dh, m * hkv * dh
    out.append(Pass("attn", "fwd", n * fwd,
                    n * act_bytes * float(2 * q_size + 2 * kv_size)))
    out.append(Pass("attn", "bwd", n * 2 * fwd,
                    n * act_bytes * float(4 * q_size + 4 * kv_size)))
    return out


def step_flops(config: dict, traffic: dict, batch: int) -> float:
    return sum(p.flops for p in passes(config, traffic, batch))
