"""The LFM2 hybrid family (Hugging Face ``lfm2_moe``): gated short-
convolution and GQA attention blocks, dense SwiGLU FFNs in the leading
layers and routed experts after them, a tied head, trained on next-token
batches.

The configuration file gives the sizes under the Hugging Face names
(``hidden_size``, ``intermediate_size``, ``moe_intermediate_size``,
``num_hidden_layers``, ``layer_types``, ``num_dense_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``vocab_size``,
``norm_eps``, ``rope_theta``, ``conv_L_cache``, ``num_experts``,
``num_experts_per_tok``), with ``num_experts`` the experts held here and
``published.num_experts`` the router's width; ``held_experts.first`` is
the first held expert.

The reference, written from the ``lfm2_moe`` modelling code:

* pre-norm layers, ``h = x + mixer(norm(x))``, ``out = h + ffn(norm(h))``,
  a final norm, the head tied to the embedding;
* short conv: ``B, C, x~ = split3(x @ in_proj)``, ``y = C * conv(B * x~)``
  with ``conv`` causal and depthwise over ``conv_L_cache`` taps (``out[t] =
  sum_j taps[j] * u[t - L + 1 + j]``, no bias), then ``y @ out_proj``;
* attention: q, k, v projections, RMSNorm over each head's q and k, RoPE,
  causal softmax attention (``families/transformer.py``'s pieces), computed
  in query blocks so that it fits at 8k beside the reference's state;
* router: ``s = sigmoid(x @ W)`` over all ``published.num_experts``, the
  top ``num_experts_per_tok`` of ``s + expert_bias`` (the bias held at zero,
  ``assumed``), gates the chosen ``s`` normalised to sum 1; each held
  expert computed densely over every token and weighted by its gate (zero
  where the token did not choose it): no sorting, no capacity.  What the
  experts not held would add is left out, as the program leaves it out.

Departures, each the program's, so that the two compare leaf by leaf:
norm gains kept as offsets from one (``g = 1 + w``, ``w`` zero at init);
each layer's leaves under ``layers/<i>/`` (``conv`` with ``in_proj``,
``taps``, ``out_proj``; ``attn`` with ``wq`` ``[hidden, heads, head_dim]``,
``wk``, ``wv``, ``wo`` ``[heads, head_dim, hidden]``, ``q_norm``,
``k_norm``; ``ffn`` or ``moe`` with ``router``, ``w_gate``, ``w_up``,
``w_down``); every matrix ``normal / sqrt(fan_in)`` with the program's
fan-in (the second-last axis, the number of heads for ``wo``, the taps
for the conv), the embedding ``normal / sqrt(hidden)``, keys folded from
the CRC-32 of each leaf's ``/``-joined path.

``passes`` counts each GEMM's forward, input and weight gradients, the
attention's forward and backward (half the score matrix), and the held
experts' GEMMs at the mean rows ``tokens * top_k * held / num_experts``
(padding not credited).
"""

from __future__ import annotations

import functools
import math
import re
import zlib

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.work import Pass
from families.transformer import make_batch, rms_norm, rope, swiglu, tokens

HIGHEST = lax.Precision.HIGHEST
BLOCK_ROWS = 1  # sequences of one block of the reference's gradient
ATTN_BLOCK = 512  # query rows of one block of the reference's attention
STAGE_PATTERN = re.compile(
    r"(?:^|[/(])((?:shortconv|attn|route|experts|ffn)\d+|logits)(?:[/)]|$)")

__all__ = ["make_batch", "tokens"]


def _held(config: dict) -> tuple[int, int, int]:
    """(first held expert, experts held, experts routed over)."""
    return (config["held_experts"]["first"], config["num_experts"],
            config["published"]["num_experts"])


def check_program(config: dict) -> None:
    """Refuse to run when the program's registry entry for the arch is
    not the configuration the file states."""
    from repro.launch import train as launcher

    cfg = launcher.get_config(config["arch"])
    if cfg.family != "lfm2":
        raise SystemExit(f"the program's {config['arch']} is of the family "
                         f"{cfg.family!r}, not lfm2")
    first, count = cfg.held
    have = {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
            "intermediate_size": cfg.d_ff,
            "moe_intermediate_size": cfg.d_ff_expert,
            "layer_types": list(cfg.layer_types),
            "num_dense_layers": cfg.n_dense_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.resolved_head_dim, "vocab_size": cfg.vocab,
            "norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "conv_L_cache": cfg.conv_width, "num_experts": count,
            "num_experts_per_tok": cfg.moe_top_k,
            "held_first": first, "routed_over": cfg.n_experts,
            "tied": cfg.tie_embeddings}
    want = dict(config, head_dim=config["hidden_size"]
                // config["num_attention_heads"],
                held_first=config["held_experts"]["first"],
                routed_over=config["published"]["num_experts"],
                tied=True)
    wrong = {k: (v, want[k]) for k, v in have.items() if v != want[k]}
    if wrong:
        raise SystemExit(f"the program's {config['arch']} is not the "
                         f"configuration's (program, file): {wrong}")


def launcher_args(config: dict, traffic: dict) -> list:
    return ["--seq", str(traffic["seq"]), "--remat", config["remat"]]


def _kinds(config: dict):
    """(index, mixer kind, dense FFN) of every layer."""
    for i, kind in enumerate(config["layer_types"]):
        yield i, kind, i < config["num_dense_layers"]


def stages(config: dict) -> list:
    out = []
    for i, kind, dense in _kinds(config):
        out.append((f"shortconv{i}", "shortconv") if kind == "conv"
                   else (f"attn{i}", "attn"))
        out.append((f"ffn{i}", "ffn") if dense else (f"experts{i}", "experts"))
    return out + [("logits", "logits")]


def _defs(config: dict) -> dict:
    """Every leaf's shape and the fan-in of its initial scale (0 for
    zeros; the embedding's is the hidden size)."""
    d, ff = config["hidden_size"], config["intermediate_size"]
    fe = config["moe_intermediate_size"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    dh = d // hq
    _, count, routed = _held(config)
    layers = {}
    for i, kind, dense in _kinds(config):
        lp = {"mixer_norm": ((d,), 0), "ffn_norm": ((d,), 0)}
        if kind == "conv":
            lp["conv"] = {"in_proj": ((d, 3 * d), d),
                          "taps": ((config["conv_L_cache"], d),
                                   config["conv_L_cache"]),
                          "out_proj": ((d, d), d)}
        else:
            lp["attn"] = {"wq": ((d, hq, dh), d), "wk": ((d, hkv, dh), d),
                          "wv": ((d, hkv, dh), d), "wo": ((hq, dh, d), hq),
                          "q_norm": ((dh,), 0), "k_norm": ((dh,), 0)}
        if dense:
            lp["ffn"] = {"w_gate": ((d, ff), d), "w_up": ((d, ff), d),
                         "w_down": ((ff, d), ff)}
        else:
            lp["moe"] = {"router": ((d, routed), d),
                         "w_gate": ((count, d, fe), d),
                         "w_up": ((count, d, fe), d),
                         "w_down": ((count, fe, d), fe)}
        layers[str(i)] = lp
    return {"embed": ((config["vocab_size"], d), d), "final_norm": ((d,), 0),
            "layers": layers}


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


def _dot(a, w, q):
    return jnp.dot(q(a), q(w), precision=HIGHEST)


def short_conv(p: dict, x, config: dict, q=lambda a: a):
    """The gated short convolution of ``x`` [batch, seq, hidden]."""
    b, c, xt = jnp.split(_dot(x, p["in_proj"], q), 3, axis=-1)
    u = q(b * xt)
    taps = q(p["taps"])
    n, seq = taps.shape[0], x.shape[1]
    up = jnp.pad(u, ((0, 0), (n - 1, 0), (0, 0)))
    conv = sum(taps[j] * up[:, j:j + seq] for j in range(n))
    return _dot(c * conv, p["out_proj"], q)


def _attend(qb, kh, vh, lo, q):
    """One block of queries (rows ``lo`` on) against every key, causal."""
    s = jnp.einsum("bqhk,bshk->bhqs", q(qb), q(kh), precision=HIGHEST)
    s = s / math.sqrt(qb.shape[-1])
    rows = lo + jnp.arange(qb.shape[1])[:, None]
    s = jnp.where(jnp.arange(kh.shape[1])[None, :] <= rows, s, -jnp.inf)
    return jnp.einsum("bhqs,bshk->bqhk", q(jax.nn.softmax(s, -1)), q(vh),
                      precision=HIGHEST)


def attention(p: dict, x, config: dict, q=lambda a: a):
    """Causal GQA self-attention of ``x`` [batch, seq, hidden], a block of
    ``ATTN_BLOCK`` queries at a time, each block recomputed in the
    backward pass."""
    eps = config["norm_eps"]
    proj = lambda w: jnp.einsum("bsd,dhk->bshk", q(x), q(w), precision=HIGHEST)
    qh, kh, vh = proj(p["wq"]), proj(p["wk"]), proj(p["wv"])
    qh, kh = rms_norm(qh, p["q_norm"], eps), rms_norm(kh, p["k_norm"], eps)
    qh, kh = rope(qh, config["rope_theta"]), rope(kh, config["rope_theta"])
    group = qh.shape[2] // kh.shape[2]
    kh, vh = jnp.repeat(kh, group, axis=2), jnp.repeat(vh, group, axis=2)
    seq = x.shape[1]
    block = min(ATTN_BLOCK, seq)
    blocks = qh.reshape(qh.shape[0], seq // block, block, *qh.shape[2:])
    attend = jax.checkpoint(functools.partial(_attend, q=q))
    o = lax.map(lambda a: attend(a[0], kh, vh, a[1]),
                (blocks.swapaxes(0, 1), jnp.arange(seq // block) * block))
    o = o.swapaxes(0, 1).reshape(qh.shape)
    return jnp.einsum("bqhk,hkd->bqd", q(o), q(p["wo"]), precision=HIGHEST)


def routed_experts(p: dict, x, config: dict, q=lambda a: a):
    """The held experts' part of the routed FFN of ``x`` [batch, seq,
    hidden]: each held expert over every token, weighted by its gate."""
    first, count, _ = _held(config)
    scores = jax.nn.sigmoid(_dot(x, p["router"], q))
    _, idx = lax.top_k(scores, config["num_experts_per_tok"])
    gates = jnp.take_along_axis(scores, idx, -1)
    gates = gates / gates.sum(-1, keepdims=True)

    def expert(y, e):
        w = jnp.sum(jnp.where(idx == first + e, gates, 0.0), -1)
        ffn = {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}
        return y + w[..., None] * swiglu(ffn, x, "silu", q), None

    y, _ = lax.scan(jax.checkpoint(expert), jnp.zeros_like(x),
                    jnp.arange(count))
    return y


def _layer(x, lp, kind: str, dense: bool, config: dict, q):
    eps = config["norm_eps"]
    mixer = short_conv if kind == "conv" else attention
    x = x + mixer(lp["conv" if kind == "conv" else "attn"],
                  rms_norm(x, lp["mixer_norm"], eps), config, q)
    h = rms_norm(x, lp["ffn_norm"], eps)
    if dense:
        return x + swiglu(lp["ffn"], h, "silu", q)
    return x + routed_experts(lp["moe"], h, config, q)


def logits(params, tokens, config: dict, q=lambda a: a):
    """``tokens`` [batch, seq] -> logits [batch, seq, vocab]; each layer is
    recomputed in the backward pass."""
    x = params["embed"][tokens]
    for i, kind, dense in _kinds(config):
        layer = functools.partial(_layer, kind=kind, dense=dense,
                                  config=config, q=q)
        x = jax.checkpoint(layer)(x, params["layers"][str(i)])
    x = rms_norm(x, params["final_norm"], config["norm_eps"])
    return _dot(x, params["embed"].T, q)


def loss_fn(params, batch: dict, config: dict, q):
    x = logits(params, batch["tokens"], config, q)
    labels = batch["labels"]
    lse = jax.nn.logsumexp(x, -1)
    return jnp.mean(lse - jnp.take_along_axis(x, labels[..., None], -1)[..., 0])


def passes(config: dict, traffic: dict, batch: int,
           act_bytes: int = 2) -> list[Pass]:
    """Every pass of one training step on ``batch`` sequences (see the
    module's docstring).  The embedding's gather, the norms, the conv's
    taps and gates, routing, the loss and the optimizer are not counted."""
    d, ff = config["hidden_size"], config["intermediate_size"]
    fe = config["moe_intermediate_size"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    dh = d // hq
    _, count, routed = _held(config)
    seq = int(traffic["seq"])
    m = batch * seq
    rows = m * config["num_experts_per_tok"] * count / routed
    gemms = []  # (stage, rows, k, n)
    for i, kind, dense in _kinds(config):
        if kind == "conv":
            gemms += [(f"shortconv{i}", m, d, 3 * d),
                      (f"shortconv{i}", m, d, d)]
        else:
            gemms += [(f"attn{i}", m, d, (hq + 2 * hkv) * dh),
                      (f"attn{i}", m, hq * dh, d)]
        if dense:
            gemms += [(f"ffn{i}", m, d, 2 * ff), (f"ffn{i}", m, ff, d)]
        else:
            gemms += [(f"experts{i}", rows, d, 2 * fe),
                      (f"experts{i}", rows, fe, d)]
    gemms.append(("logits", m, d, config["vocab_size"]))
    out = []
    for stage, r, k, n in gemms:
        nbytes = act_bytes * float(r * k + k * n + r * n)
        for kind in ("fwd", "dx", "dw"):
            out.append(Pass(stage, kind, 2.0 * r * k * n, nbytes))
    pairs = seq * (seq + 1) / 2
    fwd = 2 * 2.0 * batch * hq * dh * pairs
    q_size, kv_size = m * hq * dh, m * hkv * dh
    for i, kind, _ in _kinds(config):
        if kind != "conv":
            out.append(Pass(f"attn{i}", "fwd", fwd,
                            act_bytes * float(2 * q_size + 2 * kv_size)))
            out.append(Pass(f"attn{i}", "bwd", 2 * fwd,
                            act_bytes * float(4 * q_size + 4 * kv_size)))
    return out


def step_flops(config: dict, traffic: dict, batch: int) -> float:
    return sum(p.flops for p in passes(config, traffic, batch))
