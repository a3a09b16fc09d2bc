"""LFM2 hybrid decoder (lfm2-8b-a1b): gated short-convolution blocks and
GQA attention blocks, a dense SwiGLU FFN in the leading layers, routed
experts in the rest.

Every layer is pre-norm, ``h = x + mixer(norm(x))``, ``out = h +
ffn(norm(h))``, with the mixer and the FFN of each layer fixed by
``cfg.layer_types`` and ``cfg.n_dense_layers``; a final norm comes before
a head tied to the embedding.

* Short conv (``layer_types`` "conv"): ``B, C, x~ = split3(in_proj(x))``;
  ``y = C * conv(B * x~)``, a causal depthwise conv of ``cfg.conv_width``
  taps with no bias; ``out_proj(y)``.
* Attention ("full_attention"): q, k, v projections, an RMSNorm on each
  head's q and k, RoPE, causal softmax attention, ``wo``.
* Routed experts: :func:`repro.models.moe.held_expert_ffn`, the experts
  ``cfg.held`` of ``cfg.n_experts``, sigmoid routing, no capacity.

The layers differ in kind, so they run as a Python loop over
``layer_types`` (the HLO holds every layer; a 5-layer share compiles in
about a minute).  There is one path, the planned one: every GEMM runs
through ``fc_layer`` with planned dX/dW (``in_proj``/``out_proj``, the
fused q/k/v and ``wo``, the dense FFN, the head at the chunked-CE chunk),
the attention through the planned flash kernel with the exact backward in
query blocks (``transformer._attn_vjp``), the experts through the planned
grouped matmul.  The conv's taps and the gates are elementwise XLA under
the block's scope.  Scopes: ``shortconv<i>``, ``attn<i>``, ``ffn<i>``,
``route<i>``, ``experts<i>``, ``logits``.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.fc_layer import fc_layer
from repro.models import layers as ll
from repro.models import moe
from repro.models.module import ParamDef
from repro.models.transformer import _attn_vjp, _bwd_for, _chunk_m
from repro.plan import local_schedule


def param_defs(cfg: ModelConfig) -> dict:
    d, ff = cfg.d_model, cfg.d_ff
    fe = cfg.d_ff_expert or ff
    _, count = cfg.held
    layers = {}
    for i, kind in enumerate(cfg.layer_types):
        lp = {"mixer_norm": ParamDef((d,), init="zeros"),
              "ffn_norm": ParamDef((d,), init="zeros")}
        if kind == "conv":
            lp["conv"] = {"in_proj": ParamDef((d, 3 * d)),
                          "taps": ParamDef((cfg.conv_width, d)),
                          "out_proj": ParamDef((d, d))}
        else:
            lp["attn"] = ll.attn_defs(cfg, 1, layers_prefix=False)
        if i < cfg.n_dense_layers:
            lp["ffn"] = {"w_gate": ParamDef((d, ff)), "w_up": ParamDef((d, ff)),
                         "w_down": ParamDef((ff, d))}
        else:
            lp["moe"] = {"router": ParamDef((d, cfg.n_experts)),
                         "w_gate": ParamDef((count, d, fe)),
                         "w_up": ParamDef((count, d, fe)),
                         "w_down": ParamDef((count, fe, d))}
        layers[str(i)] = lp
    defs = {**ll.embed_defs(cfg), "layers": layers}
    # Unit rows would put the tied head's logits at sqrt(d) a unit; the
    # embedding starts at the 1/sqrt(d) scale of every other matrix.
    defs["embed"] = ParamDef((cfg.vocab, d), defs["embed"].spec,
                             scale=d ** -0.5)
    return defs


def causal_conv(u, taps):
    """``u`` [B, S, d] through a causal depthwise conv: ``out[t] =
    sum_j taps[j] * u[t - K + 1 + j]`` (zeros before the start), in f32."""
    K = taps.shape[0]
    S = u.shape[1]
    up = jnp.pad(u.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    taps = taps.astype(jnp.float32)
    return sum(taps[j] * up[:, j:j + S] for j in range(K)).astype(u.dtype)


def _gemm(x, w, sched: dict, cell: str):
    return fc_layer(x, w, sched.get(cell), _bwd_for(sched, cell))


def _shortconv(p, h, cfg, sched, cd):
    B, S, d = h.shape
    bcx = _gemm(h.reshape(B * S, d), p["in_proj"].astype(cd), sched, "in_proj")
    b, c, xt = jnp.split(bcx.reshape(B, S, 3 * d), 3, axis=-1)
    y = c * causal_conv(b * xt, p["taps"])
    return _gemm(y.reshape(B * S, d), p["out_proj"].astype(cd), sched,
                 "out_proj").reshape(B, S, d)


def _attention(p, h, cfg, sched, cd):
    B, S, d = h.shape
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    w_qkv = jnp.concatenate(
        [p["wq"].reshape(d, Hq * Dh), p["wk"].reshape(d, Hkv * Dh),
         p["wv"].reshape(d, Hkv * Dh)], axis=1).astype(cd)
    qkv = _gemm(h.reshape(B * S, d), w_qkv, sched, "qkv")
    q, k, v = jnp.split(qkv, [Hq * Dh, (Hq + Hkv) * Dh], axis=-1)
    pos = jnp.arange(S, dtype=jnp.int32)
    q = ll.rms_norm(q.reshape(B, S, Hq, Dh), p["q_norm"], cfg.norm_eps)
    k = ll.rms_norm(k.reshape(B, S, Hkv, Dh), p["k_norm"], cfg.norm_eps)
    q = ll.rope(q, pos, cfg.rope_theta).transpose(0, 2, 1, 3)
    k = ll.rope(k, pos, cfg.rope_theta).transpose(0, 2, 1, 3)
    v = v.reshape(B, S, Hkv, Dh).transpose(0, 2, 1, 3)
    o = _attn_vjp(q, k, v, True, None, local_schedule(sched.get("attn")))
    o = o.transpose(0, 2, 1, 3).reshape(B * S, Hq * Dh)
    return _gemm(o, p["wo"].reshape(Hq * Dh, d).astype(cd), sched,
                 "wo").reshape(B, S, d)


def _dense_ffn(p, h, cfg, sched, cd):
    B, S, d = h.shape
    w_gu = jnp.concatenate([p["w_gate"], p["w_up"]], axis=1).astype(cd)
    g, u = jnp.split(_gemm(h.reshape(B * S, d), w_gu, sched, "ffn_up"), 2,
                     axis=-1)
    return _gemm(ll._ACT[cfg.act](g) * u, p["w_down"].astype(cd), sched,
                 "ffn_down").reshape(B, S, d)


def _layer(cfg: ModelConfig, i: int, sched: dict, cd):
    """Layer ``i`` as ``(x, params) -> (x, counters)``."""
    kind = cfg.layer_types[i]

    def body(x, lp):
        mixer = (f"shortconv{i}", _shortconv) if kind == "conv" else (
            f"attn{i}", _attention)
        with jax.named_scope(mixer[0]):
            h = ll.rms_norm(x, lp["mixer_norm"], cfg.norm_eps)
            x = x + mixer[1](lp["conv" if kind == "conv" else "attn"], h, cfg,
                             sched, cd)
        counters = {}
        if "ffn" in lp:
            with jax.named_scope(f"ffn{i}"):
                h = ll.rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
                x = x + _dense_ffn(lp["ffn"], h, cfg, sched, cd)
        else:
            B, S, d = x.shape
            with jax.named_scope(f"route{i}"):
                h = ll.rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
            y, counters = moe.held_expert_ffn(h.reshape(B * S, d), lp["moe"],
                                              cfg, sched, i)
            x = x + y.reshape(B, S, d)
        return x, counters

    return body


def forward(cfg: ModelConfig, params: dict, tokens: jax.Array, *,
            compute_dtype=jnp.bfloat16, remat: str = "none",
            schedules: dict | None = None, cache=None, **_):
    """Returns (hidden [B, S, d], counters): counters per expert layer,
    ``{"experts<i>": {"rows": [count], "pad": ()}}``.  ``schedules`` from
    :func:`plan_training` (planned here when None).  ``remat="block"``
    recomputes each layer in the backward pass."""
    if cache is not None:
        raise ValueError("the lfm2 family trains only: it has no decode cache")
    cd = jnp.dtype(compute_dtype)
    B, S = tokens.shape
    sched = schedules or plan_training(cfg, B, S, in_bytes=cd.itemsize)
    x = ll.embed_tokens(params, tokens, cfg, cd)
    counters = {}
    for i in range(cfg.n_layers):
        body = _layer(cfg, i, sched, cd)
        if remat != "none":
            body = jax.checkpoint(body)
        x, c = body(x, params["layers"][str(i)])
        if c:
            counters[f"experts{i}"] = c
    return x, counters


def logits(cfg: ModelConfig, params: dict, hidden: jax.Array, *,
           schedules: dict | None = None) -> jax.Array:
    """Hidden -> [B, S, vocab] through the final norm and the tied head,
    the planned ``fc_layer`` GEMM: at the chunked-CE chunk M of
    ``schedules`` (:func:`plan_training`), else planned at the hidden's
    rows."""
    B, S, d = hidden.shape
    sched = schedules or plan_training(cfg, B, S,
                                       in_bytes=hidden.dtype.itemsize)
    with jax.named_scope("logits"):
        x = ll.rms_norm(hidden, params["final_norm"], cfg.norm_eps)
        out = _gemm(x.reshape(B * S, d), params["embed"].T.astype(x.dtype),
                    sched, "logits")
    return out.reshape(B, S, -1)


def gemm_shapes(cfg: ModelConfig, batch: int, seq: int,
                loss_chunks: int = 1) -> dict:
    """``{cell: (m, k, n)}`` of every fc_layer GEMM kind of the step."""
    d, ff = cfg.d_model, cfg.d_ff
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    m = batch * seq
    kinds = set(cfg.layer_types)
    out = {"logits": (_chunk_m(batch, seq, loss_chunks), d, cfg.vocab)}
    if "conv" in kinds:
        out.update(in_proj=(m, d, 3 * d), out_proj=(m, d, d))
    if "full_attention" in kinds:
        out.update(qkv=(m, d, (Hq + 2 * Hkv) * Dh), wo=(m, Hq * Dh, d))
    if cfg.n_dense_layers:
        out.update(ffn_up=(m, d, 2 * ff), ffn_down=(m, ff, d))
    return out


def plan_training(cfg: ModelConfig, batch: int, seq: int, *,
                  loss_chunks: int = 1, in_bytes: int = 4, machine=None,
                  mesh=None, shard_axis: str = "data",
                  autotune=None) -> dict:
    """Every kernel launch of the training step, forward and backward:
    ``<cell>``, ``<cell>.dx``, ``<cell>.dw`` for each GEMM of
    :func:`gemm_shapes` (the fused dX/dW kernel where it fits), ``attn``
    for the flash kernel, and ``experts_up``/``experts_down``, each a
    ``{"fwd", "dx", "dw"}`` set of the grouped matmul planned for the mean
    routed rows of a held expert, ``tokens * top_k / n_experts``, the
    down GEMM on the up GEMM's row tile."""
    from repro.core import fc_layer as fl
    from repro.core.machine import TPU_V5E
    from repro.kernels.matmul import grouped
    from repro.plan import autotune as at

    if mesh is not None:
        raise ValueError("the lfm2 family plans one chip's share; it has no "
                         "sharded plan")
    machine = machine or TPU_V5E
    out = {}
    for name, (m, k, n) in gemm_shapes(cfg, batch, seq, loss_chunks).items():
        out[name] = at.resolve("matmul", dict(m=m, n=n, k=k, in_bytes=in_bytes),
                               machine=machine, policy=autotune)
        for kk, s in fl.plan_bwd((m, k), (k, n), in_bytes=in_bytes,
                                 machine=machine, autotune=autotune).items():
            out[f"{name}.{kk}"] = s
    if "full_attention" in cfg.layer_types:
        out["attn"] = at.resolve(
            "flash_attention",
            dict(seq_q=seq, seq_kv=seq, head_dim=cfg.resolved_head_dim,
                 n_q_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                 batch=batch, in_bytes=in_bytes, causal=True),
            machine=machine, policy=autotune)
    if cfg.n_layers > cfg.n_dense_layers:
        d, fe = cfg.d_model, cfg.d_ff_expert or cfg.d_ff
        _, count = cfg.held
        assignments = batch * seq * cfg.moe_top_k
        rows = max(1, assignments // cfg.n_experts)
        up = grouped.plan(assignments, d, 2 * fe, count, group_rows=rows,
                          in_bytes=in_bytes)
        tile = up["fwd"].block("block_m")
        m = moe.expert_buffer_rows(assignments, count, tile)
        out["experts_up"] = grouped.plan(m, d, 2 * fe, count, group_rows=rows,
                                         in_bytes=in_bytes)
        out["experts_down"] = grouped.plan(m, fe, d, count, group_rows=rows,
                                           in_bytes=in_bytes, block_m=tile)
    return out


def make_loss_fn(cfg: ModelConfig, tcfg, parallel=None):
    """The training loss and its counters, ``(loss, counters)``: the
    planned forward at the batch's static shape, then the chunked
    cross-entropy through the planned head.  The family has one path, so
    ``tcfg.planned_kernels`` is not consulted."""
    from repro.runtime.train import chunked_ce

    del parallel  # one chip's share: no exchange
    dt = jnp.dtype(tcfg.compute_dtype)
    fam = sys.modules[__name__]

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        B, S = tokens.shape
        sched = plan_training(cfg, B, S, loss_chunks=tcfg.loss_chunks,
                              in_bytes=dt.itemsize)
        h, counters = forward(cfg, params, tokens, compute_dtype=dt,
                              remat=tcfg.remat, schedules=sched)
        loss = chunked_ce(cfg, fam, params, h, batch["labels"],
                          tcfg.loss_chunks, schedules=sched)
        return loss, counters

    return loss_fn
