"""Mixture-of-Experts transformer (grok-1, qwen3-moe), and the held-expert
FFN of the planned path (:func:`held_expert_ffn`, used by models/lfm2.py).

Two expert paths live here.  The XLA family below drops every assignment
past its capacity factor; :func:`held_expert_ffn` holds a chip's share of
the experts and drops none, through the planned grouped matmul.

Expert dispatch follows the paper's Alg 4 structure (DESIGN.md Sec. 4):
every TP device keeps a *private partial output* for the experts it owns
and the partials are summed by one reduction (psum over the `model` axis),
exactly like Manticore clusters reducing their private FC output volumes.

Concretely, inside ``shard_map`` over the mesh:
  * routing (softmax + top-k) is computed redundantly on every device from
    replicated router weights - no collective;
  * if E % tp == 0 (qwen3-moe): experts are sharded over `model` (EP);
    each device scatters only the tokens routed to *its* experts into an
    [E_loc, C_loc, d] buffer (local capacity C_loc = ceil(k*T_loc/E * cf)),
    runs its expert FFNs, and contributes zeros elsewhere;
  * else (grok-1, E=8 < tp=16): experts are replicated and d_ff is sharded
    (TP-within-expert); every device computes all experts on a 1/tp slice
    of the hidden dim;
  * one psum over `model` combines the partials. Tokens stay sharded over
    the data axes throughout - token traffic never crosses the data axis.

Single-device (smoke-test) path is the same math without the psum.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers as ll
from repro.models import transformer as tf
from repro.models.module import ParamDef

param_count_note = "MoE params = dense attn + E * expert FFN"


def param_defs(cfg: ModelConfig) -> dict:
    L, d, ff, E = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.n_experts
    ep = E % 16 == 0  # expert-parallel vs TP-within-expert
    es = "model" if ep else None
    ffs = None if ep else ll.ff_spec(ff)
    return {
        **ll.embed_defs(cfg),
        "layers": {
            "ln1": ParamDef((L, d), (None, None), init="zeros"),
            "ln2": ParamDef((L, d), (None, None), init="zeros"),
            "attn": ll.attn_defs(cfg, L),
            "moe": {
                "router": ParamDef((L, d, E), (None, None, None), fan_in_axis=1),
                "w_gate": ParamDef((L, E, d, ff), (None, es, None, ffs), fan_in_axis=2),
                "w_up": ParamDef((L, E, d, ff), (None, es, None, ffs), fan_in_axis=2),
                # stored [E, d, ff] like w_gate/w_up: avoids XLA layout-transposing
                # the whole stack at the shard_map boundary (see EXPERIMENTS Perf)
                "w_down": ParamDef((L, E, d, ff), (None, es, None, ffs), fan_in_axis=3),
            },
        },
    }


def _moe_local(xt, mp, cfg: ModelConfig, e_offset: int, n_local: int, act: str):
    """Token dispatch + expert FFN for the local expert slice.

    ``xt``: [T, d] local tokens; ``mp``: router [d, E] + expert weights with
    a leading local-expert dim [E_loc, ...]. Returns the *partial* output
    [T, d] (zero rows for tokens owned by other devices' experts).
    """
    T, d = xt.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    E_loc = mp["w_gate"].shape[0]

    logits = (xt.astype(jnp.float32) @ mp["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, -1)  # [T, E]
    gates, idx = jax.lax.top_k(probs, k)  # [T, k]
    gates = gates / gates.sum(-1, keepdims=True)

    # Slot-major flattening: slot 0 (highest gate) gets capacity priority.
    idx_f = idx.T.reshape(k * T)  # [kT]
    gate_f = gates.T.reshape(k * T)
    tok_f = jnp.tile(jnp.arange(T, dtype=jnp.int32), (k,))

    cap = max(1, math.ceil(k * T / E * cfg.capacity_factor))
    # Position-within-expert via stable sort over int32 keys: O(kT log kT)
    # int traffic instead of the [kT, E] one-hot cumsum (which cost
    # ~80 TB/device of HBM on qwen3-moe prefill — see EXPERIMENTS Sec. Perf).
    # Stable sort preserves row order within an expert, so positions are
    # bit-identical to the cumsum formulation.
    order = jnp.argsort(idx_f, stable=True)  # [kT]
    sorted_e = idx_f[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E, dtype=idx_f.dtype))  # [E]
    rank_sorted = jnp.arange(k * T, dtype=jnp.int32) - starts[sorted_e].astype(jnp.int32)
    pos_f = jnp.zeros((k * T,), jnp.int32).at[order].set(rank_sorted)

    e_loc = idx_f - e_offset
    valid = (pos_f < cap) & (e_loc >= 0) & (e_loc < n_local)
    slot = jnp.where(valid, e_loc * cap + pos_f, n_local * cap)  # overflow row

    buf = jnp.zeros((n_local * cap + 1, d), xt.dtype).at[slot].set(xt[tok_f])
    expert_in = buf[:-1].reshape(n_local, cap, d)

    cd = xt.dtype
    h = ll._ACT[act](
        jnp.einsum("ecd,edf->ecf", expert_in, mp["w_gate"].astype(cd))
    ) * jnp.einsum("ecd,edf->ecf", expert_in, mp["w_up"].astype(cd))
    h = jnp.einsum("ecf,edf->ecd", h, mp["w_down"].astype(cd))  # [E_loc, C, d]

    h_pad = jnp.concatenate([h.reshape(n_local * cap, d), jnp.zeros((1, d), cd)], 0)
    y_rows = jnp.where(valid[:, None], h_pad[slot], 0.0)  # [kT, d]
    y = (gate_f[:, None].astype(cd) * y_rows).reshape(k, T, d).sum(0)
    del E_loc
    return y


def apply_moe_ffn(mp, x, cfg: ModelConfig, parallel=None):
    """x: [B, S, d] -> [B, S, d].  ``parallel``: runtime ParallelCtx or None."""
    B, S, d = x.shape
    E = cfg.n_experts
    ep = E % 16 == 0

    if parallel is None:
        xt = x.reshape(B * S, d)
        y = _moe_local(xt, mp, cfg, e_offset=0, n_local=E, act=cfg.act)
        return y.reshape(B, S, d)

    mesh, dp, tp = parallel.mesh, parallel.dp_axes, parallel.tp_axis
    tp_size = mesh.shape[tp]
    n_local = E // tp_size if ep else E

    # w_down shares [E, d, ff] layout/spec with w_gate/w_up.
    wspec = dspec = P(tp, None, None) if ep else P(None, None, ll.ff_spec(cfg.d_ff))

    def fn(xl, router, wg, wu, wd):
        Bl, Sl, _ = xl.shape
        xt = xl.reshape(Bl * Sl, d)
        e_off = jax.lax.axis_index(tp) * n_local if ep else 0
        mp_loc = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        y = _moe_local(xt, mp_loc, cfg, e_offset=e_off, n_local=n_local, act=cfg.act)
        y = jax.lax.psum(y, tp)
        return y.reshape(Bl, Sl, d)

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None), wspec, wspec, dspec),
        out_specs=P(dp, None, None),
        check_vma=False,
    )(x, mp["router"], mp["w_gate"], mp["w_up"], mp["w_down"])


# ---------------------------------------------------------------------------
# Held experts with no capacity (the planned path; models/lfm2.py)
# ---------------------------------------------------------------------------


def route(x, router, cfg: ModelConfig):
    """``x`` [T, d] -> (expert ids [T, k] int32, gates [T, k] f32): the
    top k of ``sigmoid(x @ router)`` over every one of ``cfg.n_experts``
    (plus an expert bias, held at zero), the gates the chosen scores
    normalised to sum 1 (LFM2's routing).

    The scores are computed in f32 at full precision, so that the choice
    of experts depends on the layer's input and not on how the matmul
    rounds."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    gates, idx = jax.lax.top_k(jax.nn.sigmoid(logits), cfg.moe_top_k)
    return idx.astype(jnp.int32), gates / gates.sum(-1, keepdims=True)


def expert_buffer_rows(assignments: int, count: int, tile: int) -> int:
    """Rows of the sorted buffer: every assignment landing on a held
    expert, each of the ``count`` groups padded to the row ``tile``."""
    return -(-assignments // tile) * tile + count * tile


@jax.custom_vjp
def take_rows(src, idx, inv):
    """``out[i] = src[idx[i]]``, zero where ``idx[i] == len(src)``.

    ``inv`` [len(src), m] names the rows of ``out`` that read each row of
    ``src`` (``len(out)`` where fewer than m do), so the gradient is a
    gather too, ``dsrc[r] = sum_j dout[inv[r, j]]``, and not the scatter-add
    that differentiating a gather gives."""
    return _take(src, idx)


def _take(src, idx):
    return jnp.concatenate([src, jnp.zeros_like(src[:1])]).at[idx].get(
        mode="promise_in_bounds")


def _take_fwd(src, idx, inv):
    return _take(src, idx), (idx, inv)


def _take_bwd(res, g):
    idx, inv = res
    dsrc = _take(g, inv.reshape(-1)).reshape(*inv.shape, g.shape[-1])
    return dsrc.astype(jnp.float32).sum(1).astype(g.dtype), None, None


take_rows.defvjp(_take_fwd, _take_bwd)


def held_expert_ffn(x, mp: dict, cfg: ModelConfig, schedules: dict,
                    layer: int):
    """The routed-expert FFN of the experts this chip holds, with no
    capacity: ``x`` [T, d] -> (partial output [T, d], counters).

    Routes over all ``n_experts``; keeps the assignments that land on the
    held experts ``cfg.held = (first, count)``, sorts them by expert and
    pads each group to the row tile of ``schedules["experts_up"]``; one
    grouped GEMM computes gate and up, another down, and each token sums
    its gated rows.  The buffer has room for every assignment
    (``expert_buffer_rows``), so none is ever dropped; its rows that no
    held expert filled are the grouped kernels' tail and cost no tile.
    Rows move between tokens and the buffer by gathers both ways
    (:func:`take_rows`).  What the absent experts would add is left out:
    the caller adds the partial output to the residual, as a chip of an
    expert-parallel deployment does before the exchange.

    ``mp``: ``router`` [d, n_experts], ``w_gate``/``w_up`` [count, d, f],
    ``w_down`` [count, f, d].  Counters: ``rows`` [count], the assignments
    each held expert computed; ``pad``, the padding rows of the groups.
    Scopes: ``route<layer>`` (router, top-k, sort, the gathers into the
    buffer and back) and ``experts<layer>`` (the grouped GEMMs)."""
    from repro.kernels.matmul.grouped import grouped_matmul

    T, d = x.shape
    k = cfg.moe_top_k
    A = T * k  # assignment a = token * k + slot
    first, count = cfg.held
    cd = x.dtype
    up, down = schedules["experts_up"], schedules["experts_down"]
    tile = up["fwd"].block("block_m")
    rows = expert_buffer_rows(A, count, tile)
    with jax.named_scope(f"route{layer}"):
        idx, gates = route(x, mp["router"], cfg)
        local = idx.reshape(-1) - first
        key = jnp.where((local >= 0) & (local < count), local, count)
        sizes = jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32)
        padded = -(-sizes // tile) * tile
        pad_start = jnp.cumsum(padded) - padded
        run_start = jnp.cumsum(sizes) - sizes
        order = jnp.argsort(key, stable=True)
        key_s = jnp.minimum(key[order], count - 1)
        rank = jnp.arange(A, dtype=jnp.int32) - run_start[key_s]
        dest_s = jnp.where(key[order] < count, pad_start[key_s] + rank, rows)
        # Each held assignment's buffer row, and each row's assignment
        # (A for padding and the tail): the two maps between them.
        dest = jnp.full((A,), rows, jnp.int32).at[order].set(dest_s)
        row_of = jnp.full((rows,), A, jnp.int32).at[dest_s].set(
            order.astype(jnp.int32), mode="drop")
        row_tok = jnp.where(row_of < A, row_of // k, T)
        group_sizes = jnp.concatenate(
            [padded, (rows - padded.sum())[None]]).astype(jnp.int32)
        xr = take_rows(x, row_tok, dest.reshape(T, k))
    with jax.named_scope(f"experts{layer}"):
        w_gu = jnp.concatenate([mp["w_gate"], mp["w_up"]], -1).astype(cd)
        gu = grouped_matmul(xr, w_gu, group_sizes, up)
        g, u = jnp.split(gu, 2, axis=-1)
        h = ll._ACT[cfg.act](g) * u
        yr = grouped_matmul(h, mp["w_down"].astype(cd), group_sizes, down)
    with jax.named_scope(f"route{layer}"):
        ya = take_rows(yr, dest, row_of[:, None]).reshape(T, k, d)
        y = (gates[..., None] * ya.astype(jnp.float32)).sum(1)
    return y.astype(cd), {"rows": sizes, "pad": (padded - sizes).sum()}


def layer_meta(cfg):
    return tf.layer_meta(cfg)


def init_cache(cfg, batch, max_seq, dtype=jnp.bfloat16):
    return tf.init_cache(cfg, batch, max_seq, dtype)


def forward(
    cfg: ModelConfig, params: dict, tokens, *, pos0=0, cache=None,
    remat: str = "none", compute_dtype=jnp.bfloat16, parallel=None,
):
    from repro.runtime.parallel import constrain

    x = ll.embed_tokens(params, tokens, cfg, compute_dtype)
    x = constrain(x, parallel, ("dp", None, None))
    meta = tf.layer_meta(cfg)

    def body(x, xs):
        lp, window, theta, ck, cv = xs
        h = ll.rms_norm(x, lp["ln1"], cfg.norm_eps)
        h, new_cache = ll.apply_attention(
            lp["attn"], h, cfg, pos0=pos0, window=window, theta=theta,
            cache=(ck, cv) if cache is not None else None, parallel=parallel,
        )
        x = x + h
        h = ll.rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + apply_moe_ffn(lp["moe"], h, cfg, parallel)
        if cache is None:
            new_cache = (jnp.zeros((), x.dtype), jnp.zeros((), x.dtype))
        return x, new_cache

    if remat == "block":
        body = jax.checkpoint(body, prevent_cse=False)

    ck = cache["k"] if cache is not None else jnp.zeros((cfg.n_layers,))
    cv = cache["v"] if cache is not None else jnp.zeros((cfg.n_layers,))
    x, caches = jax.lax.scan(
        body, x, (params["layers"], meta["window"], meta["theta"], ck, cv)
    )
    new_cache = {"k": caches[0], "v": caches[1]} if cache is not None else None
    return x, new_cache


def logits(cfg, params, hidden):
    return ll.logits_from_hidden(params, hidden, cfg)
