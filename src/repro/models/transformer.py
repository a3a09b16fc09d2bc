"""Dense decoder-only transformer (gemma3 / qwen3 / qwen1.5 / chameleon).

Layers are stacked (leading L dim) and executed with lax.scan so the HLO is
one layer body regardless of depth.  Per-layer heterogeneity (gemma3's 5:1
local:global attention with different RoPE bases) is expressed as scanned
per-layer scalars (window, theta), not as distinct HLO.

The planned wing (DESIGN.md Sec. 11): ``forward(..., use_kernels=True,
schedules=plan_training(...))`` runs every GEMM of the block through the
planned ``fc_layer`` (Alg 4/5 Pallas kernel, planned dX/dW backward) and
the attention cell through the planned flash-attention kernel — the same
schedule-pinning contract as ``models/cnn.py``, with
:class:`repro.plan.TransformerBlockPlanner` owning the delegation table
(qkv/wo/mlp GEMMs -> MatmulPlanner, attn -> AttentionPlanner).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.fc_layer import fc_layer
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.models import layers as ll
from repro.models.module import ParamDef
from repro.plan import local_schedule, with_reference_vjp


def param_defs(cfg: ModelConfig) -> dict:
    L, d = cfg.n_layers, cfg.d_model
    return {
        **ll.embed_defs(cfg),
        "layers": {
            "ln1": ParamDef((L, d), (None, None), init="zeros"),
            "ln2": ParamDef((L, d), (None, None), init="zeros"),
            "attn": ll.attn_defs(cfg, L),
            "mlp": ll.mlp_defs(cfg, L),
        },
    }


def layer_meta(cfg: ModelConfig) -> dict:
    """Per-layer (window, theta) arrays; window -1 means full attention."""
    L = cfg.n_layers
    idx = jnp.arange(L)
    if cfg.global_every:
        is_global = (idx + 1) % cfg.global_every == 0
        window = jnp.where(is_global, -1, cfg.local_window or -1)
        theta = jnp.where(
            is_global, cfg.rope_theta_global or cfg.rope_theta, cfg.rope_theta
        )
    else:
        window = jnp.full((L,), cfg.local_window or -1, jnp.int32)
        theta = jnp.full((L,), cfg.rope_theta, jnp.float32)
    return {"window": window.astype(jnp.int32), "theta": theta.astype(jnp.float32)}


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    """KV cache [L, B, Smax, Hkv, Dh] per tensor."""
    shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.resolved_head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def forward(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # [B, S] int32
    *,
    pos0=0,
    cache: dict | None = None,
    remat: str = "none",
    compute_dtype=jnp.bfloat16,
    parallel=None,
    use_kernels: bool = False,
    schedules: dict | None = None,
):
    """Returns (hidden [B, S, d], new_cache).

    ``use_kernels=True`` (training only — no cache) runs the planned
    wing: every projection GEMM through the Pallas ``fc_layer`` and the
    attention cell through the planned flash-attention kernel.
    ``schedules`` maps cell names ("qkv", "attn", "wo", "mlp_up",
    "mlp_down") to explicit :class:`repro.plan.Schedule` objects (from
    :func:`plan_forward`); backward overrides ride in the same dict under
    "<cell>.dx"/"<cell>.dw" keys, which :func:`plan_training` emits — so
    ``jax.grad`` through this forward runs pinned planned backward
    kernels (attention's backward is the exact gradient in query blocks,
    in XLA: :func:`_attn_bwd`)."""
    from repro.runtime.parallel import constrain

    if use_kernels and cache is None:
        return _forward_planned(cfg, params, tokens, compute_dtype,
                                schedules, remat=remat), None

    x = ll.embed_tokens(params, tokens, cfg, compute_dtype)
    x = constrain(x, parallel, ("dp", None, None))
    meta = layer_meta(cfg)

    def body(x, xs):
        lp, window, theta, ck, cv = xs
        h, new_cache = _block(x, lp, cfg, window, theta, pos0,
                              (ck, cv) if cache is not None else None, parallel)
        return h, new_cache

    if remat == "block":
        body = jax.checkpoint(body, prevent_cse=False)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            prevent_cse=False,
        )

    ck = cache["k"] if cache is not None else jnp.zeros((cfg.n_layers,))
    cv = cache["v"] if cache is not None else jnp.zeros((cfg.n_layers,))
    x, caches = jax.lax.scan(
        body, x, (params["layers"], meta["window"], meta["theta"], ck, cv)
    )
    new_cache = None
    if cache is not None:
        new_cache = {"k": caches[0], "v": caches[1]}
    return x, new_cache


def _block(x, lp, cfg, window, theta, pos0, cache, parallel=None):
    h = ll.rms_norm(x, lp["ln1"], cfg.norm_eps)
    h, new_cache = ll.apply_attention(
        lp["attn"], h, cfg, pos0=pos0, window=window, theta=theta, cache=cache,
        parallel=parallel,
    )
    x = x + h
    h = ll.rms_norm(x, lp["ln2"], cfg.norm_eps)
    x = x + ll.apply_mlp(lp["mlp"], h, cfg.act, parallel)
    if cache is None:
        new_cache = (jnp.zeros((), x.dtype), jnp.zeros((), x.dtype))
    return x, new_cache


def _bwd_for(sched: dict, cell: str) -> dict | None:
    """The backward-Schedule overrides of one cell: ``{"qkv.dx": s}`` style
    keys (see :func:`plan_training`) become ``{"dx": s}``."""
    prefix = cell + "."
    out = {k[len(prefix):]: v for k, v in sched.items() if k.startswith(prefix)}
    return out or None


def _attn_kernel(q, k, v, causal, window, schedule):
    return flash_attention(q, k, v, causal=causal, window=window,
                           schedule=schedule)


def _attn_ref(q, k, v, causal, window, schedule):
    del schedule  # blocking never changes numerics
    return attention_ref(q, k, v, causal=causal, window=window)


# Bytes of one query block's f32 scores in the attention backward.
ATTN_BWD_BLOCK_BYTES = 256 * 2**20


def attn_bwd_block(batch: int, heads: int, seq: int) -> int:
    """Query rows of one block of :func:`_attn_bwd`: the largest power of
    two whose f32 scores against every key fit ``ATTN_BWD_BLOCK_BYTES``."""
    rows = max(8, ATTN_BWD_BLOCK_BYTES // (batch * heads * seq * 4))
    return min(seq, 1 << (rows.bit_length() - 1))


def _attn_bwd(q, k, v, g, causal, window, schedule):
    """The exact gradient of softmax attention, a block of queries at a
    time: each block's scores are recomputed against the keys it sees
    (up to its last row under ``causal``, from its window's start under
    ``window``), its softmax rebuilt, and dK, dV accumulated, so that no
    [S, S] array is ever held.  Matmuls take the inputs' dtype with f32
    accumulation; scores, softmax and the row sums are f32.  The blocks
    run one after another."""
    del schedule  # the forward kernel's blocking
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    grp = Hq // Hkv
    scale = D ** -0.5
    f32 = jnp.float32
    qg = q.reshape(B, Hkv, grp, S, D)
    gg = g.astype(q.dtype).reshape(B, Hkv, grp, S, D)
    dk = jnp.zeros(k.shape, f32)
    dv = jnp.zeros(v.shape, f32)
    dqs = []
    bq = attn_bwd_block(B, Hq, S)
    for lo in range(0, S, bq):
        hi = min(S, lo + bq)
        k_hi = hi if causal else S
        k_lo = max(0, lo - window + 1) if window else 0
        # The barrier makes each block wait for the last one's dK and dV,
        # so that one block's scores are live at a time.
        qg, gg, k, v, dk, dv = jax.lax.optimization_barrier(
            (qg, gg, k, v, dk, dv))
        qb, gb = qg[..., lo:hi, :], gg[..., lo:hi, :]
        kb, vb = k[:, :, k_lo:k_hi], v[:, :, k_lo:k_hi]
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kb,
                       preferred_element_type=f32) * scale
        qi = jnp.arange(lo, hi)[:, None]
        ki = jnp.arange(k_lo, k_hi)[None, :]
        keep = jnp.ones((hi - lo, k_hi - k_lo), bool)
        if causal:
            keep &= ki <= qi
        if window:
            keep &= qi - ki < window
        s = jnp.where(keep, s, -1e30)
        # The row max passes a barrier: XLA would otherwise make the max
        # and its broadcast one reduce-window as wide as the row, whose
        # work grows with the square of the keys.
        e = jnp.exp(s - jax.lax.optimization_barrier(s.max(-1, keepdims=True)))
        p = e / e.sum(-1, keepdims=True)
        pc = p.astype(q.dtype)
        o = jnp.einsum("bhgqk,bhkd->bhgqd", pc, vb, preferred_element_type=f32)
        delta = jnp.sum(o * gb.astype(f32), -1, keepdims=True)
        dp = jnp.einsum("bhgqd,bhkd->bhgqk", gb, vb, preferred_element_type=f32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dqs.append(jnp.einsum("bhgqk,bhkd->bhgqd", ds, kb,
                              preferred_element_type=f32))
        dk = dk.at[:, :, k_lo:k_hi].add(
            jnp.einsum("bhgqk,bhgqd->bhkd", ds, qb, preferred_element_type=f32))
        dv = dv.at[:, :, k_lo:k_hi].add(
            jnp.einsum("bhgqk,bhgqd->bhkd", pc, gb, preferred_element_type=f32))
    dq = jnp.concatenate(dqs, axis=-2).reshape(q.shape)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# The planned attention cell: forward is the flash-attention Pallas kernel
# under its AttentionPlanner schedule; backward is :func:`_attn_bwd`, the
# exact gradient in query blocks, in XLA (no planned attention backward
# kernel exists yet; the GEMM cells have theirs, via fc_layer).
_attn_vjp = with_reference_vjp(_attn_kernel, _attn_ref,
                               nondiff_argnums=(3, 4, 5), bwd_fn=_attn_bwd)


def _forward_planned(cfg: ModelConfig, params: dict, tokens: jax.Array,
                     compute_dtype, schedules: dict | None,
                     remat: str = "none") -> jax.Array:
    """The planned training forward: hidden [B, S, d] (no cache).

    Cell decomposition mirrors ``TransformerBlockPlanner.cell_planners``:
    q/k/v fold into ONE fused ``[B*S, d] @ [d, (Hq+2*Hkv)*Dh]`` GEMM (one
    x stream for all three projections), gate+up into one
    ``[B*S, d] @ [d, 2*ff]`` GEMM, and attention runs on the [B, H, S, D]
    layout the flash kernel wants.  Per-layer heterogeneous windows
    (``global_every``) would make the attention cell's window a traced
    scan carry, which a pinned static schedule cannot express.
    """
    if cfg.global_every:
        raise ValueError(
            "planned transformer forward needs one static attention "
            f"window; global_every={cfg.global_every} mixes per-layer "
            "windows inside the scanned block (use the XLA path)")
    sched = schedules or {}
    cd = jnp.dtype(compute_dtype)
    x = ll.embed_tokens(params, tokens, cfg, cd)
    B, S, d = x.shape
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    pos = jnp.arange(S, dtype=jnp.int32)
    window = cfg.local_window or None
    s_attn = local_schedule(sched.get("attn"))

    def body(x, lp):
        ap, mp = lp["attn"], lp["mlp"]
        h = ll.rms_norm(x, lp["ln1"], cfg.norm_eps)
        w_qkv = jnp.concatenate(
            [ap["wq"].reshape(d, Hq * Dh), ap["wk"].reshape(d, Hkv * Dh),
             ap["wv"].reshape(d, Hkv * Dh)], axis=1).astype(cd)
        qkv = fc_layer(h.reshape(B * S, d), w_qkv, sched.get("qkv"),
                       _bwd_for(sched, "qkv"))
        q, k, v = jnp.split(qkv, [Hq * Dh, (Hq + Hkv) * Dh], axis=-1)
        q = q.reshape(B, S, Hq, Dh)
        k = k.reshape(B, S, Hkv, Dh)
        v = v.reshape(B, S, Hkv, Dh)
        if cfg.qkv_bias:
            q = q + ap["bq"].astype(cd)
            k = k + ap["bk"].astype(cd)
            v = v + ap["bv"].astype(cd)
        if cfg.qk_norm:
            q = ll.rms_norm(q, ap["q_norm"], cfg.norm_eps)
            k = ll.rms_norm(k, ap["k_norm"], cfg.norm_eps)
        q = ll.rope(q, pos, cfg.rope_theta)
        k = ll.rope(k, pos, cfg.rope_theta)
        o = _attn_vjp(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), True, window, s_attn)
        o = o.transpose(0, 2, 1, 3).reshape(B * S, Hq * Dh)
        wo = ap["wo"].reshape(Hq * Dh, d).astype(cd)
        x = x + fc_layer(o, wo, sched.get("wo"),
                         _bwd_for(sched, "wo")).reshape(B, S, d)
        h = ll.rms_norm(x, lp["ln2"], cfg.norm_eps)
        w_gu = jnp.concatenate([mp["w_gate"], mp["w_up"]], axis=1).astype(cd)
        gu = fc_layer(h.reshape(B * S, d), w_gu, sched.get("mlp_up"),
                      _bwd_for(sched, "mlp_up"))
        g, u = jnp.split(gu, 2, axis=-1)
        down = fc_layer(ll._ACT[cfg.act](g) * u, mp["w_down"].astype(cd),
                        sched.get("mlp_down"), _bwd_for(sched, "mlp_down"))
        return x + down.reshape(B, S, d), None

    if remat == "block":
        body = jax.checkpoint(body, prevent_cse=False)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            prevent_cse=False,
        )
    x, _ = jax.lax.scan(body, x, params["layers"])
    return x


def logits(cfg: ModelConfig, params: dict, hidden: jax.Array, *,
           schedules: dict | None = None) -> jax.Array:
    """Hidden -> [B, S, vocab].  With a "logits" entry in ``schedules``
    (from :func:`plan_forward`, planned at the chunked-CE token-chunk
    size) the head runs the planned ``fc_layer`` GEMM; backward overrides
    ride under "logits.dx"/"logits.dw"."""
    sched = schedules or {}
    s = sched.get("logits")
    if s is None:
        return ll.logits_from_hidden(params, hidden, cfg)
    x = ll.rms_norm(hidden, params["final_norm"], cfg.norm_eps)
    B, S, d = x.shape
    w = (params["embed"].T if cfg.tie_embeddings
         else params["w_out"]).astype(x.dtype)
    out = fc_layer(x.reshape(B * S, d), w, s, _bwd_for(sched, "logits"))
    return out.reshape(B, S, -1)


def _chunk_m(batch: int, seq: int, loss_chunks: int) -> int:
    """The logits GEMM's M: chunked_ce's token-chunk row count — its
    ``while S % n: n -= 1`` divisor adjustment, verbatim."""
    n = max(1, loss_chunks)
    while seq % n:
        n -= 1
    return batch * (seq // n)


def plan_forward(cfg: ModelConfig, batch: int, seq: int, *,
                 loss_chunks: int = 1, in_bytes: int = 4, machine=None,
                 mesh=None, shard_axis: str = "data",
                 autotune=None) -> dict:
    """Plan every kernel launch of the planned :func:`forward` plus the
    :func:`logits` head, without running them.

    Returns {cell name: Schedule} keyed qkv/attn/wo/mlp_up/mlp_down/logits
    — the delegation table is ``TransformerBlockPlanner.cell_planners``
    (matmul cells to MatmulPlanner, the attention cell to
    AttentionPlanner), each cell resolved through the autotune cache like
    every other op.  The logits cell is planned at the *chunk* M that
    ``runtime.train.chunked_ce`` actually calls (``loss_chunks``), not the
    full token count.  With ``mesh=`` every cell comes back as a
    ShardedSchedule — the GEMM cells' tp/batch/psum/ring argmin per cell
    (DESIGN.md Sec. 11).
    """
    from repro.core.machine import TPU_V5E
    from repro.plan import autotune as at
    from repro.plan.planners import TransformerBlockPlanner

    machine = machine or TPU_V5E
    cells = TransformerBlockPlanner(machine).cell_planners(
        batch=batch, seq=seq, d_model=cfg.d_model, n_heads=cfg.n_heads,
        d_ff=cfg.d_ff, n_kv_heads=cfg.n_kv_heads, in_bytes=in_bytes,
        causal=True)
    out = {name: at.resolve(planner.op, kw, machine=machine, mesh=mesh,
                            axis=shard_axis, policy=autotune)
           for name, (planner, kw) in cells.items()}
    out["logits"] = at.resolve(
        "matmul",
        dict(m=_chunk_m(batch, seq, loss_chunks), n=cfg.vocab,
             k=cfg.d_model, in_bytes=in_bytes),
        machine=machine, mesh=mesh, axis=shard_axis, policy=autotune)
    return out


def plan_training(cfg: ModelConfig, batch: int, seq: int, *,
                  loss_chunks: int = 1, in_bytes: int = 4, machine=None,
                  mesh=None, shard_axis: str = "data",
                  autotune=None) -> dict:
    """:func:`plan_forward` plus every planned backward kernel
    ``jax.grad`` runs: "<cell>.dx"/"<cell>.dw" for each GEMM cell (the
    fused dX/dW kernel when it fits; the attention cell differentiates
    its XLA reference, so it contributes no backward entries).  Pass the
    result via ``schedules=`` so the whole train step executes pinned
    planned kernels — the same contract as ``cnn.plan_training``."""
    from repro.core import fc_layer as fl

    out = plan_forward(cfg, batch, seq, loss_chunks=loss_chunks,
                       in_bytes=in_bytes, machine=machine, mesh=mesh,
                       shard_axis=shard_axis, autotune=autotune)
    d, ff = cfg.d_model, cfg.d_ff
    Hq = cfg.n_heads
    Hkv = cfg.n_kv_heads or Hq
    Dh = cfg.resolved_head_dim
    m = batch * seq
    gemms = {
        "qkv": (m, d, (Hq + 2 * Hkv) * Dh),
        "wo": (m, Hq * Dh, d),
        "mlp_up": (m, d, 2 * ff),
        "mlp_down": (m, ff, d),
        "logits": (_chunk_m(batch, seq, loss_chunks), d, cfg.vocab),
    }
    for name, (mm, k, n) in gemms.items():
        bwd = fl.plan_bwd((mm, k), (k, n), in_bytes=in_bytes,
                          machine=machine, mesh=mesh, shard_axis=shard_axis,
                          autotune=autotune)
        for kk, s in bwd.items():
            out[f"{name}.{kk}"] = s
    return out


def make_loss_fn(cfg: ModelConfig, tcfg, parallel=None):
    """Family-registry hook (runtime.train.make_loss_fn dispatches here):
    the dense-transformer training loss.  Under ``tcfg.planned_kernels``
    the whole step runs planned Pallas kernels — :func:`plan_training`
    pins every cell's Schedule at trace time (batch/seq are static there,
    exactly like the cnn hook reads ``imgs.shape``), the planned forward
    executes them, and ``chunked_ce`` routes its logits GEMM through the
    planned head."""
    import sys

    from repro.runtime.train import chunked_ce

    dt = jnp.dtype(tcfg.compute_dtype)
    fam = sys.modules[__name__]

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        if tcfg.planned_kernels:
            B, S = tokens.shape
            sched = plan_training(cfg, B, S, loss_chunks=tcfg.loss_chunks,
                                  in_bytes=dt.itemsize)
            h, _ = forward(cfg, params, tokens, compute_dtype=dt,
                           remat=tcfg.remat, use_kernels=True,
                           schedules=sched)
            return chunked_ce(cfg, fam, params, h, batch["labels"],
                              tcfg.loss_chunks, parallel, schedules=sched)
        h, _ = forward(cfg, params, tokens, remat=tcfg.remat,
                       compute_dtype=dt, parallel=parallel)
        return chunked_ce(cfg, fam, params, h, batch["labels"],
                          tcfg.loss_chunks, parallel)

    return loss_fn
