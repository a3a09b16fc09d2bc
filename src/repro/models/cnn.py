"""The paper's own domain: a CNN built directly from core.conv_layer and
core.fc_layer (VGG-style conv/pool stages + two FC layers).

Config reuse: ``n_layers`` = conv stages, ``d_model`` = base channel width
(doubled per stage), ``d_ff`` = FC hidden width, ``vocab`` = classes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.conv_layer import conv_block
from repro.core.fc_layer import fc_layer
from repro.models.module import ParamDef

IMG = 32  # input resolution (CIFAR-like)
IN_CH = 3
F = 3  # receptive field of every conv filter (the paper's running F)


def _stage_channels(cfg: ModelConfig) -> list[tuple[int, int]]:
    chans, c_in = [], IN_CH
    for i in range(cfg.n_layers):
        c_out = cfg.d_model * (2**i)
        chans.append((c_in, c_out))
        c_in = c_out
    return chans


def _stage_geometry(cfg: ModelConfig, batch: int):
    """The single source of every stage's operand shapes: yields
    ``(name, x_shape, w_shape)`` for each conv stage (halving the plane
    per 2x2 pool) and each FC stage — consumed by param_defs (widths),
    plan_forward and plan_training, so a topology change lands in one
    place."""
    H = IMG
    for i, (ci, co) in enumerate(_stage_channels(cfg)):
        yield f"conv{i}", (batch, H, H, ci), (F, F, ci, co)
        H //= 2
    flat = H * H * cfg.d_model * (2 ** (cfg.n_layers - 1))
    yield "fc1", (batch, flat), (flat, cfg.d_ff)
    yield "fc2", (batch, cfg.d_ff), (cfg.d_ff, cfg.vocab)


def param_defs(cfg: ModelConfig) -> dict:
    defs = {}
    for name, _x_shape, w_shape in _stage_geometry(cfg, batch=1):
        if name.startswith("conv"):
            i = name[len("conv"):]
            defs[name] = ParamDef(w_shape, (None, None, None, None), fan_in_axis=2)
            defs[f"bias{i}"] = ParamDef((w_shape[3],), (None,), init="zeros")
        else:
            spec = (None, "model") if name == "fc1" else ("model", None)
            defs[name] = ParamDef(w_shape, spec)
            defs[f"{name}_b"] = ParamDef((w_shape[1],), (None,), init="zeros")
    return defs


def batch_shard_specs(dp) -> dict:
    """Family-registry hook: how this family's batch shards over the data
    axes (``dp`` is an axis name or tuple).  Images shard their batch
    dimension — the same "batch" partition the mesh-aware ConvPlanner
    emits for every conv stage (plan_forward(..., mesh=)) — so the
    launcher needs no family special-casing."""
    return {"images": P(dp, None, None, None), "labels": P(dp)}


def data_source(cfg: ModelConfig, batch: int, shard, seed: int = 0):
    """Family-registry hook (registry.make_data_source dispatches here):
    this family trains on image/label batches, not token streams."""
    from repro.data.pipeline import SyntheticImageSource

    return SyntheticImageSource(IMG, IN_CH, cfg.vocab, batch, shard,
                                seed=seed)


def make_loss_fn(cfg: ModelConfig, tcfg, parallel=None):
    """Family-registry hook (runtime.train.make_loss_fn dispatches here):
    image-classification cross-entropy over :func:`forward`.  Under
    ``tcfg.planned_kernels`` the step runs the full planned set — fused
    forward kernels plus the planned dgrad/wgrad/dX/dW backward kernels,
    every Schedule pinned by :func:`plan_training` (cached per shape).

    With a ``parallel`` context over more than one data-parallel device
    the planned step runs data-parallel under one ``shard_map``: each
    device executes the per-device Schedules of the mesh-aware plan on its
    share of the batch (a Pallas kernel cannot be partitioned by the
    compiler, so without the shard_map every device would run it on the
    whole batch), and the parameter gradients are summed across devices
    by the shard_map's transpose."""
    dt = jnp.dtype(tcfg.compute_dtype)

    def local_loss(params, images, labels, schedules):
        imgs = images.astype(dt)
        # Mixed precision: every kernel computes on compute-dtype operands
        # (the planners size their blocks for that width); the gradients
        # flow back through the cast to the float32 params.
        params = jax.tree.map(lambda p: p.astype(dt), params)
        if tcfg.planned_kernels:
            out = forward(cfg, params, imgs, use_kernels=True,
                          schedules=schedules)
        else:
            out = forward(cfg, params, imgs, use_kernels=False)
        # Under its own scope, as each stage is, so that the compiled
        # step's loss operations carry it in op_name.
        with jax.named_scope("loss"):
            out = out.astype(jnp.float32)
            lse = jax.nn.logsumexp(out, -1)
            tgt = jnp.take_along_axis(out, labels[:, None], -1)[:, 0]
            return (lse - tgt).mean()

    if not (tcfg.planned_kernels and parallel is not None
            and parallel.dp_size > 1):
        def loss_fn(params, batch):
            schedules = None
            if tcfg.planned_kernels:
                schedules = plan_training(cfg, batch["images"].shape[0],
                                          in_bytes=dt.itemsize)
            return local_loss(params, batch["images"], batch["labels"],
                              schedules)

        return loss_fn

    from repro.plan import local_schedule

    dp = parallel.dp_axes
    if len(dp) != 1:
        raise NotImplementedError(
            f"the planned data-parallel step shards the batch over one mesh "
            f"axis; got data axes {dp}")
    axis = dp[0]

    def loss_fn(params, batch):
        splan = plan_training(cfg, batch["images"].shape[0],
                              in_bytes=dt.itemsize,
                              mesh=parallel.plan_mesh(), shard_axis=axis)
        unsharded = {k: s.strategy for k, s in splan.items()
                     if s.strategy != "batch"}
        if unsharded:
            raise ValueError(
                f"batch {batch['images'].shape[0]} does not shard over "
                f"{parallel.mesh.shape[axis]} devices: {unsharded}")
        local = {k: local_schedule(s) for k, s in splan.items()}

        def per_device(params, images, labels):
            return local_loss(params, images, labels, local)[None]

        # One loss per device, averaged outside the shard_map: the
        # backward then hands each device 1/n of the cotangent, and the
        # transpose sums the replicated parameters' gradients over ``axis``.
        losses = jax.shard_map(
            per_device, mesh=parallel.mesh,
            in_specs=(P(), P(axis), P(axis)), out_specs=P(axis),
            check_vma=False,
        )(params, batch["images"], batch["labels"])
        return losses.mean()

    return loss_fn


def _bwd_for(sched: dict, stage: str) -> dict | None:
    """The backward-Schedule overrides of one stage: ``{"conv0.dgrad": s}``
    style keys (see :func:`plan_training`) become ``{"dgrad": s}``."""
    prefix = stage + "."
    out = {k[len(prefix):]: v for k, v in sched.items() if k.startswith(prefix)}
    return out or None


def forward(cfg: ModelConfig, params: dict, images: jax.Array, *,
            use_kernels: bool = True, schedules: dict | None = None, **_):
    """images: [B, IMG, IMG, 3] -> logits [B, classes].

    ``schedules`` optionally maps stage names ("conv0", ..., "fc1", "fc2")
    to explicit :class:`repro.plan.Schedule` objects (e.g. from
    :func:`plan_forward`), overriding the per-stage capacity planner.
    Backward-pass overrides ride in the same dict under
    "<stage>.dgrad"/"<stage>.wgrad" (conv; plus "<stage>.recompute" on
    ragged geometries where the fused forward can't emit the mask
    residual) and "<stage>.dx"/"<stage>.dw" (FC) keys —
    :func:`plan_training` emits the full set, so ``jax.grad`` through
    this forward runs pinned planned backward kernels.
    """
    sched = schedules or {}
    x = images
    for i in range(cfg.n_layers):
        f, b = params[f"conv{i}"], params[f"bias{i}"]
        # Each stage runs under its own name scope, so the compiled step's
        # kernels (forward and backward) carry their stage in op_name.
        with jax.named_scope(f"conv{i}"):
            if use_kernels:
                # One batched kernel launch per stage: conv + bias + ReLU +
                # 2x2 max-pool all fused in the flush — no HBM round-trip
                # between the conv and its epilogue.
                x = conv_block(x, f, b, 1, F // 2, 2, "strip",
                               sched.get(f"conv{i}"),
                               _bwd_for(sched, f"conv{i}"))
            else:
                from repro.kernels.conv2d.ref import conv2d_fused_ref

                x = conv2d_fused_ref(x, f, b, stride=1, padding=F // 2,
                                     relu=True, pool=2)
    x = x.reshape(x.shape[0], -1)
    for name in ("fc1", "fc2"):
        with jax.named_scope(name):
            if use_kernels:
                x = fc_layer(x, params[name], sched.get(name),
                             _bwd_for(sched, name))
            else:
                x = x @ params[name]
            x = x + params[f"{name}_b"]
        if name == "fc1":
            x = jax.nn.relu(x)
    return x


def plan_forward(cfg: ModelConfig, batch: int, *, in_bytes: int = 4,
                 machine=None, mesh=None, shard_axis: str = "data",
                 autotune=None, conv_algorithm=None) -> dict:
    """Plan every kernel launch of :func:`forward` without running it.

    Returns {stage name: Schedule} — pass back in via ``schedules=`` to pin
    the blocking, or sum ``.modeled_words`` to connect the whole model's
    planned traffic to analysis/roofline.py (repro.plan.to_roofline).
    With ``mesh=`` every stage comes back as a ShardedSchedule sharding
    the batch over ``shard_axis`` (the data-parallel step of
    :func:`make_loss_fn` executes exactly these) — ``forward`` consumes
    either flavor, a 1-device mesh reproducing today's plans exactly.
    ``autotune=`` ("cache-only"/"tune") resolves every stage through the
    measured-winner cache (repro.plan.autotune) before the argmin.
    ``conv_algorithm=`` pins one family of the conv stages' two-level
    algorithm x blocking argmin ("direct"/"im2col"); the default lets
    both compete per stage, and :func:`forward` executes whichever kernel
    each stage's schedule tag names.
    """
    from repro.core import conv_layer as cl
    from repro.core import fc_layer as fl

    out = {}
    for name, x_shape, w_shape in _stage_geometry(cfg, batch):
        if name.startswith("conv"):
            out[name] = cl.plan(x_shape, w_shape, stride=1, padding=F // 2,
                                pool=2, in_bytes=in_bytes, machine=machine,
                                mesh=mesh, shard_axis=shard_axis,
                                autotune=autotune,
                                algorithm=conv_algorithm)
        else:
            # Under a mesh the whole step runs data-parallel (make_loss_fn),
            # so the FC stages shard the batch like the convs; the Alg 3/4
            # ring and psum dataflows run through fc_layer_sharded.
            out[name] = fl.plan(x_shape, w_shape, in_bytes=in_bytes,
                                machine=machine, mesh=mesh,
                                shard_axis=shard_axis, autotune=autotune,
                                strategy=None if mesh is None else "batch")
    return out


def plan_training(cfg: ModelConfig, batch: int, *, in_bytes: int = 4,
                  machine=None, mesh=None, shard_axis: str = "data",
                  autotune=None, conv_algorithm=None, seq=None,
                  loss_chunks: int = 1) -> dict:
    """:func:`plan_forward` plus every backward kernel ``jax.grad`` runs:
    "<stage>.dgrad"/"<stage>.wgrad" for conv stages (the fused-epilogue
    backward — a "<stage>.recompute" entry appears only on ragged
    geometries), "<stage>.dx"/"<stage>.dw" for FC stages.  Pass the result via
    ``schedules=`` so the whole training step executes pinned planned
    kernels; sum ``.modeled_words`` for the step's modeled HBM traffic.
    With ``mesh=`` the wgrad/dw entries additionally charge the gradient
    all-reduce (Alg 4's tree reduction) as ``ici_words`` — the modeled
    cost of data-parallel training, split HBM vs interconnect.  The
    backward stages resolve through the same ``autotune=`` policy.
    ``seq``/``loss_chunks`` belong to the uniform family-hook signature
    (token families size their logits cell with them); the image family
    has no sequence axis or chunked logits head and ignores both.
    """
    del seq, loss_chunks  # image batches: no token axes
    from repro.core import conv_layer as cl
    from repro.core import fc_layer as fl

    out = plan_forward(cfg, batch, in_bytes=in_bytes, machine=machine,
                       mesh=mesh, shard_axis=shard_axis, autotune=autotune,
                       conv_algorithm=conv_algorithm)
    for name, x_shape, w_shape in _stage_geometry(cfg, batch):
        if name.startswith("conv"):
            # pool=2 matches forward()'s fused conv_block epilogue, so the
            # conv stages plan the fused-epilogue backward (mask-scatter
            # dgrad, no recompute entry) whenever the plane tiles evenly.
            bwd = cl.plan_bwd(x_shape, w_shape, stride=1, padding=F // 2,
                              pool=2, in_bytes=in_bytes, machine=machine,
                              mesh=mesh, shard_axis=shard_axis,
                              autotune=autotune)
        else:
            bwd = fl.plan_bwd(x_shape, w_shape, in_bytes=in_bytes,
                              machine=machine, mesh=mesh,
                              shard_axis=shard_axis, autotune=autotune)
        for k, s in bwd.items():
            out[f"{name}.{k}"] = s
    return out
