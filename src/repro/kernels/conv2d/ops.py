"""Public wrapper for the batched, strip-tiled stacked conv2d kernel — a
thin registration against the ``repro.plan`` scheduling layer.

Blocking comes from :class:`repro.plan.ConvPlanner` (the same capacity rule
that gives Delta_O <= 24/12 on Manticore in core/ccr.py): pass nothing and
the planner trades strip height against output-channel stacking by modeled
main-memory words; pass ``block_*`` to pin individual blocks; or pass a
full explicit :class:`repro.plan.Schedule` to override the planner
entirely (``schedule=``).  The registered ``sharded_impl`` executes the
mesh-aware planner's data-parallel strategies ("batch"/"stack") from a
:class:`repro.plan.ShardedSchedule`, specs read off its partition.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.machine import TPU_V5E, MachineModel, machine_named
from repro.kernels.conv2d.conv2d import conv2d_fused_pallas
from repro.kernels.conv2d.ref import conv2d_fused_ref, conv2d_ref, maxpool_ref  # noqa: F401
from repro.plan import ConvPlanner, Schedule, pad_dim, pallas_op, partition_specs
from repro.plan.planners import round_up as _round_up

_LANE = 128


def conv_out_extent(extent: int, padding: int, F: int, stride: int) -> int:
    """Output rows/cols of one spatial axis: (E + 2P - F)//S + 1 (Sec. 1.1).
    The single source of this formula for wrapper, planner and layers."""
    return (extent + 2 * padding - F) // stride + 1


def _fused_pool(H_O: int, W_O: int, pool: int) -> int:
    """Pool fuses into the kernel flush only when the output plane tiles
    evenly; otherwise bias+ReLU stay fused and the (rare) ragged pool runs
    as a tail op."""
    return pool if (pool > 1 and H_O % pool == 0 and W_O % pool == 0) else 1


def _shape_args(
    x, f, bias=None, *, stride=1, padding=0, relu=False, pool=1,
    block_do=None, block_di=None, block_h=None,
    algorithm=None, block_m=None, block_n=None, block_k=None,
):
    """Planner shapes from concrete operands (the op registry contract).
    ``algorithm`` pins one family of the two-level argmin ("direct" /
    "im2col"); block_m/n/k pin the im2col GEMM's delegated blocking the
    way block_do/di/h pin the direct kernel's."""
    batched = x.ndim == 4
    B = x.shape[0] if batched else 1
    H, W, d_in = x.shape[-3], x.shape[-2], x.shape[-1]
    F, d_out = f.shape[0], f.shape[3]
    H_O = conv_out_extent(H, padding, F, stride)
    W_O = conv_out_extent(W, padding, F, stride)
    return dict(
        H_O=H_O, W_O=W_O, F=F, S=stride, d_in=d_in, d_out=d_out,
        in_bytes=x.dtype.itemsize, block_di=block_di,
        pool=_fused_pool(H_O, W_O, pool), batch=B,
        padding=padding, H_I=H, W_I=W,
        block_h=block_h, block_do=block_do,
        algorithm=algorithm, block_m=block_m, block_n=block_n,
        block_k=block_k,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "stride", "padding", "relu", "pool", "schedule", "out_dtype",
        "interpret", "emit_mask",
    ),
)
def _conv2d_impl(
    x, f, bias, *, stride, padding, relu, pool, schedule, out_dtype, interpret,
    emit_mask=False,
):
    batched = x.ndim == 4
    if not batched:
        x = x[None]
    B, H, W, d_in = x.shape
    F = f.shape[0]
    d_out = f.shape[3]
    S = stride
    H_O = conv_out_extent(H, padding, F, S)
    W_O = conv_out_extent(W, padding, F, S)
    assert H_O > 0 and W_O > 0, "receptive field larger than padded input"
    fused_pool = _fused_pool(H_O, W_O, pool)

    # Blocking comes from the Schedule; default missing blocks and clamp
    # defensively so a hand-built (possibly partial) schedule still runs
    # (fidelity of the plan is the planner's job, legality is ours).
    bdi = schedule.block("block_di", min(_round_up(d_in, _LANE), 512))
    hb = _round_up(
        min(schedule.block("block_h", H_O), _round_up(H_O, fused_pool)), fused_pool
    )
    bdo = min(schedule.block("block_do", _LANE), _round_up(d_out, _LANE))
    if interpret:
        # Interpreted wall time scales with the 128-lane channel pad; when a
        # block already spans its extent (grid dim 1) shrink it to the true
        # extent — step counts and grids are unchanged.
        if bdi >= d_in:
            bdi = max(1, d_in)
        if bdo >= d_out:
            bdo = max(1, d_out)

    n_h = -(-H_O // hb)
    rows_needed = (n_h * hb - 1) * S + F
    pad_bottom = padding + max(0, rows_needed - (H + 2 * padding))
    dip, dop = _round_up(d_in, bdi), _round_up(d_out, bdo)
    xp = jnp.pad(x, ((0, 0), (padding, pad_bottom), (padding, padding), (0, 0)))
    xp = pad_dim(xp, 3, dip)
    fp = pad_dim(pad_dim(f, 2, dip), 3, dop)
    bp = pad_dim(bias.astype(jnp.float32), 0, dop)[None]

    out = conv2d_fused_pallas(
        xp, fp, bp,
        stride=S, block_h=hb, block_do=bdo, block_di=bdi,
        H_O=H_O, W_O=W_O, relu=relu, pool=fused_pool,
        emit_mask=emit_mask, out_dtype=out_dtype, interpret=interpret,
        machine=machine_named(schedule.machine),
    )
    if emit_mask:
        out, mask = out
        out = out[:, : H_O // fused_pool, :, :d_out]
        mask = mask[:, : H_O // fused_pool, :, :d_out]
        assert not (pool > 1 and fused_pool == 1), (
            "ragged pool cannot emit the epilogue-VJP mask")
        if not batched:
            return out[0], mask[0]
        return out, mask
    out = out[:, : H_O // fused_pool, :, :d_out]
    if pool > 1 and fused_pool == 1:  # ragged tail pool (odd H_O/W_O)
        out = maxpool_ref(out, pool)
    return out if batched else out[0]


def _local_impl(x, f, bias, *, schedule, **kw):
    """Algorithm dispatch off the schedule tag: a two-level-argmin (or
    cache-replayed) schedule carrying ``algorithm="im2col"`` executes the
    patch-matrix GEMM kernel, ``algorithm="rows"`` the row-GEMM kernel;
    everything else runs the direct strip kernel.  The imports are lazy
    so the conv modules stay acyclic."""
    algorithm = getattr(schedule, "algorithm", "direct")
    if algorithm == "im2col":
        from repro.kernels.conv2d.im2col import _conv2d_im2col_impl

        return _conv2d_im2col_impl(x, f, bias, schedule=schedule, **kw)
    if algorithm == "rows":
        from repro.kernels.conv2d.rows import conv2d_rows

        return conv2d_rows(x, f, bias, schedule=schedule, **kw)
    return _conv2d_impl(x, f, bias, schedule=schedule, **kw)


def _impl(
    x, f, bias, *, schedule, out_dtype, interpret,
    stride=1, padding=0, relu=False, pool=1,
    block_do=None, block_di=None, block_h=None,  # consumed by the planner
    algorithm=None, block_m=None, block_n=None, block_k=None,
):
    del block_do, block_di, block_h, algorithm, block_m, block_n, block_k
    return _local_impl(
        x, f, bias, stride=stride, padding=padding, relu=relu, pool=int(pool),
        schedule=schedule, out_dtype=out_dtype, interpret=interpret,
    )


def _sharded_impl(x, f, bias, *, schedule, mesh, out_dtype, interpret,
                  stride=1, padding=0, relu=False, pool=1,
                  block_do=None, block_di=None, block_h=None,
                  algorithm=None, block_m=None, block_n=None, block_k=None):
    """Data-parallel conv from a ShardedSchedule: "batch" shards images,
    "stack" shards output channels (each device runs the planned local
    kernel on its shard); no interconnect traffic either way — the specs
    come from ``schedule.partition``, the blocking (and algorithm tag)
    from the per-device local Schedule, so both partitions apply to both
    algorithm families."""
    del block_do, block_di, block_h  # consumed by the planner
    del algorithm, block_m, block_n, block_k
    if schedule.strategy not in ("batch", "stack"):
        raise NotImplementedError(
            f"conv2d sharded strategy {schedule.strategy!r}")
    *in_specs, out_spec = partition_specs(schedule)
    batched = x.ndim == 4
    if not batched:
        x = x[None]

    def fn(xl, fl, bl):
        return _local_impl(
            xl, fl, bl, stride=stride, padding=padding, relu=relu,
            pool=int(pool), schedule=schedule.schedule, out_dtype=out_dtype,
            interpret=interpret,
        )

    out = jax.shard_map(fn, mesh=mesh, in_specs=tuple(in_specs),
                    out_specs=out_spec, check_vma=False)(x, f, bias)
    return out if batched else out[0]


conv2d_op = pallas_op(
    "conv2d",
    planner=ConvPlanner,
    shape_args=_shape_args,
    impl=_impl,
    reference=conv2d_fused_ref,
    sharded_impl=_sharded_impl,
)


def conv2d(
    x: jax.Array,
    f: jax.Array,
    *,
    stride: int = 1,
    padding: int = 0,
    bias: jax.Array | None = None,
    relu: bool = False,
    pool: int | None = None,
    schedule: Schedule | None = None,
    block_do: int | None = None,
    block_di: int | None = None,
    block_h: int | None = None,
    algorithm: str | None = None,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    out_dtype=None,
    interpret: bool | None = None,
    machine: MachineModel = TPU_V5E,
) -> jax.Array:
    """Convolutional layer forward (paper Algs 1/2) for arbitrary shapes.

    ``x``: [H, W, D_I] or [B, H, W, D_I]; ``f``: [F, F, D_I, D_O].  One
    batched ``pallas_call`` serves the whole batch (grid axis, not vmap);
    any stride runs in-kernel.  ``bias`` ([D_O]), ``relu`` and ``pool``
    (2 = fused 2x2 max-pool) execute in the kernel's flush step on the
    VMEM-resident output strip — no HBM round-trip between the conv and
    its epilogue.  Blocking: ``schedule`` > ``block_*`` pins > planner.

    The planner's argmin is *two-level*: algorithm x blocking.  When the
    im2col-GEMM family wins (or ``algorithm="im2col"`` pins it), the call
    executes the patch-matrix GEMM kernel (kernels/conv2d/im2col.py) with
    its delegated ``block_m/n/k`` blocking instead of the strip kernel.
    """
    d_out = f.shape[3]
    if bias is None:
        bias = jnp.zeros((d_out,), jnp.float32)
    return conv2d_op(
        x, f, bias,
        schedule=schedule, machine=machine, interpret=interpret,
        out_dtype=out_dtype or x.dtype,
        stride=stride, padding=padding, relu=relu, pool=int(pool or 1),
        block_do=block_do, block_di=block_di, block_h=block_h,
        algorithm=algorithm, block_m=block_m, block_n=block_n,
        block_k=block_k,
    )


def conv2d_with_mask(
    x: jax.Array,
    f: jax.Array,
    *,
    bias: jax.Array | None = None,
    stride: int = 1,
    padding: int = 0,
    pool: int = 1,
    schedule: Schedule | None = None,
    out_dtype=None,
    interpret: bool | None = None,
    machine: MachineModel = TPU_V5E,
) -> tuple[jax.Array, jax.Array | None]:
    """Conv + ReLU (+ fused pool) that also emits the epilogue-VJP mask.

    Identical output to :func:`conv2d` with ``relu=True``, plus the int8
    per-output-pixel mask (pool argmax position, or the ReLU liveness bit
    when ``pool == 1``) the backward pass scatters ``dY`` through instead
    of recomputing the convolution.  A ``algorithm="rows"`` schedule runs
    the row-GEMM kernel, whose flush stores the same mask.  Returns
    ``(out, None)`` — same ``out``, no mask — on the paths no flush can
    serve: an ``algorithm="im2col"`` schedule and the ragged-pool tail
    (``H_O`` or ``W_O`` not divisible by ``pool``); callers fall back to
    the recompute backward there.
    """
    from repro.plan import default_interpret, require_fit

    pool = int(pool or 1)
    if bias is None:
        bias = jnp.zeros((f.shape[3],), jnp.float32)
    F = f.shape[0]
    H_O = conv_out_extent(x.shape[-3], padding, F, stride)
    W_O = conv_out_extent(x.shape[-2], padding, F, stride)
    if schedule is None:
        schedule = conv2d_op.plan(
            x, f, bias, machine=machine,
            stride=stride, padding=padding, relu=True, pool=pool,
        )
    ragged = pool > 1 and _fused_pool(H_O, W_O, pool) == 1
    if ragged or getattr(schedule, "algorithm", "direct") == "im2col":
        out = conv2d(
            x, f, bias=bias, stride=stride, padding=padding, relu=True,
            pool=pool, schedule=schedule, out_dtype=out_dtype,
            interpret=interpret, machine=machine,
        )
        return out, None
    interpret = default_interpret(interpret)
    require_fit(schedule, interpret)
    if schedule.algorithm == "rows":
        from repro.kernels.conv2d.rows import conv2d_rows

        return conv2d_rows(
            x, f, bias, stride=stride, padding=padding, relu=True, pool=pool,
            schedule=schedule, out_dtype=out_dtype or x.dtype,
            interpret=interpret, emit_mask=True)
    return _conv2d_impl(
        x, f, bias, stride=stride, padding=padding, relu=True, pool=pool,
        schedule=schedule, out_dtype=out_dtype or x.dtype,
        interpret=interpret, emit_mask=True,
    )
