"""The paper's convolutional layer as a composable, differentiable module.

``strategy`` selects the paper algorithm:
  * "alg1"  - one output depth slice at a time (block_do = 1);
  * "alg2"  - Delta_O output stacking at the full-plane strip, Delta_O from
              the capacity planner;
  * "strip" - Alg 2 + spatial strip tiling: the planner trades strip height
              against Delta_O (the schedule the Pallas kernel actually runs);
  * "alg3"  - Alg 2 blocking within each device + ring input-slice reuse
              across devices (core/ring.py) when input channels are sharded.

Blocking flows through the ``repro.plan`` layer: each strategy is a
different constraint handed to :class:`repro.plan.ConvPlanner`, and an
explicit :class:`repro.plan.Schedule` (``schedule=``) overrides the
planner entirely.  Forward runs the batched strip-tiled Pallas kernel
(interpret mode off-TPU); :func:`conv_block` additionally fuses the layer
epilogue (bias + ReLU + optional 2x2 max-pool) into the kernel's flush
step.

Backward is *also* planned (DESIGN.md Sec. 4): ``jax.grad`` runs the
``conv2d_dgrad`` strip kernel (flipped-filter transposed conv) for dX and
the ``conv2d_wgrad`` accumulation kernel for dF, each scheduled by its own
planner — override with ``bwd_schedules={"dgrad": ..., "wgrad": ...,
"recompute": ...}`` (see :func:`plan_bwd`).  When a backward schedule does
not fit the machine the layer falls back to the XLA reference VJP, which
also remains the parity oracle (tests/test_backward_plan.py).  Traffic
accounting for any strategy comes from core/ccr.py.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from repro.core import ccr
from repro.core.machine import MANTICORE, TPU_V5E, machine_named
from repro.kernels.conv2d.bwd import conv2d_dgrad, conv2d_wgrad, epilogue_scatter
from repro.kernels.conv2d.ops import conv2d, conv2d_with_mask
from repro.kernels.conv2d.ref import conv2d_fused_ref, conv2d_ref, maxpool_ref
from repro.plan import (
    Schedule, ShardedSchedule, freeze_schedules, get_op, local_schedule,
    with_reference_vjp,
)

# The machine backward schedules are planned (and fit-checked) against.
_BWD_MACHINE = TPU_V5E

_WARNED_SCHEDULES: set = set()  # (role, schedule) pairs already reported


class UnfitScheduleWarning(UserWarning):
    """A backward fit gate dropped a layer to the XLA reference path.
    ``warnings.simplefilter("error", UnfitScheduleWarning)`` turns the
    drop into an error for a run that must stay on the planned kernels."""


def warn_unfit_schedule(role: str, sched: Schedule, machine) -> None:
    """Warn exactly once per (role, schedule) when a fit gate silently
    drops a pinned backward schedule to the XLA/recompute fallback —
    the autotune cache's loud-first-fallback discipline (see
    ``repro.plan.autotune._warn_once``) applied to the layers' gates.
    Steady-state replays of the same unfit cell stay quiet."""
    key = (role, sched)
    if key in _WARNED_SCHEDULES:
        return
    _WARNED_SCHEDULES.add(key)
    warnings.warn(
        f"backward schedule {role!r} (op={sched.op!r}, grid={sched.grid}) "
        f"overflows VMEM: working set {sched.vmem_bytes} B > "
        f"{machine.usable_for_working_set(2)} B usable on {machine.name!r}; "
        f"falling back to the XLA reference path",
        UnfitScheduleWarning, stacklevel=3)


def _strategy_blocks(strategy, x, f, stride, padding):
    """Map a paper strategy onto planner constraints (block_do, block_h)."""
    from repro.kernels.conv2d.ops import conv_out_extent

    block_do = 1 if strategy == "alg1" else None  # None -> capacity planner
    block_h = None if strategy in ("strip", "alg1") else -1  # -1 -> full plane
    if block_h == -1:
        block_h = max(1, conv_out_extent(x.shape[-3], padding, f.shape[0], stride))
    return block_do, block_h


def _planned_conv_backward(x, f, dy, stride, padding, sd, *, mask=None, pool=1):
    """dX/dW through the planned Pallas backward kernels; ``sd`` maps
    {"dgrad"/"wgrad": Schedule} overrides.  With ``mask``/``pool`` (the
    fused forward's epilogue-VJP residual) ``dy`` is the *pooled*
    cotangent and the kernels scatter it to full rate in-jit — the
    fused_epilogue backward, no recompute conv.  Returns None when a
    schedule does not fit the machine (or the geometry is out of the
    dgrad contract) — the caller then falls back to the XLA reference
    VJP, loudly on the first unfit cell."""
    F = f.shape[0]
    if padding > F - 1:
        return None
    out_hw = (x.shape[-3], x.shape[-2])
    s_dg = local_schedule(sd.get("dgrad"))  # sharded pins run their local blocking
    if s_dg is None:
        s_dg = get_op("conv2d_dgrad").plan(
            dy, f, stride=stride, padding=padding, out_hw=out_hw,
            mask=mask, pool=pool)
    s_wg = local_schedule(sd.get("wgrad"))
    if s_wg is None:
        s_wg = get_op("conv2d_wgrad").plan(
            x, dy, F=F, stride=stride, padding=padding, mask=mask, pool=pool)
    # Each schedule is fit-checked against the machine it was planned for
    # (a user-pinned Manticore schedule must not pass a TPU-sized gate).
    m_dg = machine_named(s_dg.machine, _BWD_MACHINE)
    m_wg = machine_named(s_wg.machine, _BWD_MACHINE)
    if not s_dg.fits(m_dg):
        warn_unfit_schedule("dgrad", s_dg, m_dg)
        return None
    if not s_wg.fits(m_wg):
        warn_unfit_schedule("wgrad", s_wg, m_wg)
        return None
    dx = conv2d_dgrad(dy, f, stride=stride, padding=padding, out_hw=out_hw,
                      mask=mask, pool=pool, schedule=s_dg,
                      out_dtype=jnp.float32)
    dw = conv2d_wgrad(x, dy, F=F, stride=stride, padding=padding,
                      mask=mask, pool=pool, schedule=s_wg,
                      out_dtype=jnp.float32)
    return dx.astype(x.dtype), dw.astype(f.dtype)


def _conv_layer_kernel(x, f, stride, padding, strategy, schedule, bwd_schedules):
    del bwd_schedules  # consumed by the backward pass
    block_do, block_h = _strategy_blocks(strategy, x, f, stride, padding)
    return conv2d(
        x, f, stride=stride, padding=padding, schedule=schedule,
        block_do=block_do, block_h=block_h,
    )


def _conv_layer_ref(x, f, stride, padding, strategy, schedule, bwd_schedules):
    del strategy, schedule, bwd_schedules  # schedule knobs never change numerics
    return conv2d_ref(x, f, stride=stride, padding=padding)


def _conv_layer_bwd(x, f, g, stride, padding, strategy, schedule, bwd_schedules):
    del strategy, schedule
    planned = _planned_conv_backward(
        x, f, g.astype(x.dtype), stride, padding, dict(bwd_schedules or ()))
    if planned is None:  # XLA reference VJP fallback
        _, vjp = jax.vjp(
            lambda xx, ff: conv2d_ref(xx, ff, stride=stride, padding=padding),
            x, f)
        return vjp(g)
    return planned


_conv_layer_vjp = with_reference_vjp(
    _conv_layer_kernel, _conv_layer_ref, nondiff_argnums=(2, 3, 4, 5, 6),
    bwd_fn=_conv_layer_bwd,
)


def conv_layer(x, f, stride=1, padding=0, strategy="alg2",
               schedule: Schedule | ShardedSchedule | None = None,
               bwd_schedules=None):
    """x: [B, H, W, D_I] or [H, W, D_I]; f: [F, F, D_I, D_O].

    ``schedule`` accepts either flavor — a ShardedSchedule contributes its
    per-device local blocking (a single-device mesh plan is exactly
    today's Schedule).  ``bwd_schedules`` optionally maps
    {"dgrad"/"wgrad": Schedule} to pin the planned backward kernels'
    blocking (see :func:`plan_bwd`)."""
    return _conv_layer_vjp(x, f, stride, padding, strategy,
                           local_schedule(schedule),
                           freeze_schedules(bwd_schedules))


def _conv_block_kernel(x, f, b, stride, padding, pool, schedule,
                       bwd_schedules):
    del bwd_schedules  # consumed by the backward pass
    return conv2d(x, f, bias=b, stride=stride, padding=padding, relu=True,
                  pool=pool, schedule=schedule)


def _conv_block_ref(x, f, b, stride, padding, pool, schedule, bwd_schedules):
    del schedule, bwd_schedules
    return conv2d_fused_ref(
        x, f, b, stride=stride, padding=padding, relu=True, pool=pool
    )


def _conv_block_fwd(x, f, b, stride, padding, pool, schedule, bwd_schedules):
    """The differentiated forward: same output as the primal kernel, plus
    the int8 epilogue-VJP mask as the auxiliary residual (None on the
    paths the fused flush can't emit it — im2col schedules, ragged pool
    tails — where the backward recomputes as before)."""
    del bwd_schedules  # consumed by the backward pass
    return conv2d_with_mask(
        x, f, bias=b, stride=stride, padding=padding, pool=pool,
        schedule=schedule)


def _conv_block_bwd(x, f, b, aux, g, stride, padding, pool, schedule,
                    bwd_schedules):
    del schedule
    sd = dict(bwd_schedules or ())
    g = g.astype(jnp.float32)
    if aux is not None:
        # Fused-epilogue backward: the saved int8 mask replaces the
        # recompute conv entirely — dY scatters through the pool-argmax /
        # ReLU-liveness mask inside the dgrad/wgrad kernels; the bias
        # gradient reads the same scattered full-rate dY (XLA CSE merges
        # this scatter with the kernels' identical in-jit prologue under
        # the one enclosing backward jit).
        dy_full = epilogue_scatter(g, aux, pool)
        db = dy_full.sum(tuple(range(dy_full.ndim - 1))).astype(b.dtype)
        planned = _planned_conv_backward(x, f, g.astype(x.dtype), stride,
                                         padding, sd, mask=aux, pool=pool)
        if planned is None:  # XLA reference VJP fallback for the conv itself
            _, vjp = jax.vjp(
                lambda xx, ff: conv2d_ref(xx, ff, stride=stride,
                                          padding=padding,
                                          out_dtype=jnp.float32), x, f)
            dx, dw = vjp(dy_full)
            dx, dw = dx.astype(x.dtype), dw.astype(f.dtype)
        else:
            dx, dw = planned
        return dx, dw, db
    # No mask residual: rematerialize the pre-pool activation with the
    # planned forward kernel (the fused forward never stores it), backprop
    # the elementwise/pool epilogue in XLA, then run the planned transposed
    # kernels on dY.  A pinned recompute Schedule gets the same fit gate as
    # dgrad/wgrad: if it overflows its machine, drop it (loudly, once) and
    # let the planner re-plan a fitting blocking instead of launching a
    # known-oversized kernel.
    recompute = local_schedule(sd.get("recompute"))
    if recompute is not None and not recompute.fits(
            machine_named(recompute.machine, _BWD_MACHINE)):
        warn_unfit_schedule(
            "recompute", recompute,
            machine_named(recompute.machine, _BWD_MACHINE))
        recompute = None
    y0 = conv2d(x, f, bias=b, stride=stride, padding=padding, relu=False,
                pool=1, schedule=recompute, out_dtype=jnp.float32)

    def _epilogue(y):
        y = jnp.maximum(y, 0.0)
        return maxpool_ref(y, pool) if pool > 1 else y

    _, evjp = jax.vjp(_epilogue, y0)
    dy, = evjp(g)
    db = dy.sum(tuple(range(dy.ndim - 1))).astype(b.dtype)
    planned = _planned_conv_backward(x, f, dy.astype(x.dtype), stride,
                                     padding, sd)
    if planned is None:  # XLA reference VJP fallback for the conv itself
        _, vjp = jax.vjp(
            lambda xx, ff: conv2d_ref(xx, ff, stride=stride, padding=padding,
                                      out_dtype=jnp.float32), x, f)
        dx, dw = vjp(dy)
        dx, dw = dx.astype(x.dtype), dw.astype(f.dtype)
    else:
        dx, dw = planned
    return dx, dw, db


_conv_block_vjp = with_reference_vjp(
    _conv_block_kernel, _conv_block_ref, nondiff_argnums=(3, 4, 5, 6, 7),
    bwd_fn=_conv_block_bwd, fwd_fn=_conv_block_fwd,
)


def conv_block(x, f, b, stride=1, padding=0, pool=1, strategy="strip",
               schedule: Schedule | None = None, bwd_schedules=None):
    """Fused conv + bias + ReLU (+ optional ``pool x pool`` max-pool).

    The whole epilogue runs in the Pallas kernel's flush step on the
    VMEM-resident output strip — the activation never round-trips HBM
    between the conv and the pool.  ``x``: [B, H, W, D_I] or [H, W, D_I];
    ``f``: [F, F, D_I, D_O]; ``b``: [D_O].  An explicit ``schedule``
    overrides the strategy's planner constraints; ``bwd_schedules``
    ({"dgrad"/"wgrad"/"recompute": Schedule}) pins the planned backward.
    A ``"rows"`` schedule (the planner's pick for inputs far narrower
    than a lane) runs the row-GEMM stage of kernels/conv2d/rows.py,
    forward and backward, wherever the pool tiles the plane.
    """
    from repro.kernels.conv2d.ops import _fused_pool, conv_out_extent

    schedule = local_schedule(schedule)
    if schedule is None:
        block_do, block_h = _strategy_blocks(strategy, x, f, stride, padding)
        bias = b if b is not None else jnp.zeros((f.shape[3],), jnp.float32)
        schedule = local_schedule(get_op("conv2d").plan(
            x, f, bias, stride=stride, padding=padding, relu=True,
            pool=pool, block_do=block_do, block_h=block_h))
    F = f.shape[0]
    H_O = conv_out_extent(x.shape[-3], padding, F, stride)
    W_O = conv_out_extent(x.shape[-2], padding, F, stride)
    if schedule.algorithm == "rows" and _fused_pool(H_O, W_O, pool) == pool:
        return _conv_rows_block(x, f, b, padding, pool, schedule,
                                dict(bwd_schedules or ()))
    return _conv_block_vjp(x, f, b, stride, padding, pool, schedule,
                           freeze_schedules(bwd_schedules))


def _conv_rows_block(x, f, b, padding, pool, schedule, sd):
    """The rows family's stage, its backward schedules from ``sd`` where
    they are rows schedules, else planned for the same machine."""
    from repro.kernels.conv2d.ops import conv_out_extent
    from repro.kernels.conv2d.rows import conv_rows_block
    from repro.plan import default_interpret, require_fit
    from repro.plan.planners import plan_rows

    F = f.shape[0]
    shape = dict(H_O=conv_out_extent(x.shape[-3], padding, F, 1),
                 W_O=conv_out_extent(x.shape[-2], padding, F, 1), F=F,
                 d_in=f.shape[2], d_out=f.shape[3],
                 in_bytes=x.dtype.itemsize, pool=pool,
                 batch=x.shape[0] if x.ndim == 4 else 1)
    machine = machine_named(schedule.machine, _BWD_MACHINE)
    bwd = {}
    for role, op in (("wgrad", "conv2d_wgrad"), ("dgrad", "conv2d_dgrad")):
        s = local_schedule(sd.get(role))
        if s is None or s.algorithm != "rows":
            s = plan_rows(op, machine, role, **shape)
        bwd[role] = s
    interpret = default_interpret(None)
    for s in (schedule, *bwd.values()):
        require_fit(s, interpret)
    return conv_rows_block(x, f, b, padding=padding, pool=pool,
                           schedule=schedule, interpret=interpret, **bwd)


def plan(
    x_shape, f_shape, *, stride=1, padding=0, pool=1, in_bytes=4,
    machine=None, strategy="strip", mesh=None, shard_axis="data",
    shard_strategy=None, autotune=None, algorithm=None,
):
    """Plan this layer without running it: the Schedule the kernel would
    use for operands of these shapes (report `.modeled_words` next to
    measured time, or pass it back in via ``schedule=``).  With ``mesh=``
    the mesh-aware planner returns a ShardedSchedule — the device
    partitioning ("batch" or "stack" data parallelism over
    ``shard_axis``, pinnable with ``shard_strategy=``) plus the HBM/ICI
    word split; a single-device mesh degenerates to today's Schedule.
    ``autotune`` ("off" | "cache-only" | "tune", default the process
    policy) lets a measured winner for this cell override the argmin.
    ``algorithm`` pins one family of the two-level argmin ("direct" /
    "im2col" / "rows"); the default lets them compete — the paper strategies
    ("alg1"/"alg2"/"alg3") pin direct-kernel blocks and therefore already
    imply the direct family."""
    from repro.core.machine import TPU_V5E
    from repro.kernels.conv2d.ops import _fused_pool, conv_out_extent
    from repro.plan import autotune as at

    machine = machine or TPU_V5E
    batched = len(x_shape) == 4
    B = x_shape[0] if batched else 1
    H, W, d_in = x_shape[-3], x_shape[-2], x_shape[-1]
    F, d_out = f_shape[0], f_shape[3]
    H_O = conv_out_extent(H, padding, F, stride)
    W_O = conv_out_extent(W, padding, F, stride)
    fused = _fused_pool(H_O, W_O, pool)
    block_do = 1 if strategy == "alg1" else None
    block_h = H_O if strategy in ("alg2", "alg3") else None
    return at.resolve("conv2d", dict(
        H_O=H_O, W_O=W_O, F=F, S=stride, d_in=d_in, d_out=d_out,
        in_bytes=in_bytes, pool=fused, batch=B, padding=padding,
        H_I=H, W_I=W, block_do=block_do, block_h=block_h,
        algorithm=algorithm,
    ), machine=machine, mesh=mesh, axis=shard_axis,
        strategy=shard_strategy, policy=autotune)


def plan_bwd(
    x_shape, f_shape, *, stride=1, padding=0, pool=None, in_bytes=4,
    machine=None, mesh=None, shard_axis="data", autotune=None,
) -> dict:
    """Backward-pass Schedules for this layer's shapes: the dgrad and
    wgrad kernels ``jax.grad`` will run, plus — on the recompute path
    only — the pre-epilogue recompute conv of :func:`conv_block`.  Pass
    (a subset of) the result back via ``bwd_schedules=`` to pin the
    blocking; sum ``.modeled_words`` to model the layer's training-step
    traffic.

    ``pool`` opts into the fused-epilogue backward: when given and the
    output plane tiles evenly (the fused forward emits the int8 mask
    residual), the dgrad cell is planned as its ``fused_epilogue``
    variant — dY scatters through the saved mask inside the kernels —
    and the "recompute" entry is dropped entirely (recompute_words = 0).
    A ragged pool (or ``pool=None``) keeps today's recompute plan.  Where
    the forward plans the rows family (fused pool), "dgrad" and "wgrad"
    are that family's backward kernels.

    Geometries outside the dgrad kernel's contract (padding > F-1, where
    the layer trains via the XLA fallback) return only the plannable
    subset — no "dgrad" key.  With ``mesh=`` every entry is a
    ShardedSchedule: dgrad and the recompute shard with the batch (no
    collective), while the sharded wgrad charges the Alg-4 tree reduction
    of dW as ici_words.  The backward cells autotune through the same
    ``autotune=`` policy as the forward (each op is its own cache cell).
    """
    from repro.kernels.conv2d.ops import _fused_pool, conv_out_extent
    from repro.plan import autotune as at

    machine = machine or _BWD_MACHINE
    batched = len(x_shape) == 4
    B = x_shape[0] if batched else 1
    H, W, d_in = x_shape[-3], x_shape[-2], x_shape[-1]
    F, d_out = f_shape[0], f_shape[3]
    H_O = conv_out_extent(H, padding, F, stride)
    W_O = conv_out_extent(W, padding, F, stride)
    fused = pool is not None and _fused_pool(H_O, W_O, pool) == pool

    def res(op, **shape):
        return at.resolve(op, shape, machine=machine, mesh=mesh,
                          axis=shard_axis, policy=autotune)

    if fused and plan(x_shape, f_shape, stride=stride, padding=padding,
                      pool=pool, in_bytes=in_bytes, machine=machine,
                      mesh=mesh, shard_axis=shard_axis,
                      autotune=autotune).algorithm == "rows":
        # The rows forward's backward kernels read its mask and views.
        return {
            role: res(f"conv2d_{role}", H_O=H_O, W_O=W_O, F=F, S=stride,
                      d_in=d_in, d_out=d_out, in_bytes=in_bytes, batch=B,
                      pool=pool, algorithm="rows")
            for role in ("wgrad", "dgrad")}
    out = {
        "wgrad": res(
            "conv2d_wgrad",
            H_O=H_O, W_O=W_O, F=F, S=stride, d_in=d_in, d_out=d_out,
            in_bytes=in_bytes, batch=B, padding=padding, H_I=H, W_I=W),
    }
    if not fused:
        out["recompute"] = res(
            "conv2d",
            H_O=H_O, W_O=W_O, F=F, S=stride, d_in=d_in, d_out=d_out,
            in_bytes=in_bytes, pool=1, batch=B, padding=padding,
            H_I=H, W_I=W)
    if padding <= F - 1:
        out["dgrad"] = res(
            "conv2d_dgrad",
            H_O=H_O, W_O=W_O, F=F, S=stride, P=padding, d_in=d_in,
            d_out=d_out, in_bytes=in_bytes, batch=B, H_I=H, W_I=W,
            pool=pool if fused else None)
    return out


def traffic(
    shape: ccr.ConvShape, strategy: str = "alg2", precision: str = "sp",
    machine=MANTICORE, h_block: int | None = None,
) -> ccr.Traffic:
    """Predicted word traffic for this layer under the chosen algorithm."""
    if strategy == "alg1":
        return ccr.alg1_traffic(shape)
    if strategy == "alg2":
        return ccr.alg2_traffic(shape, max(1, ccr.alg2_max_stack(shape, machine, precision)))
    if strategy == "strip":
        hb = h_block or max(1, shape.W_O // 2)
        stack = max(1, ccr.alg2_strip_max_stack(shape, machine, precision, hb))
        return ccr.alg2_strip_traffic(shape, stack, hb)
    if strategy == "alg3":
        return ccr.alg3_traffic(shape, max(1, ccr.alg3_max_stack(shape, machine, precision)))
    raise ValueError(strategy)
