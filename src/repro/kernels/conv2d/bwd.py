"""Planned backward kernels for the strip-tiled conv2d pipeline.

Two first-class ``pallas_op`` registrations (DESIGN.md Sec. 4):

* ``conv2d_dgrad`` — the input gradient.  dX is a *stride-1* strip conv
  over the S-dilated gradient with spatially flipped, channel-swapped
  filters, so it runs the forward kernel (:func:`conv2d_fused_pallas`)
  verbatim on that transposed geometry: halo-overlapped gradient strips,
  Delta_I output stacking, same VMEM accumulator discipline.  The
  dilation + transposed zero padding happen in one ``lax.pad``.
* ``conv2d_wgrad`` — the filter gradient.  dW[ky, kx] accumulates
  X_strip^T @ dY_strip over a (d_i-block, d_o-stack, batch, strip) grid;
  the F^2 x block_di x block_do f32 accumulator is the VMEM-resident
  output stack (it never round-trips HBM between batch elements or
  strips) and flushes exactly once on the last (batch, strip) step.

Both ops take an optional ``mask``/``pool`` pair — the int8 pool-argmax/
ReLU mask the forward fused kernel emitted as a residual.  When given,
:func:`epilogue_scatter` runs as the kernel's *prologue inside the same
jit*: the pooled cotangent scatters through the mask into the full-rate
dY both kernels then stream, replacing the old recompute path's full
forward-conv re-run (XLA CSE de-duplicates the scatter between dgrad and
wgrad, so the cost model charges it once, on the dgrad schedule).

Two pipelined execution variants ride on the schedules' ``algorithm``
tag (any other tag runs the plain BlockSpec pipeline — identical
numerics, serialized streams):

* dgrad ``"fused_epilogue"`` folds the d_out stream inside each grid step
  behind a double-buffered async-copy loop (the dY-strip fetch overlaps
  the filter stream), dropping the grid's stream dimension;
* wgrad ``"pipelined"`` folds the whole (batch, strip) accumulation sweep
  inside each (d_i, d_o) step the same way.

A ``"rows"`` tag runs the row-GEMM family's backward kernels instead
(kernels/conv2d/rows.py), behind its forward's mask.

Both wrappers compute on a width padded up to the sublane tile
(:func:`_sublane`): the manual DMA copies whole rows of the padded input,
and the TPU only copies slices whose second-minor extent is a multiple of
the tile.  The extra columns carry zero gradient (wgrad) or are sliced
off (dgrad), so the results are exact.

Blocking comes from :class:`repro.plan.ConvDgradPlanner` /
:class:`repro.plan.ConvWgradPlanner`; an explicit ``schedule=`` overrides
the planner, mirroring the forward wrapper contract.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.machine import TPU_V5E, MachineModel, machine_named
from repro.kernels.compiler_params import compiler_params
from repro.kernels.conv2d.conv2d import conv2d_fused_pallas
from repro.plan import ConvDgradPlanner, ConvWgradPlanner, Schedule, pad_dim, pallas_op
from repro.plan.planners import round_up as _round_up

_LANE = 128


def _sublane(dtype) -> int:
    """Second-minor tile of a TPU array of ``dtype``: 8 rows of 32-bit
    words, packed 2x for 16-bit and 4x for 8-bit elements."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


# ---------------------------------------------------------------------------
# Fused epilogue VJP: scatter dY through the saved pool-argmax/ReLU mask
# ---------------------------------------------------------------------------


def epilogue_scatter(g: jax.Array, mask: jax.Array, pool: int) -> jax.Array:
    """The epilogue VJP from the saved mask: route the pooled cotangent
    ``g`` [..., Hp, Wp, C] to the argmax position of each pool window
    (zero elsewhere, per the int8 mask — index in [0, pool^2), or pool^2
    for a dead all-ReLU-clamped window), returning the full-rate dY
    [..., Hp*pool, Wp*pool, C] in f32.  With ``pool == 1`` the mask is the
    ReLU liveness bit (0 alive, 1 dead).  Winner-take-all on exact
    pool-window ties, where the XLA reference VJP splits evenly — a
    measure-zero difference off the ReLU-dead case (which both zero)."""
    m = mask.astype(jnp.int32)
    g = g.astype(jnp.float32)
    if pool == 1:
        return jnp.where(m == 0, g, 0.0)
    p2 = pool * pool
    oh = jax.nn.one_hot(m, p2, dtype=g.dtype)  # dead index p2 -> zero row
    d = g[..., None] * oh
    *lead, hp, wp, c, _ = d.shape
    d = d.reshape(*lead, hp, wp, c, pool, pool)
    # (..., Hp, Wp, C, py, px) -> (..., Hp, py, Wp, px, C)
    off = len(lead)
    perm = tuple(range(off)) + tuple(off + i for i in (0, 3, 1, 4, 2))
    return d.transpose(perm).reshape(*lead, hp * pool, wp * pool, c)


# ---------------------------------------------------------------------------
# dgrad: dX via the forward strip kernel on the transposed geometry
# ---------------------------------------------------------------------------


def dgrad_out_extent(out: int, F: int, stride: int, padding: int) -> int:
    """Default dX extent for one axis: the exact-cover forward input
    (H_O - 1)*S + F - 2P.  A forward input larger than this (ragged
    stride) still back-propagates into its first min(H_I, (H_O-1)S+F-P)
    rows — pass the true extent via ``out_hw`` and the kernel computes
    those rows (the rest are zeros it also produces)."""
    return (out - 1) * stride + F - 2 * padding


def conv2d_dgrad_ref(dy, f, *, stride: int = 1, padding: int = 0, out_hw=None):
    """XLA oracle: the VJP of conv2d_ref with respect to its input."""
    from repro.kernels.conv2d.ref import conv2d_ref

    F = f.shape[0]
    d_in = f.shape[2]
    H_O, W_O = dy.shape[-3], dy.shape[-2]
    H_I, W_I = out_hw if out_hw is not None else (
        dgrad_out_extent(H_O, F, stride, padding),
        dgrad_out_extent(W_O, F, stride, padding),
    )
    shape = dy.shape[:-3] + (H_I, W_I, d_in)
    x0 = jnp.zeros(shape, jnp.float32)
    _, vjp = jax.vjp(
        lambda x: conv2d_ref(x, f, stride=stride, padding=padding,
                             out_dtype=jnp.float32), x0)
    return vjp(dy.astype(jnp.float32))[0]


def _dgrad_shape_args(dy, f, *, stride=1, padding=0, out_hw=None,
                      mask=None, pool=1,
                      block_h=None, block_do=None, block_di=None):
    """Planner shapes (forward-layer terms) from concrete operands;
    ``out_hw`` is the dX extent the kernel actually produces.  With a
    mask residual ``dy`` is the *pooled* cotangent: the full-rate extents
    are scaled back up and the pool factor (never the traced mask array —
    plans are cached on hashable shapes) rides into the planner, which
    then defaults to the fused_epilogue variant."""
    batched = dy.ndim == 4
    B = dy.shape[0] if batched else 1
    H_O, W_O, d_out = dy.shape[-3], dy.shape[-2], dy.shape[-1]
    if mask is not None:
        H_O, W_O = H_O * pool, W_O * pool
    H_I, W_I = out_hw if out_hw is not None else (None, None)
    return dict(
        H_O=H_O, W_O=W_O, F=f.shape[0], S=stride, P=padding,
        d_in=f.shape[2], d_out=d_out, in_bytes=dy.dtype.itemsize, batch=B,
        H_I=H_I, W_I=W_I, pool=pool if mask is not None else None,
        block_h=block_h, block_do=block_do, block_di=block_di,
    )


def _dgrad_dma_kernel(x_hbm, f_hbm, o_ref, acc_ref, *, n_di: int, F: int,
                      block_h: int, W_O: int, block_di: int, block_do: int,
                      h_halo: int):
    """The fused_epilogue dgrad step: the d_out stream runs *inside* the
    grid step as a manually double-buffered async-copy loop — the dY-strip
    slab for the next d_out block is in flight while the current slab's
    F^2 matmuls accumulate (DmaLoad/DmaWait prefetch, by hand)."""
    b, h, do = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    def body(xs, fs, sem):
        def copies(di, slot):
            return (
                pltpu.make_async_copy(
                    x_hbm.at[b, pl.ds(h * block_h, h_halo), :,
                             pl.ds(di * block_di, block_di)],
                    xs.at[slot], sem.at[slot, 0]),
                pltpu.make_async_copy(
                    f_hbm.at[:, :, pl.ds(di * block_di, block_di),
                             pl.ds(do * block_do, block_do)],
                    fs.at[slot], sem.at[slot, 1]),
            )

        def start(di, slot):
            for c in copies(di, slot):
                c.start()

        def wait(di, slot):
            for c in copies(di, slot):
                c.wait()

        acc_ref[...] = jnp.zeros_like(acc_ref)
        start(0, 0)  # pipeline fill: warm-up fetch of the first slab

        def step(di, carry):
            slot = jax.lax.rem(di, 2)

            @pl.when(di + 1 < n_di)
            def _prefetch():  # next slab's DMA overlaps this slab's MACs
                start(di + 1, jax.lax.rem(di + 1, 2))

            wait(di, slot)
            x = xs[slot]
            fblk = fs[slot]
            for ky in range(F):  # stride-1 conv: F^2 shifted MXU matmuls
                for kx in range(F):
                    win = jax.lax.slice(
                        x, (ky, kx, 0),
                        (ky + block_h, kx + W_O, block_di),
                    ).reshape(block_h * W_O, block_di)
                    acc_ref[...] += jnp.dot(
                        win, fblk[ky, kx],
                        preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, n_di, step, 0)
        o_ref[0] = acc_ref[...].reshape(block_h, W_O, -1).astype(o_ref.dtype)

    pl.run_scoped(
        body,
        xs=pltpu.VMEM((2, h_halo, x_hbm.shape[2], block_di), x_hbm.dtype),
        fs=pltpu.VMEM((2, F, F, block_di, block_do), f_hbm.dtype),
        sem=pltpu.SemaphoreType.DMA((2, 2)),
    )


def _dgrad_dma_pallas(x_pad, f, *, block_h: int, block_do: int,
                      block_di: int, H_O: int, W_O: int, out_dtype,
                      interpret: bool, machine: MachineModel = TPU_V5E):
    """Double-buffered fused_epilogue dgrad: grid (B, strip, dX stack)
    with the d_in-side stream folded in-kernel.  Same operands and result
    as stride-1 relu/pool-free :func:`conv2d_fused_pallas`."""
    B, H_in, W_in, d_in = x_pad.shape
    F, F2, d_in2, d_out = f.shape
    assert F == F2 and d_in == d_in2
    assert d_in % block_di == 0 and d_out % block_do == 0
    n_h = -(-H_O // block_h)
    assert H_in >= (n_h * block_h - 1) + F
    assert W_in >= (W_O - 1) + F
    kernel = functools.partial(
        _dgrad_dma_kernel, n_di=d_in // block_di, F=F, block_h=block_h,
        W_O=W_O, block_di=block_di, block_do=block_do,
        h_halo=block_h - 1 + F,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, n_h, d_out // block_do),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # streamed by hand
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (1, block_h, W_O, block_do), lambda b, h, do: (b, h, 0, do)),
        out_shape=jax.ShapeDtypeStruct(
            (B, n_h * block_h, W_O, d_out), out_dtype or x_pad.dtype),
        scratch_shapes=[pltpu.VMEM((block_h * W_O, block_do), jnp.float32)],
        compiler_params=compiler_params(
            machine, "parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(x_pad, f)


@functools.partial(
    jax.jit,
    static_argnames=("stride", "padding", "out_hw", "pool", "schedule",
                     "out_dtype", "interpret"),
)
def _dgrad_impl_jit(dy, f, mask, *, stride, padding, out_hw, pool, schedule,
                    out_dtype, interpret):
    batched = dy.ndim == 4
    if not batched:
        dy = dy[None]
        if mask is not None:
            mask = mask[None]
    if mask is not None:
        # Fused epilogue VJP prologue: rebuild the full-rate dY from the
        # pooled cotangent and the saved mask, inside this jit (the twin
        # scatter in the wgrad jit CSEs away when both run under one
        # enclosing backward jit).
        dy = epilogue_scatter(dy, mask, pool).astype(dy.dtype)
    B, H_O, W_O, d_out = dy.shape
    F = f.shape[0]
    d_in = f.shape[2]
    S, P = stride, padding
    assert P <= F - 1, f"dgrad needs padding <= F-1, got {P} for F={F}"
    H_I, W_I = out_hw if out_hw is not None else (
        dgrad_out_extent(H_O, F, S, P), dgrad_out_extent(W_O, F, S, P))
    pt = F - 1 - P  # transposed padding

    bdi = schedule.block("block_di", min(_round_up(d_out, _LANE), 512))
    hb = max(1, min(schedule.block("block_h", H_I), H_I))
    bdo = min(schedule.block("block_do", _LANE), _round_up(d_in, _LANE))
    if interpret:
        # Interpret mode has no 128-lane MXU: clamp channel blocks that
        # already cover their extent down to it, so off-TPU runs don't
        # multiply lane-padding zeros (128x waste at CNN channel counts).
        # Only a covering block shrinks, so every grid extent — and with
        # it critical_path_steps — is unchanged.
        if bdi >= d_out:
            bdi = max(1, d_out)
        if bdo >= d_in:
            bdo = max(1, d_in)

    n_h = -(-H_I // hb)
    H_dil, W_dil = (H_O - 1) * S + 1, (W_O - 1) * S + 1
    # The stride-1 conv over the dilated gradient produces all H_I rows of
    # dX directly: rows past the dilated extent read pure zero padding and
    # come out zero (a ragged-stride forward input leaves such rows).
    # Columns are computed out to the sublane tile (W_k) and sliced off.
    sub = _sublane(dy.dtype)
    W_k = _round_up(W_I, sub)
    rows_needed = (n_h * hb - 1) + F
    pad_bottom = pt + max(0, rows_needed - (H_dil + 2 * pt))
    cols = _round_up(max(W_dil + 2 * pt, (W_k - 1) + F), sub)
    pad_right = cols - W_dil - pt
    # One lax.pad: S-1 interior zeros (dilation) + transposed edge padding.
    xp = jax.lax.pad(dy, jnp.zeros((), dy.dtype),
                     ((0, 0, 0), (pt, pad_bottom, S - 1), (pt, pad_right, S - 1),
                      (0, 0, 0)))
    dip, dop = _round_up(d_out, bdi), _round_up(d_in, bdo)
    xp = pad_dim(xp, 3, dip)
    # Spatially flipped, channel-swapped filters: [F, F, D_O, D_I].
    ft = jnp.flip(f, (0, 1)).transpose(0, 1, 3, 2)
    ftp = pad_dim(pad_dim(ft, 2, dip), 3, dop)
    bias = jnp.zeros((1, dop), jnp.float32)

    machine = machine_named(schedule.machine)
    if getattr(schedule, "algorithm", "direct") == "fused_epilogue":
        out = _dgrad_dma_pallas(
            xp, ftp, block_h=hb, block_do=bdo, block_di=bdi,
            H_O=H_I, W_O=W_k, out_dtype=out_dtype, interpret=interpret,
            machine=machine,
        )
    else:
        out = conv2d_fused_pallas(
            xp, ftp, bias, stride=1, block_h=hb, block_do=bdo, block_di=bdi,
            H_O=H_I, W_O=W_k, relu=False, pool=1,
            out_dtype=out_dtype, interpret=interpret, machine=machine,
        )
    dx = out[:, :H_I, :W_I, :d_in]
    return dx if batched else dx[0]


def _dgrad_impl(dy, f, *, schedule, out_dtype, interpret, stride=1, padding=0,
                out_hw=None, mask=None, pool=1, block_h=None, block_do=None,
                block_di=None):
    del block_h, block_do, block_di  # consumed by the planner
    if schedule.algorithm == "rows":  # behind the row-GEMM forward
        from repro.kernels.conv2d.rows import conv2d_rows_dgrad

        return conv2d_rows_dgrad(
            dy, f, mask, stride=stride, padding=padding, out_hw=out_hw,
            pool=pool, schedule=schedule, out_dtype=out_dtype,
            interpret=interpret)
    return _dgrad_impl_jit(
        dy, f, mask, stride=stride, padding=padding, out_hw=out_hw,
        pool=pool, schedule=schedule, out_dtype=out_dtype,
        interpret=interpret,
    )


dgrad_op = pallas_op(
    "conv2d_dgrad",
    planner=ConvDgradPlanner,
    shape_args=_dgrad_shape_args,
    impl=_dgrad_impl,
    reference=conv2d_dgrad_ref,
)


def conv2d_dgrad(
    dy: jax.Array,
    f: jax.Array,
    *,
    stride: int = 1,
    padding: int = 0,
    out_hw: tuple[int, int] | None = None,
    mask: jax.Array | None = None,
    pool: int = 1,
    schedule: Schedule | None = None,
    block_h: int | None = None,
    block_do: int | None = None,
    block_di: int | None = None,
    out_dtype=None,
    interpret: bool | None = None,
    machine: MachineModel = TPU_V5E,
) -> jax.Array:
    """Input gradient of :func:`repro.kernels.conv2d.ops.conv2d`.

    ``dy``: [B, H_O, W_O, D_O] or [H_O, W_O, D_O] cotangent of the conv
    output; ``f``: [F, F, D_I, D_O] the forward filters.  Runs the forward
    strip kernel on the S-dilated, (F-1-P)-padded gradient with flipped,
    channel-swapped filters.  ``out_hw`` = (H_I, W_I) of the forward input
    pads the result up to the true input extent (ragged strides leave
    trailing zero-gradient rows).

    With ``mask``/``pool`` (the forward fused kernel's int8 epilogue-VJP
    residual), ``dy`` is the *pooled* post-epilogue cotangent:
    :func:`epilogue_scatter` rebuilds the full-rate conv-output gradient
    in-jit before the kernel runs — no recompute conv.  Blocking:
    ``schedule`` > ``block_*`` pins > ConvDgradPlanner.
    """
    return dgrad_op(
        dy, f, schedule=schedule, machine=machine, interpret=interpret,
        out_dtype=out_dtype or dy.dtype, stride=stride, padding=padding,
        out_hw=out_hw, mask=mask, pool=pool,
        block_h=block_h, block_do=block_do, block_di=block_di,
    )


# ---------------------------------------------------------------------------
# wgrad: dW accumulated over the (batch, strip) grid
# ---------------------------------------------------------------------------


def conv2d_wgrad_ref(x, dy, *, F: int, stride: int = 1, padding: int = 0):
    """XLA oracle: the VJP of conv2d_ref with respect to its filters."""
    from repro.kernels.conv2d.ref import conv2d_ref

    f0 = jnp.zeros((F, F, x.shape[-1], dy.shape[-1]), jnp.float32)
    _, vjp = jax.vjp(
        lambda f: conv2d_ref(x, f, stride=stride, padding=padding,
                             out_dtype=jnp.float32), f0)
    return vjp(dy.astype(jnp.float32))[0]


def _wgrad_kernel(x_ref, g_ref, o_ref, acc_ref, *,
                  n_b: int, n_h: int, F: int, S: int, block_h: int, W_O: int):
    b, h = pl.program_id(2), pl.program_id(3)

    @pl.when((b == 0) & (h == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)  # dW stack starts at zero

    x = x_ref[0]  # [(block_h-1)*S+F, W_in, bdi] halo'd input strip block
    bdi = x.shape[-1]
    g = g_ref[0].reshape(block_h * W_O, -1)  # [strip rows, bdo] gradient
    # dW[ky, kx] += win^T @ g — F^2 transposed MXU matmuls per strip.
    for ky in range(F):
        for kx in range(F):
            win = jax.lax.slice(
                x,
                (ky, kx, 0),
                (ky + (block_h - 1) * S + 1, kx + (W_O - 1) * S + 1, bdi),
                (S, S, 1),
            ).reshape(block_h * W_O, bdi)
            acc_ref[ky, kx] += jax.lax.dot_general(
                win, g, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when((b == n_b - 1) & (h == n_h - 1))
    def _flush():  # single DmaStore of the accumulated filter gradient
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def conv2d_wgrad_pallas(
    x_pad: jax.Array,
    dy: jax.Array,
    *,
    F: int,
    stride: int,
    block_h: int,
    block_do: int,
    block_di: int,
    H_O: int,
    W_O: int,
    out_dtype=None,
    interpret: bool = False,
    machine: MachineModel = TPU_V5E,
) -> jax.Array:
    """Filter gradient over the (d_i, d_o, batch, strip) grid.

    ``x_pad``: [B, H_in, W_in, D_I] spatially pre-padded inputs with
    H_in >= (n_h*block_h - 1)*stride + F; ``dy``: [B, n_h*block_h, W_O,
    D_O] with rows beyond H_O zero-padded (zero rows contribute nothing).
    D_I, D_O must be multiples of the channel blocks.  Returns
    [F, F, D_I, D_O].
    """
    B, H_in, W_in, d_in = x_pad.shape
    B2, H_g, W_g, d_out = dy.shape
    assert B == B2 and W_g == W_O, (x_pad.shape, dy.shape, W_O)
    n_h = H_g // block_h
    assert n_h * block_h == H_g and n_h == -(-H_O // block_h)
    assert d_in % block_di == 0 and d_out % block_do == 0
    assert H_in >= (n_h * block_h - 1) * stride + F
    assert W_in >= (W_O - 1) * stride + F
    h_halo = (block_h - 1) * stride + F
    out_dtype = out_dtype or x_pad.dtype

    kernel = functools.partial(
        _wgrad_kernel, n_b=B, n_h=n_h, F=F, S=stride,
        block_h=block_h, W_O=W_O,
    )
    return pl.pallas_call(
        kernel,
        grid=(d_in // block_di, d_out // block_do, B, n_h),
        in_specs=[
            # Halo-overlapped input strip block (element offsets, every
            # dim a pl.Element), indexed by (batch, strip, d_i-block):
            # re-streamed once per d_o stack.
            pl.BlockSpec(
                (pl.Element(1), pl.Element(h_halo), pl.Element(W_in),
                 pl.Element(block_di)),
                lambda di, do, b, h: (b, h * block_h * stride, 0,
                                      di * block_di),
            ),
            # Gradient strip for the d_o stack: re-streamed once per
            # d_i-block.
            pl.BlockSpec((1, block_h, W_O, block_do),
                         lambda di, do, b, h: (b, h, 0, do)),
        ],
        # The dW block ignores (b, h): it stays VMEM-resident across the
        # whole batch/strip sweep and is written once at the flush.
        out_specs=pl.BlockSpec((F, F, block_di, block_do),
                               lambda di, do, b, h: (0, 0, di, do)),
        out_shape=jax.ShapeDtypeStruct((F, F, d_in, d_out), out_dtype),
        scratch_shapes=[pltpu.VMEM((F, F, block_di, block_do), jnp.float32)],
        compiler_params=compiler_params(
            machine, "parallel", "parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
    )(x_pad, dy)


def _wgrad_shape_args(x, dy, *, F, stride=1, padding=0, mask=None, pool=1,
                      block_h=None, block_do=None, block_di=None):
    batched = x.ndim == 4
    B = x.shape[0] if batched else 1
    H, W, d_in = x.shape[-3], x.shape[-2], x.shape[-1]
    H_O, W_O, d_out = dy.shape[-3], dy.shape[-2], dy.shape[-1]
    if mask is not None:
        # dy is the pooled cotangent: the kernel streams the scattered
        # full-rate gradient, so the planner models the scaled extents.
        # (The mask array itself never enters the dict — plans are cached
        # on hashable shapes; the scatter is charged on the dgrad side.)
        H_O, W_O = H_O * pool, W_O * pool
    return dict(
        H_O=H_O, W_O=W_O, F=F, S=stride, d_in=d_in, d_out=d_out,
        in_bytes=x.dtype.itemsize, batch=B, padding=padding, H_I=H, W_I=W,
        block_h=block_h, block_do=block_do, block_di=block_di,
    )


def _wgrad_dma_kernel(x_hbm, g_hbm, o_ref, acc_ref, *, n_b: int, n_h: int,
                      F: int, S: int, block_h: int, W_O: int, block_di: int,
                      block_do: int, h_halo: int):
    """The pipelined wgrad step: the whole (batch, strip) accumulation
    sweep is folded inside each (d_i, d_o) grid step as a manually
    double-buffered async-copy loop — the next strip's X/dY slabs are in
    flight while the current strip's F^2 transposed matmuls accumulate."""
    di, do = pl.program_id(0), pl.program_id(1)

    def body(xs, gs, sem):
        def copies(t, slot):
            b = t // n_h
            h = jax.lax.rem(t, n_h)
            return (
                pltpu.make_async_copy(
                    x_hbm.at[b, pl.ds(h * block_h * S, h_halo), :,
                             pl.ds(di * block_di, block_di)],
                    xs.at[slot], sem.at[slot, 0]),
                pltpu.make_async_copy(
                    g_hbm.at[b, pl.ds(h * block_h, block_h), :,
                             pl.ds(do * block_do, block_do)],
                    gs.at[slot], sem.at[slot, 1]),
            )

        def start(t, slot):
            for c in copies(t, slot):
                c.start()

        def wait(t, slot):
            for c in copies(t, slot):
                c.wait()

        acc_ref[...] = jnp.zeros_like(acc_ref)
        T = n_b * n_h
        start(0, 0)  # pipeline fill: warm-up fetch of the first strip

        def step(t, carry):
            slot = jax.lax.rem(t, 2)

            @pl.when(t + 1 < T)
            def _prefetch():  # next strip's DMA overlaps this strip's MACs
                start(t + 1, jax.lax.rem(t + 1, 2))

            wait(t, slot)
            x = xs[slot]
            g = gs[slot].reshape(block_h * W_O, block_do)
            for ky in range(F):
                for kx in range(F):
                    win = jax.lax.slice(
                        x, (ky, kx, 0),
                        (ky + (block_h - 1) * S + 1,
                         kx + (W_O - 1) * S + 1, block_di),
                        (S, S, 1),
                    ).reshape(block_h * W_O, block_di)
                    acc_ref[ky, kx] += jax.lax.dot_general(
                        win, g, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, T, step, 0)
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    pl.run_scoped(
        body,
        xs=pltpu.VMEM((2, h_halo, x_hbm.shape[2], block_di), x_hbm.dtype),
        gs=pltpu.VMEM((2, block_h, W_O, block_do), g_hbm.dtype),
        sem=pltpu.SemaphoreType.DMA((2, 2)),
    )


def _wgrad_dma_pallas(x_pad, dy, *, F: int, stride: int, block_h: int,
                      block_do: int, block_di: int, H_O: int, W_O: int,
                      out_dtype, interpret: bool,
                      machine: MachineModel = TPU_V5E):
    """Double-buffered pipelined wgrad: grid (d_i, d_o) with the whole
    (batch, strip) sweep folded in-kernel.  Same operands and result as
    :func:`conv2d_wgrad_pallas`."""
    B, H_in, W_in, d_in = x_pad.shape
    B2, H_g, W_g, d_out = dy.shape
    assert B == B2 and W_g == W_O, (x_pad.shape, dy.shape, W_O)
    n_h = H_g // block_h
    assert n_h * block_h == H_g and n_h == -(-H_O // block_h)
    assert d_in % block_di == 0 and d_out % block_do == 0
    assert H_in >= (n_h * block_h - 1) * stride + F
    assert W_in >= (W_O - 1) * stride + F
    kernel = functools.partial(
        _wgrad_dma_kernel, n_b=B, n_h=n_h, F=F, S=stride, block_h=block_h,
        W_O=W_O, block_di=block_di, block_do=block_do,
        h_halo=(block_h - 1) * stride + F,
    )
    return pl.pallas_call(
        kernel,
        grid=(d_in // block_di, d_out // block_do),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # streamed by hand
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((F, F, block_di, block_do),
                               lambda di, do: (0, 0, di, do)),
        out_shape=jax.ShapeDtypeStruct(
            (F, F, d_in, d_out), out_dtype or x_pad.dtype),
        scratch_shapes=[pltpu.VMEM((F, F, block_di, block_do), jnp.float32)],
        compiler_params=compiler_params(machine, "arbitrary", "arbitrary"),
        interpret=interpret,
    )(x_pad, dy)


@functools.partial(
    jax.jit,
    static_argnames=("F", "stride", "padding", "pool", "schedule",
                     "out_dtype", "interpret"),
)
def _wgrad_impl_jit(x, dy, mask, *, F, stride, padding, pool, schedule,
                    out_dtype, interpret):
    batched = x.ndim == 4
    if not batched:
        x, dy = x[None], dy[None]
        if mask is not None:
            mask = mask[None]
    if mask is not None:
        # Fused epilogue VJP prologue (see _dgrad_impl_jit; under one
        # enclosing backward jit, XLA CSEs this with the dgrad twin).
        dy = epilogue_scatter(dy, mask, pool).astype(dy.dtype)
    B, H, W, d_in = x.shape
    _, H_O, W_O, d_out = dy.shape
    S, P = stride, padding

    bdi = schedule.block("block_di", min(_round_up(d_in, _LANE), 512))
    hb = max(1, min(schedule.block("block_h", H_O), H_O))
    bdo = min(schedule.block("block_do", _LANE), _round_up(d_out, _LANE))
    if interpret:
        # See _dgrad_impl_jit: shrink covering channel blocks off-TPU so
        # interpret mode doesn't grind through 128-lane padding; grid
        # extents (and critical_path_steps) are unchanged.
        if bdi >= d_in:
            bdi = max(1, d_in)
        if bdo >= d_out:
            bdo = max(1, d_out)

    n_h = -(-H_O // hb)
    rows_needed = (n_h * hb - 1) * S + F
    pad_bottom = P + max(0, rows_needed - (H + 2 * P))
    # Output columns run out to the sublane tile of both operands (the
    # padded dY columns are zero, so they add nothing to dW), and the input
    # is padded wide enough for them and tile-aligned itself.
    sub = max(_sublane(x.dtype), _sublane(dy.dtype))
    W_k = _round_up(W_O, sub)
    cols = _round_up(max(W + 2 * P, (W_k - 1) * S + F), sub)
    dip, dop = _round_up(d_in, bdi), _round_up(d_out, bdo)
    xp = jnp.pad(x, ((0, 0), (P, pad_bottom), (P, cols - W - P), (0, 0)))
    xp = pad_dim(xp, 3, dip)
    gp = pad_dim(pad_dim(pad_dim(dy, 1, n_h * hb), 2, W_k), 3, dop)

    machine = machine_named(schedule.machine)
    if getattr(schedule, "algorithm", "direct") == "pipelined":
        dw = _wgrad_dma_pallas(
            xp, gp, F=F, stride=S, block_h=hb, block_do=bdo, block_di=bdi,
            H_O=H_O, W_O=W_k, out_dtype=out_dtype, interpret=interpret,
            machine=machine,
        )
    else:
        dw = conv2d_wgrad_pallas(
            xp, gp, F=F, stride=S, block_h=hb, block_do=bdo, block_di=bdi,
            H_O=H_O, W_O=W_k, out_dtype=out_dtype, interpret=interpret,
            machine=machine,
        )
    return dw[:, :, :d_in, :d_out]


def _wgrad_impl(x, dy, *, schedule, out_dtype, interpret, F, stride=1,
                padding=0, mask=None, pool=1, block_h=None, block_do=None,
                block_di=None):
    del block_h, block_do, block_di  # consumed by the planner
    if schedule.algorithm == "rows":  # behind the row-GEMM forward
        from repro.kernels.conv2d.rows import conv2d_rows_wgrad

        return conv2d_rows_wgrad(
            x, dy, mask, F=F, stride=stride, padding=padding, pool=pool,
            schedule=schedule, out_dtype=out_dtype, interpret=interpret)
    return _wgrad_impl_jit(
        x, dy, mask, F=F, stride=stride, padding=padding, pool=pool,
        schedule=schedule, out_dtype=out_dtype, interpret=interpret,
    )


wgrad_op = pallas_op(
    "conv2d_wgrad",
    planner=ConvWgradPlanner,
    shape_args=_wgrad_shape_args,
    impl=_wgrad_impl,
    reference=conv2d_wgrad_ref,
)


def conv2d_wgrad(
    x: jax.Array,
    dy: jax.Array,
    *,
    F: int,
    stride: int = 1,
    padding: int = 0,
    mask: jax.Array | None = None,
    pool: int = 1,
    schedule: Schedule | None = None,
    block_h: int | None = None,
    block_do: int | None = None,
    block_di: int | None = None,
    out_dtype=None,
    interpret: bool | None = None,
    machine: MachineModel = TPU_V5E,
) -> jax.Array:
    """Filter gradient of :func:`repro.kernels.conv2d.ops.conv2d`.

    ``x``: [B, H, W, D_I] or [H, W, D_I] the forward input; ``dy``: the
    matching conv-output cotangent; ``F`` the filter extent.  One batched
    ``pallas_call`` accumulates dW in VMEM over the whole (batch, strip)
    grid and stores it once.  With ``mask``/``pool``, ``dy`` is the pooled
    post-epilogue cotangent and the in-jit scatter rebuilds the full-rate
    gradient first (see :func:`conv2d_dgrad`).  Blocking: ``schedule`` >
    ``block_*`` pins > ConvWgradPlanner.
    """
    return wgrad_op(
        x, dy, schedule=schedule, machine=machine, interpret=interpret,
        out_dtype=out_dtype or x.dtype, F=F, stride=stride, padding=padding,
        mask=mask, pool=pool,
        block_h=block_h, block_do=block_do, block_di=block_di,
    )
