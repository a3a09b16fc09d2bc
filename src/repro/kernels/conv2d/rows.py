"""Row-GEMM conv2d: the conv family for inputs far narrower than a lane.

The direct and im2col kernels keep channels in lanes, so a stage with 3
input channels and 64 filters fills 128-lane blocks with zeros in HBM, in
the MXU and in the backward's scatter.  This family lays the stage out
with a whole padded input row in lanes instead (stride 1):

* **layout** — the spatially padded input ``[B, H+2P, W+2P, d_in]`` is
  viewed as rows ``[B, H+2P, (W+2P)*d_in]`` (a free reshape; K lanes).
  For each filter row ``ky`` a banded (block-Toeplitz) matrix
  ``T[ky]`` of shape ``[K, W_O*d_out]`` turns one input row into one
  output row: :func:`band` builds it from the filters by a fixed 0/1
  shift tensor (an einsum), outside the kernels' custom VJP, so autodiff
  folds the band's gradient back into dW.  Its columns are ordered
  (pool column phase | w // pool, d_out), each phase padded to Nh lanes,
  so the pool's column pairs are whole lane blocks and the pooled store
  ``[rows, Nh]`` is NHWC in memory order.
* **row phases** — :func:`row_views` stores the V = F + pool - 1 row
  views ``x[k::pool]`` that the pool's row phases read: output row
  ``pool*r + py`` is ``sum_ky view[py + ky][r] @ T[ky]``, so the row pairs
  of the pool are separate accumulators and no kernel slices sublanes.
* **forward** (``_conv_rows_impl``) — per block of images, the F row
  GEMMs for each (row phase, column phase) with f32 accumulation, then
  the flush of the direct kernel: bias, ReLU, max-pool and the int8 mask
  (argmax position 0..pool^2-1 in the direct kernel's order, pool^2 for
  a dead window).
* **backward** — ``_conv_rows_wgrad_impl`` rebuilds dY from the pooled
  cotangent and the mask in VMEM, block by block, and accumulates
  ``dT[ky] += view^T @ dY`` and the bias row's gradient over the batch;
  ``_conv_rows_dgrad_impl`` computes the views' gradient ``dY @ T^T``
  (XLA drops it where the input is the images).  No full-rate dY and no
  lane-padded copy of the input goes through HBM.

The band's zeros are real MXU work (K spans the whole row where a tap
needs F * d_in lanes of it), so the planner (``repro.plan.planners``
``plan_rows``) picks this family only where the other families' lane
padding costs more.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import ccr
from repro.core.machine import machine_named
from repro.kernels.compiler_params import compiler_params
from repro.kernels.conv2d.ops import _fused_pool, conv_out_extent
from repro.kernels.conv2d.ref import maxpool_ref
from repro.plan import Schedule, pad_dim
from repro.plan.planners import round_up

_LANE = 128
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class RowsGeometry:
    """Static shapes of one rows-family stage (stride 1): the input
    ``[B, H, W, d_in]`` padded by ``P``, ``F x F`` filters, a fused pool
    of ``pool``, the batch padded to ``Bp`` images and each pooled plane
    to ``Hq`` rows (a whole number of sublane tiles)."""

    B: int
    H: int
    W: int
    d_in: int
    d_out: int
    F: int
    P: int
    pool: int
    Bp: int
    Hq: int
    K: int
    Nh: int
    V: int

    @property
    def H_O(self) -> int:
        return conv_out_extent(self.H, self.P, self.F, 1)

    @property
    def W_O(self) -> int:
        return conv_out_extent(self.W, self.P, self.F, 1)

    @property
    def Hp(self) -> int:
        return self.H_O // self.pool

    @property
    def Wp(self) -> int:
        return self.W_O // self.pool


def rows_geometry(x_shape, f_shape, *, padding: int, pool: int, dtype,
                  block_b: int = 1) -> RowsGeometry:
    """The geometry of a stage on batched input ``x_shape``; the batch is
    padded to a whole number of ``block_b`` image blocks."""
    B, H, W, d_in = x_shape
    F, d_out = f_shape[0], f_shape[3]
    H_O = conv_out_extent(H, padding, F, 1)
    W_O = conv_out_extent(W, padding, F, 1)
    row_tile = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    K, Nh, V = ccr.conv_rows_geometry(W_O=W_O, F=F, d_in=d_in, d_out=d_out,
                                      pool=pool, lane=_LANE)
    return RowsGeometry(B=B, H=H, W=W, d_in=d_in, d_out=d_out, F=F,
                        P=padding, pool=pool, Bp=round_up(B, block_b),
                        Hq=round_up(H_O // pool, row_tile), K=K, Nh=Nh, V=V)


def row_views(x: jax.Array, geo: RowsGeometry) -> jax.Array:
    """``[V, Bp*Hq, K]``: view ``k`` holds padded input rows
    ``k, k + pool, ...`` of every image, each row ``(W+2P)*d_in`` words
    in K lanes.  Linear in ``x``; its transpose is the input gradient."""
    P, p = geo.P, geo.pool
    xp = jnp.pad(x, ((0, geo.Bp - geo.B), (P, P), (P, P), (0, 0)))
    xr = pad_dim(xp.reshape(geo.Bp, geo.H + 2 * P, -1), 2, geo.K)
    span = p * (geo.Hp - 1) + 1
    views = jnp.stack([
        jax.lax.slice(xr, (0, k, 0), (geo.Bp, k + span, geo.K), (1, p, 1))
        for k in range(geo.V)])
    return pad_dim(views, 2, geo.Hq).reshape(geo.V, geo.Bp * geo.Hq, geo.K)


def band(f: jax.Array, geo: RowsGeometry) -> jax.Array:
    """The banded filter matrices ``[F, pool, K, Nh]`` in f32:
    ``T[ky, px][(i, c), (v, o)] = f[ky, i - (v*pool + px), c, o]`` where
    the tap lies in the filter, else 0.  Linear in ``f``; its transpose
    folds the band's gradient into dW."""
    F, p, Wi = geo.F, geo.pool, geo.W + 2 * geo.P
    kx, i, px, v = np.ogrid[:F, :Wi, :p, :geo.Wp]
    shift = (i == v * p + px + kx).astype(np.float32)
    t = jnp.einsum("xipv,yxco->ypicvo", shift, f.astype(jnp.float32),
                   precision=_HIGHEST)
    t = t.reshape(F, p, Wi * geo.d_in, geo.Wp * geo.d_out)
    return pad_dim(pad_dim(t, 2, geo.K), 3, geo.Nh)


def bias_row(b: jax.Array, geo: RowsGeometry) -> jax.Array:
    """``[1, Nh]`` f32: the bias of every output column of a phase."""
    return pad_dim(jnp.tile(b.astype(jnp.float32), geo.Wp), 0, geo.Nh)[None]


def pack(y: jax.Array, geo: RowsGeometry, fill=0) -> jax.Array:
    """NHWC pooled ``[B, Hp, Wp, d_out]`` -> the kernels' ``[Bp*Hq, Nh]``."""
    y = y.reshape(geo.B, geo.Hp, geo.Wp * geo.d_out)
    y = jnp.pad(y, ((0, geo.Bp - geo.B), (0, geo.Hq - geo.Hp),
                    (0, geo.Nh - geo.Wp * geo.d_out)), constant_values=fill)
    return y.reshape(geo.Bp * geo.Hq, geo.Nh)


def unpack(y2: jax.Array, geo: RowsGeometry) -> jax.Array:
    """The kernels' ``[Bp*Hq, Nh]`` -> NHWC pooled ``[B, Hp, Wp, d_out]``."""
    y = y2.reshape(geo.Bp, geo.Hq, -1)[:geo.B, :geo.Hp, :geo.Wp * geo.d_out]
    return y.reshape(geo.B, geo.Hp, geo.Wp, geo.d_out)


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, t_ref, b_ref, o_ref, *rest, F: int, pool: int,
                block_c: int, relu: bool, emit_mask: bool):
    p = pool
    for c0 in range(0, o_ref.shape[1], block_c):
        cols = slice(c0, c0 + block_c)
        vals = []  # position py*pool + px, the direct kernel's order
        for py in range(p):
            for px in range(p):
                acc = None
                for ky in range(F):
                    d = jnp.dot(x_ref[py + ky], t_ref[ky, px, :, cols],
                                preferred_element_type=jnp.float32)
                    acc = d if acc is None else acc + d
                v = acc + b_ref[:, cols]
                vals.append(jnp.maximum(v, 0.0) if relu else v)
        out = functools.reduce(jnp.maximum, vals)
        if emit_mask:
            # First position holding the (ReLU-positive) max, p^2 for a
            # dead window: the direct kernel's epilogue-VJP mask.
            idx = jnp.full(out.shape, p * p, jnp.int32)
            for pos in reversed(range(p * p)):
                idx = jnp.where((vals[pos] == out) & (out > 0), pos, idx)
            rest[0][:, cols] = idx.astype(jnp.int8)
        o_ref[:, cols] = out.astype(o_ref.dtype)


def _phase_grads(g_ref, m_ref, cols, pool: int):
    """dY of each (row phase, column phase) of one column chunk, rebuilt
    in VMEM from the pooled cotangent and the mask."""
    g = g_ref[:, cols]
    m = m_ref[:, cols].astype(jnp.int32)
    zero = jnp.zeros_like(g)
    return g, m, [[jnp.where(m == py * pool + px, g, zero)
                   for px in range(pool)] for py in range(pool)]


def _wgrad_kernel(x_ref, g_ref, m_ref, dt_ref, db_ref, *, F: int, pool: int,
                  block_c: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dt_ref[...] = jnp.zeros_like(dt_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    p = pool
    for c0 in range(0, g_ref.shape[1], block_c):
        cols = slice(c0, c0 + block_c)
        g, m, dys = _phase_grads(g_ref, m_ref, cols, p)
        live = jnp.where(m < p * p, g.astype(jnp.float32), 0.0)
        db_ref[:, cols] += jnp.sum(live, axis=0, keepdims=True)
        for py in range(p):
            for px in range(p):
                for ky in range(F):
                    dt_ref[ky, px, :, cols] += jax.lax.dot_general(
                        x_ref[py + ky], dys[py][px], (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)


def _dgrad_kernel(g_ref, m_ref, t_ref, o_ref, *, F: int, pool: int,
                  block_c: int):
    p = pool
    accs = [None] * (F + p - 1)
    for c0 in range(0, g_ref.shape[1], block_c):
        cols = slice(c0, c0 + block_c)
        _, _, dys = _phase_grads(g_ref, m_ref, cols, p)
        for py in range(p):
            for px in range(p):
                for ky in range(F):
                    d = jax.lax.dot_general(
                        dys[py][px], t_ref[ky, px, :, cols],
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    k = py + ky
                    accs[k] = d if accs[k] is None else accs[k] + d
    for k, acc in enumerate(accs):
        o_ref[k] = acc.astype(o_ref.dtype)


_STATIC = ("geo", "block_b", "block_c", "machine", "interpret")


@functools.partial(jax.jit,
                   static_argnames=_STATIC + ("relu", "emit_mask", "out_dtype"))
def _conv_rows_impl(x4, t, brow, *, geo, block_b, block_c, machine,
                    interpret, relu, emit_mask, out_dtype):
    """Forward: ``[Bp*Hq, Nh]`` pooled output (and the int8 mask)."""
    M, rows = block_b * geo.Hq, geo.Bp * geo.Hq
    out = pl.BlockSpec((M, geo.Nh), lambda i: (i, 0))
    shape = jax.ShapeDtypeStruct((rows, geo.Nh), out_dtype)
    if emit_mask:
        out = [out, out]
        shape = [shape, jax.ShapeDtypeStruct((rows, geo.Nh), jnp.int8)]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, F=geo.F, pool=geo.pool,
                          block_c=block_c, relu=relu, emit_mask=emit_mask),
        grid=(geo.Bp // block_b,),
        in_specs=[pl.BlockSpec((geo.V, M, geo.K), lambda i: (0, i, 0)),
                  pl.BlockSpec(t.shape, lambda i: (0, 0, 0, 0)),
                  pl.BlockSpec(brow.shape, lambda i: (0, 0))],
        out_specs=out,
        out_shape=shape,
        compiler_params=compiler_params(machine_named(machine), "parallel"),
        interpret=interpret,
    )(x4, t, brow)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _conv_rows_wgrad_impl(x4, g2, m2, *, geo, block_b, block_c, machine,
                          interpret):
    """The band's gradient ``[F, pool, K, Nh]`` and the bias row's
    ``[1, Nh]``, f32, accumulated over the batch."""
    M = block_b * geo.Hq
    band_shape = (geo.F, geo.pool, geo.K, geo.Nh)
    rows = pl.BlockSpec((M, geo.Nh), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_wgrad_kernel, F=geo.F, pool=geo.pool,
                          block_c=block_c),
        grid=(geo.Bp // block_b,),
        in_specs=[pl.BlockSpec((geo.V, M, geo.K), lambda i: (0, i, 0)),
                  rows, rows],
        out_specs=[pl.BlockSpec(band_shape, lambda i: (0, 0, 0, 0)),
                   pl.BlockSpec((1, geo.Nh), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(band_shape, jnp.float32),
                   jax.ShapeDtypeStruct((1, geo.Nh), jnp.float32)],
        compiler_params=compiler_params(machine_named(machine), "arbitrary"),
        interpret=interpret,
    )(x4, g2, m2)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _conv_rows_dgrad_impl(g2, m2, t, *, geo, block_b, block_c, machine,
                          interpret):
    """The row views' gradient ``[V, Bp*Hq, K]`` in ``g2``'s dtype."""
    M = block_b * geo.Hq
    rows = pl.BlockSpec((M, geo.Nh), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_dgrad_kernel, F=geo.F, pool=geo.pool,
                          block_c=block_c),
        grid=(geo.Bp // block_b,),
        in_specs=[rows, rows, pl.BlockSpec(t.shape, lambda i: (0, 0, 0, 0))],
        out_specs=pl.BlockSpec((geo.V, M, geo.K), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((geo.V, geo.Bp * geo.Hq, geo.K),
                                       g2.dtype),
        compiler_params=compiler_params(machine_named(machine), "parallel"),
        interpret=interpret,
    )(g2, m2, t)


def _blocking(schedule: Schedule, geo: RowsGeometry) -> dict:
    """The kernel kwargs of one pass from its Schedule: the image block
    (dividing the padded batch) and a column chunk dividing Nh."""
    nb = math.gcd(schedule.block("block_b", geo.Bp), geo.Bp)
    cn = schedule.block("block_c", geo.Nh)
    return dict(block_b=nb, block_c=cn if geo.Nh % cn == 0 else geo.Nh,
                machine=schedule.machine)


# ---------------------------------------------------------------------------
# The differentiable stage: layout in XLA, a custom VJP around the kernels
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Call:
    geo: RowsGeometry
    fwd: Schedule
    wgrad: Schedule
    dgrad: Schedule
    out_dtype: object
    interpret: bool


def _forward(x4, t, brow, call: _Call, emit_mask: bool):
    return _conv_rows_impl(
        x4, t, brow, geo=call.geo, relu=True, emit_mask=emit_mask,
        out_dtype=call.out_dtype, interpret=call.interpret,
        **_blocking(call.fwd, call.geo))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_core(x4, t, brow, call: _Call):
    return _forward(x4, t.astype(x4.dtype), brow, call, emit_mask=False)


def _rows_core_fwd(x4, t, brow, call):
    tc = t.astype(x4.dtype)
    y, mask = _forward(x4, tc, brow, call, emit_mask=True)
    return y, (x4, tc, mask)


def _rows_core_bwd(call, res, gy):
    x4, tc, mask = res
    gy = gy.astype(x4.dtype)
    kw = dict(geo=call.geo, interpret=call.interpret)
    dt, db = _conv_rows_wgrad_impl(x4, gy, mask, **kw,
                                   **_blocking(call.wgrad, call.geo))
    dx4 = _conv_rows_dgrad_impl(gy, mask, tc, **kw,
                                **_blocking(call.dgrad, call.geo))
    return dx4, dt, db


_rows_core.defvjp(_rows_core_fwd, _rows_core_bwd)


def conv_rows_block(x, f, b, *, padding: int, pool: int, schedule: Schedule,
                    wgrad: Schedule, dgrad: Schedule, out_dtype=None,
                    interpret: bool = False):
    """Conv (stride 1) + bias + ReLU + fused ``pool x pool`` max-pool on
    the row-GEMM kernels, differentiable: the row views, the band and the
    bias row are XLA around a custom VJP whose backward runs the rows
    wgrad and dgrad kernels, so autodiff turns the band's gradient into
    dW, the bias row's into db and the views' into dX.  ``x``: [B, H, W,
    d_in] or unbatched; H_O and W_O must be multiples of ``pool``."""
    batched = x.ndim == 4
    if not batched:
        x = x[None]
    if b is None:
        b = jnp.zeros((f.shape[3],), jnp.float32)
    nbs = [s.block("block_b") for s in (schedule, wgrad, dgrad)]
    geo = rows_geometry(x.shape, f.shape, padding=padding, pool=pool,
                        dtype=x.dtype, block_b=math.lcm(*nbs))
    call = _Call(geo, schedule, wgrad, dgrad,
                 jnp.dtype(out_dtype or x.dtype), interpret)
    y2 = _rows_core(row_views(x, geo), band(f, geo), bias_row(b, geo), call)
    y = unpack(y2, geo)
    return y if batched else y[0]


# ---------------------------------------------------------------------------
# The ops' rows paths: conv2d, conv2d_dgrad and conv2d_wgrad given a rows
# Schedule (masks in the direct kernel's NHWC layout)
# ---------------------------------------------------------------------------


def conv2d_rows(x, f, bias, *, stride, padding, relu, pool, schedule,
                out_dtype, interpret, emit_mask=False):
    """The ``conv2d`` op on a rows Schedule; a ragged pool runs as a tail
    op (no mask), as on the direct kernel."""
    if stride != 1:
        raise ValueError(f"the rows conv runs stride 1, not {stride}")
    batched = x.ndim == 4
    if not batched:
        x = x[None]
    F = f.shape[0]
    fused = _fused_pool(conv_out_extent(x.shape[1], padding, F, 1),
                        conv_out_extent(x.shape[2], padding, F, 1), pool)
    geo = rows_geometry(x.shape, f.shape, padding=padding, pool=fused,
                        dtype=x.dtype, block_b=schedule.block("block_b"))
    out = _conv_rows_impl(
        row_views(x, geo), band(f, geo).astype(x.dtype), bias_row(bias, geo),
        geo=geo, relu=relu, emit_mask=emit_mask, out_dtype=out_dtype,
        interpret=interpret, **_blocking(schedule, geo))
    if emit_mask:
        out, mask = out
        out, mask = unpack(out, geo), unpack(mask, geo)
        return (out, mask) if batched else (out[0], mask[0])
    out = unpack(out, geo)
    if pool > 1 and fused == 1:
        out = maxpool_ref(out, pool)
    return out if batched else out[0]


def _bwd_geometry(x_shape, f_shape, padding, pool, dtype, schedule, mask):
    if mask is None or not pool:
        raise ValueError("the rows backward reads the int8 pool mask of the "
                         "rows forward: pass mask= and pool=")
    return rows_geometry(x_shape, f_shape, padding=padding, pool=pool,
                         dtype=dtype, block_b=schedule.block("block_b"))


def conv2d_rows_dgrad(dy, f, mask, *, stride, padding, out_hw, pool,
                      schedule, out_dtype, interpret):
    """The ``conv2d_dgrad`` op on a rows Schedule: ``dy`` and ``mask`` are
    the pooled cotangent and the forward's mask, NHWC."""
    if stride != 1:
        raise ValueError(f"the rows conv runs stride 1, not {stride}")
    batched = dy.ndim == 4
    if not batched:
        dy, mask = dy[None], mask[None]
    B, Hp, Wp, _ = dy.shape
    F, d_in = f.shape[0], f.shape[2]
    H, W = out_hw or (Hp * pool + F - 1 - 2 * padding,
                      Wp * pool + F - 1 - 2 * padding)
    geo = _bwd_geometry((B, H, W, d_in), f.shape, padding, pool, dy.dtype,
                        schedule, mask)
    dx4 = _conv_rows_dgrad_impl(
        pack(dy, geo), pack(mask, geo, pool * pool),
        band(f, geo).astype(dy.dtype), geo=geo, interpret=interpret,
        **_blocking(schedule, geo))
    x_struct = jax.ShapeDtypeStruct((B, H, W, d_in), dx4.dtype)
    dx, = jax.linear_transpose(functools.partial(row_views, geo=geo),
                               x_struct)(dx4)
    dx = dx.astype(out_dtype)
    return dx if batched else dx[0]


def conv2d_rows_wgrad(x, dy, mask, *, F, stride, padding, pool, schedule,
                      out_dtype, interpret):
    """The ``conv2d_wgrad`` op on a rows Schedule (pooled ``dy`` and the
    forward's NHWC ``mask``): the band's gradient folded into dW by the
    transpose of :func:`band`."""
    if stride != 1:
        raise ValueError(f"the rows conv runs stride 1, not {stride}")
    if x.ndim == 3:
        x, dy = x[None], dy[None]
        mask = None if mask is None else mask[None]
    d_out = dy.shape[-1]
    f_shape = (F, F, x.shape[-1], d_out)
    geo = _bwd_geometry(x.shape, f_shape, padding, pool, x.dtype, schedule,
                        mask)
    dt, _ = _conv_rows_wgrad_impl(
        row_views(x, geo), pack(dy.astype(x.dtype), geo),
        pack(mask, geo, pool * pool), geo=geo, interpret=interpret,
        **_blocking(schedule, geo))
    f_struct = jax.ShapeDtypeStruct(f_shape, jnp.float32)
    dw, = jax.linear_transpose(functools.partial(band, geo=geo), f_struct)(dt)
    return dw.astype(out_dtype)
