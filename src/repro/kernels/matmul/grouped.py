"""Grouped matmul over sorted row groups: the held experts' GEMMs.

``out[r] = lhs[r] @ rhs[group(r)]`` where the rows of ``lhs`` are sorted
into contiguous groups by ``group_sizes``.  ``rhs`` holds one weight per
*held* group; ``group_sizes`` has one entry more, the tail of the buffer
that belongs to no held group (the worst-case room no routing filled).
The kernels visit only the held groups' row tiles, read from
``group_sizes`` on the device, so their time follows the routed rows and
not the buffer: the tail's rows come out zero and cost no tile.

Three planned ops, each a ``pallas_op`` behind its own planner
(:class:`repro.plan.GroupedMatmulPlanner` and its dX/dW relatives):

* ``grouped_matmul``    - the forward, jax's megablox ``gmm``;
* ``grouped_matmul_dx`` - ``dlhs[r] = dout[r] @ rhs[group(r)]^T``, ``gmm``
                          with the weight transposed in the kernel;
* ``grouped_matmul_dw`` - ``drhs[g] = lhs_g^T @ dout_g``, megablox
                          ``tgmm`` over the held groups only.

The planner's ``(block_m, block_k, block_n)`` (forward roles: rows, the
weight's k, its n) become each kernel's ``(tm, tk, tn)`` tiling.
:func:`grouped_matmul` ties the three into one differentiable layer.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from repro.plan import (
    GroupedMatmulDwPlanner, GroupedMatmulDxPlanner, GroupedMatmulPlanner,
    freeze_schedules, get_op, pallas_op, with_reference_vjp,
)

# The package re-exports ``gmm`` (the function) under the module's name,
# so the kernels' module is reached through the import system.
_mb = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")


def group_ids(group_sizes, rows: int):
    """The group of every buffer row (``len(group_sizes) - 1`` is the tail)."""
    ends = jnp.cumsum(group_sizes)
    return jnp.searchsorted(ends, jnp.arange(rows), side="right")


def grouped_matmul_ref(lhs, rhs, group_sizes, out_dtype=None):
    """XLA oracle: each row against its group's weight, f32 accumulation;
    tail rows zero."""
    gid = group_ids(group_sizes, lhs.shape[0])
    out = jnp.zeros((lhs.shape[0], rhs.shape[-1]), jnp.float32)
    for g in range(rhs.shape[0]):
        y = jnp.dot(lhs, rhs[g], preferred_element_type=jnp.float32)
        out = jnp.where((gid == g)[:, None], y, out)
    return out.astype(out_dtype or lhs.dtype)


def _tiling(schedule, order: str) -> tuple[int, int, int]:
    """The kernel's (tm, tk, tn) from forward-role block names: ``order``
    names the blocks that tile the kernel's rows, contraction and
    output columns."""
    return tuple(schedule.block(f"block_{c}") for c in order)


def _shape_args(lhs, rhs, group_sizes, *, group_rows=None):
    """The forward's shapes from (rows, weights, sizes): the dX pass takes
    ``(dout, weights, sizes)`` and is planned on the same shapes."""
    return dict(m=lhs.shape[0], k=rhs.shape[1], n=rhs.shape[2],
                groups=rhs.shape[0], group_rows=group_rows,
                in_bytes=lhs.dtype.itemsize)


def _impl(lhs, rhs, group_sizes, *, schedule, out_dtype, interpret,
          group_rows=None):
    del group_rows  # consumed by the planner
    return _mb.gmm(lhs, rhs, group_sizes, out_dtype,
                   _tiling(schedule, "mkn"), interpret=interpret)


def _dx_impl(dout, rhs, group_sizes, *, schedule, out_dtype, interpret,
             group_rows=None):
    del group_rows
    return _mb.gmm(dout, rhs, group_sizes, out_dtype,
                   _tiling(schedule, "mnk"), transpose_rhs=True,
                   interpret=interpret)


def _dw_shape_args(lhs, dout, group_sizes, *, groups, group_rows=None):
    return dict(m=lhs.shape[0], k=lhs.shape[1], n=dout.shape[1],
                groups=groups, group_rows=group_rows,
                in_bytes=lhs.dtype.itemsize, out_bytes=4)


def _dw_impl(lhs, dout, group_sizes, *, schedule, out_dtype, interpret,
             groups, group_rows=None):
    del group_rows
    return _mb.tgmm(lhs.swapaxes(0, 1), dout, group_sizes, out_dtype,
                    _tiling(schedule, "mkn"), num_actual_groups=groups,
                    interpret=interpret)


pallas_op("grouped_matmul", planner=GroupedMatmulPlanner,
          shape_args=_shape_args, impl=_impl, reference=grouped_matmul_ref)
pallas_op("grouped_matmul_dx", planner=GroupedMatmulDxPlanner,
          shape_args=_shape_args, impl=_dx_impl)
pallas_op("grouped_matmul_dw", planner=GroupedMatmulDwPlanner,
          shape_args=_dw_shape_args, impl=_dw_impl)


def plan(m: int, k: int, n: int, groups: int, *, group_rows: int,
         in_bytes: int = 2, block_m: int | None = None) -> dict:
    """The three passes' Schedules for one grouped layer, sharing the
    forward's row tile (the groups are padded to it; ``block_m`` pins it):
    ``{"fwd", "dx", "dw"}``, to pass back as :func:`grouped_matmul`'s
    ``schedules``."""
    shape = dict(m=m, k=k, n=n, groups=groups, group_rows=group_rows,
                 in_bytes=in_bytes)
    fwd = GroupedMatmulPlanner().plan(**shape, block_m=block_m)
    tm = fwd.block("block_m")
    return {"fwd": fwd,
            "dx": GroupedMatmulDxPlanner().plan(**shape, block_m=tm),
            "dw": GroupedMatmulDwPlanner().plan(**shape, block_m=tm,
                                                out_bytes=4)}


def _kernel(lhs, rhs, group_sizes, schedules):
    return get_op("grouped_matmul")(lhs, rhs, group_sizes,
                                    schedule=dict(schedules)["fwd"])


def _ref(lhs, rhs, group_sizes, schedules):
    del schedules  # blocking never changes numerics
    return grouped_matmul_ref(lhs, rhs, group_sizes)


def _bwd(lhs, rhs, group_sizes, g, schedules):
    sd = dict(schedules)
    dlhs = get_op("grouped_matmul_dx")(g, rhs, group_sizes, schedule=sd["dx"],
                                       out_dtype=lhs.dtype)
    drhs = get_op("grouped_matmul_dw")(lhs, g, group_sizes, schedule=sd["dw"],
                                       groups=rhs.shape[0],
                                       out_dtype=jnp.float32)
    return dlhs, drhs.astype(rhs.dtype), None


_grouped_vjp = with_reference_vjp(_kernel, _ref, nondiff_argnums=(3,),
                                  bwd_fn=_bwd)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   schedules: dict) -> jax.Array:
    """``lhs`` [M, k] rows sorted into groups; ``rhs`` [G, k, n], one
    weight per held group; ``group_sizes`` int32 [G + 1], the last the
    tail.  ``M`` is a multiple of the row tile.  ``schedules`` from
    :func:`plan`.  Returns [M, n] in ``lhs``'s dtype, tail rows zero;
    ``jax.grad`` runs the planned dX and dW kernels."""
    return _grouped_vjp(lhs, rhs, group_sizes.astype(jnp.int32),
                        freeze_schedules(schedules))
