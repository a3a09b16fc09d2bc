"""`pallas_op`: the registry that puts one Schedule/Planner layer behind
every Pallas kernel in the repo.

Each kernel package registers itself once — a planner, a shape extractor,
and a schedule-driven implementation — and inherits the boilerplate the
three ``ops.py`` files used to duplicate in diverging dialects:

  * interpret-mode fallback (``interpret=None`` -> interpret off-TPU),
  * output-dtype promotion (``out_dtype=None`` -> first operand's dtype),
  * schedule resolution (explicit ``Schedule`` beats the planner),
  * lane padding/unpadding helpers (:func:`pad_dim`),
  * reference-VJP ``custom_vjp`` wiring (:func:`with_reference_vjp`).

Ops resolve lazily by name (:func:`get_op`), so ``repro.plan`` never
imports kernel code at module load.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.machine import TPU_V5E, MachineModel, machine_named
from repro.plan.planners import Planner, planner_for, round_up
from repro.plan.schedule import Schedule
from repro.plan.sharded import ShardedSchedule, local_schedule, mesh_spec

# ---------------------------------------------------------------------------
# Shared boilerplate
# ---------------------------------------------------------------------------


def default_interpret(interpret: bool | None) -> bool:
    """Pallas interpret-mode fallback: run interpreted anywhere but on TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def require_fit(schedule: Schedule, interpret: bool) -> None:
    """Refuse to compile a kernel whose schedule overflows its machine's
    VMEM budget: every ``pallas_call`` compiles under that budget
    (``kernels/compiler_params.py``), so an unfit plan would be refused by
    the compiler or run out of scoped VMEM.  Interpret mode has no VMEM
    and runs any blocking."""
    machine = machine_named(schedule.machine)
    if not interpret and not schedule.fits(machine):
        raise ValueError(
            f"{schedule.op!r} schedule (grid={schedule.grid}, "
            f"blocks={dict(schedule.blocks)}) needs {schedule.vmem_bytes} B "
            f"of VMEM, over the {machine.usable_for_working_set(2)} B "
            f"budget of {machine.name!r}; not compiling it")


def pad_dim(x: jax.Array, axis: int, size: int) -> jax.Array:
    """Zero-pad one axis up to ``size`` (no-op when already there)."""
    have = x.shape[axis]
    if have == size:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, size - have)
    return jnp.pad(x, widths)


def freeze_schedules(schedules) -> tuple | None:
    """Normalize a ``{name: Schedule}`` mapping into the hashable
    sorted-tuple form that ``custom_vjp`` nondiff arguments require
    (tuples and ``None`` pass through unchanged)."""
    if schedules is None or isinstance(schedules, tuple):
        return schedules
    return tuple(sorted(schedules.items()))


def with_reference_vjp(kernel_fn, ref_fn, *, nondiff_argnums: tuple[int, ...] = (),
                       bwd_fn: Callable | None = None,
                       fwd_fn: Callable | None = None):
    """``custom_vjp`` wiring shared by every layer module: forward runs the
    Pallas kernel, backward runs ``bwd_fn`` (planned backward kernels) when
    given, else differentiates the XLA reference composition.

    ``nondiff_argnums`` must be the *trailing* positional arguments of
    ``kernel_fn``; ``ref_fn`` takes the same positional arguments.
    ``bwd_fn`` is called as ``bwd_fn(*diff_args, cotangent, *nondiff_args)``
    and must return one cotangent per differentiable argument.  Backward
    Schedules ride as a trailing nondiff argument (``bwd_schedules``,
    frozen via :func:`freeze_schedules`) so ``bwd_fn`` can honor them —
    closing the old gap where a user-passed schedule was silently ignored
    on the backward call because the reference VJP has no schedule knob.

    ``fwd_fn`` (same signature as ``kernel_fn``) is the *differentiated*
    forward: it returns ``(out, aux)`` where ``aux`` is a cheap auxiliary
    residual (e.g. the fused kernel's epilogue-VJP mask) — or ``None``
    when the kernel couldn't produce one.  The aux rides as the trailing
    residual, so ``bwd_fn`` becomes ``bwd_fn(*diff_args, aux, cotangent,
    *nondiff_args)``.  Primal-only calls still run plain ``kernel_fn`` and
    never pay for the aux output.
    """
    for i, j in zip(nondiff_argnums, nondiff_argnums[1:]):
        assert j == i + 1, "nondiff_argnums must be contiguous and trailing"

    @functools.partial(jax.custom_vjp, nondiff_argnums=nondiff_argnums)
    def op(*args):
        return kernel_fn(*args)

    def fwd(*args):
        assert not nondiff_argnums or nondiff_argnums[-1] == len(args) - 1, (
            "nondiff_argnums must be the trailing arguments of kernel_fn: "
            f"got {nondiff_argnums} for {len(args)} args"
        )
        diff = tuple(a for i, a in enumerate(args) if i not in nondiff_argnums)
        if fwd_fn is not None:
            out, aux = fwd_fn(*args)
            return out, diff + (aux,)
        return kernel_fn(*args), diff

    def bwd(*call):
        n = len(nondiff_argnums)
        nondiff, (res, g) = call[:n], call[n:]
        if bwd_fn is not None:
            return tuple(bwd_fn(*res, g, *nondiff))
        _, vjp = jax.vjp(lambda *d: ref_fn(*d, *nondiff), *res)
        return vjp(g)

    op.defvjp(fwd, bwd)
    return op


# ---------------------------------------------------------------------------
# The op registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PallasOp:
    """One registered kernel: planner + shape extraction + implementation.

    ``shape_args(*arrays, **params)`` maps concrete operands to the
    planner's keyword shapes; ``impl(*arrays, schedule=, out_dtype=,
    interpret=, **params)`` runs the (jit'd) kernel from a Schedule.
    """

    name: str
    planner: type  # Planner class, constructed per machine
    shape_args: Callable[..., dict[str, Any]]
    impl: Callable[..., jax.Array]
    reference: Callable[..., jax.Array] | None = None
    # Multi-device execution of a ShardedSchedule's strategy (shard_map
    # dataflow); ops without one still *plan* sharded, they just can't
    # execute the collective strategies through the registry.
    sharded_impl: Callable[..., jax.Array] | None = None

    def planner_for(self, machine: MachineModel = TPU_V5E, mesh=None,
                    shard_axis: str = "model",
                    strategy: str | None = None) -> Planner:
        if mesh is None:
            return self.planner(machine)
        return self.planner(machine, mesh_spec(mesh), shard_axis, strategy)

    def plan(self, *arrays, machine: MachineModel = TPU_V5E,
             autotune: str | None = None, **params) -> Schedule:
        """Plan from concrete operands (shapes/dtypes only are read).
        Cached per (planner, shapes): eager call loops re-plan for free.
        ``autotune`` overrides the process policy for this resolution —
        under "cache-only"/"tune" a measured winner beats the argmin."""
        shape = self.shape_args(*arrays, **params)
        tuned = _tuned(self.name, shape, machine, None, "model", None,
                       autotune, arrays[0].dtype)
        if tuned is not None:
            return tuned
        return _cached_plan(self.planner(machine), tuple(sorted(shape.items())))

    def plan_sharded(
        self, *arrays, mesh, machine: MachineModel = TPU_V5E,
        axis: str = "model", strategy: str | None = None,
        autotune: str | None = None, **params,
    ) -> ShardedSchedule:
        """Plan from concrete operands against a ``(machine, mesh)`` pair:
        the returned ShardedSchedule carries the device partitioning and
        the HBM/ICI word split (cached like :meth:`plan`; a tuned winner
        for the ``(op, shapes, machine, mesh)`` cell overrides the
        modeled psum-vs-ring-vs-batch pick)."""
        shape = self.shape_args(*arrays, **params)
        tuned = _tuned(self.name, shape, machine, mesh_spec(mesh), axis,
                       strategy, autotune, arrays[0].dtype)
        if tuned is not None:
            return tuned
        planner = self.planner_for(machine, mesh, axis, strategy)
        return _cached_plan(planner, tuple(sorted(shape.items())))

    def __call__(
        self, *arrays, schedule: Schedule | ShardedSchedule | None = None,
        machine: MachineModel = TPU_V5E, interpret: bool | None = None,
        out_dtype=None, autotune: str | None = None, **params,
    ) -> jax.Array:
        interpret = default_interpret(interpret)
        out_dtype = out_dtype or arrays[0].dtype
        schedule = local_schedule(schedule)  # degenerate sharded plans run local
        if schedule is None:
            schedule = local_schedule(
                self.plan(*arrays, machine=machine, autotune=autotune,
                          **params))
        require_fit(schedule, interpret)
        return self.impl(
            *arrays, schedule=schedule, out_dtype=out_dtype,
            interpret=interpret, **params,
        )

    def sharded(
        self, *arrays, schedule: ShardedSchedule, mesh,
        interpret: bool | None = None, out_dtype=None, **params,
    ) -> jax.Array:
        """Execute a ShardedSchedule's multi-device strategy on a live
        ``jax.sharding.Mesh``: the registered ``sharded_impl`` builds the
        shard_map dataflow (psum tree / ring permutes / data parallelism)
        from the schedule's partition — call sites never hand-wire specs.
        The "single" strategy (and any 1-wide shard group) falls back to
        the plain per-device impl."""
        if schedule.strategy == "single" or schedule.devices == 1:
            return self(*arrays, schedule=schedule.schedule,
                        interpret=interpret, out_dtype=out_dtype, **params)
        if self.sharded_impl is None:
            raise NotImplementedError(
                f"op {self.name!r} registered no sharded_impl; strategy "
                f"{schedule.strategy!r} cannot execute through the registry")
        interpret = default_interpret(interpret)
        out_dtype = out_dtype or arrays[0].dtype
        require_fit(schedule.schedule, interpret)
        return self.sharded_impl(
            *arrays, schedule=schedule, mesh=mesh, out_dtype=out_dtype,
            interpret=interpret, **params,
        )


@functools.lru_cache(maxsize=4096)
def _cached_plan(planner: Planner, shape_items: tuple) -> Schedule:
    """Planners are frozen dataclasses and shape kwargs are hashable ints,
    so identical (planner, shapes) pairs return the memoized Schedule."""
    return planner.plan(**dict(shape_items))


def _tuned(name, shape, machine, mesh, axis, strategy, policy, dtype):
    """The measured-time override for one schedule resolution (see
    repro.plan.autotune), or ``None`` when the modeled argmin stands —
    policy "off" short-circuits before the autotuner is even imported."""
    from repro.plan import autotune as _at

    if (policy or _at.get_policy()) == "off":
        return None
    return _at.tuned_schedule(name, shape, machine=machine, mesh=mesh,
                              axis=axis, strategy=strategy, policy=policy,
                              dtype=dtype)


_OPS: dict[str, PallasOp] = {}

# Ops register at import of their kernel package; get_op() imports lazily so
# `repro.plan` stays importable without (and before) any kernel code.
_PROVIDERS = {
    "conv2d": "repro.kernels.conv2d.ops",
    "conv2d_im2col": "repro.kernels.conv2d.im2col",
    "conv2d_dgrad": "repro.kernels.conv2d.bwd",
    "conv2d_wgrad": "repro.kernels.conv2d.bwd",
    "matmul": "repro.kernels.matmul.ops",
    "matmul_dx": "repro.kernels.matmul.bwd",
    "matmul_dw": "repro.kernels.matmul.bwd",
    "flash_attention": "repro.kernels.flash_attention.ops",
    "grouped_matmul": "repro.kernels.matmul.grouped",
    "grouped_matmul_dx": "repro.kernels.matmul.grouped",
    "grouped_matmul_dw": "repro.kernels.matmul.grouped",
}


def pallas_op(
    name: str, *, planner: type, shape_args: Callable, impl: Callable,
    reference: Callable | None = None, sharded_impl: Callable | None = None,
) -> PallasOp:
    """Register a kernel behind the plan layer (returns the op handle)."""
    op = PallasOp(name=name, planner=planner, shape_args=shape_args,
                  impl=impl, reference=reference, sharded_impl=sharded_impl)
    _OPS[name] = op
    return op


def get_op(name: str) -> PallasOp:
    """Look up a registered op, importing its provider module if needed."""
    if name not in _OPS and name in _PROVIDERS:
        importlib.import_module(_PROVIDERS[name])
    try:
        return _OPS[name]
    except KeyError:
        raise KeyError(f"unknown pallas op {name!r}; known: "
                       f"{sorted(set(_OPS) | set(_PROVIDERS))}") from None


def registered_ops() -> tuple[str, ...]:
    """All op names the registry can resolve."""
    return tuple(sorted(set(_OPS) | set(_PROVIDERS)))


__all__ = [
    "PallasOp", "default_interpret", "freeze_schedules", "get_op", "pad_dim",
    "pallas_op", "planner_for", "registered_ops", "require_fit", "round_up",
    "with_reference_vjp",
]
