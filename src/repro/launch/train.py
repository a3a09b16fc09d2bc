"""Production training launcher: config -> mesh -> sharded state -> data ->
elastic train loop with checkpoints, heartbeats, straggler watchdog, resume.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
        --steps 50 --mesh 1x1 --ckpt /tmp/run1
    # re-run the same command after killing it: resumes from the last
    # committed checkpoint (possibly onto a different mesh - resharding
    # restore).

On a real multi-host TPU slice the same entrypoint runs under
``jax.distributed.initialize()`` with ``--mesh 16x16`` / ``--mesh 2x16x16``;
on this CPU container use ``--mesh 1x1`` (or 2x4 under forced host
devices).  Elastic restart (DESIGN.md Sec. 7): on a detected host failure
the loop aborts the step, shrinks the mesh to the survivors
(fault_tolerance.shrink_mesh_shape — the model/TP extent is preserved),
re-plans every ShardedSchedule against the new MeshSpec (autotune
cache-only on the degraded cell, modeled argmin on miss), restores the
last *intact* committed checkpoint with the new shardings, and resumes —
bounded by --max-recoveries.  ``--chaos "kill@5,corrupt@4,nan@7"``
injects deterministic seeded faults to exercise exactly that path
(runtime/chaos.py; scripts/tier1.sh --fault-smoke).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint import checkpoint as ckpt
from repro.configs.base import TrainConfig
from repro.configs.registry import FAMILY_DEFAULT_ARCH, get_config, smoke_config
from repro.data.pipeline import ShardInfo
from repro.models.module import abstract_params, init_params, param_specs
from repro.models.registry import (
    FAMILIES, batch_shard_specs, get_family, make_data_source,
)
from repro.optim import adamw
from repro.runtime import spans
from repro.runtime import train as tr
from repro.runtime.chaos import ChaosConfig, ChaosMonkey
from repro.runtime.fault_tolerance import (
    Heartbeat, Monitor, StragglerWatchdog, shrink_mesh_shape,
)
from repro.runtime.parallel import ParallelCtx
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_auto_mesh
from repro.launch.specs import fsdp_specs


def parse_mesh(spec: str):
    dims = tuple(int(x) for x in spec.split("x"))
    if len(dims) == 3:
        return dims, ("pod", "data", "model")
    if len(dims) == 2:
        return dims, ("data", "model")
    raise ValueError(f"--mesh must be DxM or PxDxM, got {spec!r}")


@dataclasses.dataclass
class LaunchResult:
    """What one launcher run leaves behind: the final state, one history
    record per executed step, and the last incarnation's jitted step (so
    a caller can lower it again and read what was compiled)."""

    state: tr.TrainState
    history: list[dict]
    step_fn: Callable


def rows_stages_line(plan: dict) -> str | None:
    """The plan log's count of conv stages that run the row-GEMM family
    (``kernels/conv2d/rows.py``), which the planner picks by shape; None
    for a plan with no conv stage."""
    convs = sorted(k for k, s in plan.items()
                   if getattr(s, "op", None) == "conv2d")
    if not convs:
        return None
    rows = [k for k in convs if plan[k].algorithm == "rows"]
    return (f"conv stages on the rows family: {len(rows)} of {len(convs)} "
            f"({', '.join(rows) or 'none'})")


def main(argv: Sequence[str] | None = None) -> LaunchResult:
    """Run the launcher on ``argv`` (``sys.argv[1:]`` when None)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--family", default=None, choices=sorted(FAMILIES),
                    help="train a model family's reference arch (reduced "
                         "smoke config) instead of naming an --arch; the "
                         "family-registry hooks provide params, data and "
                         "loss, so e.g. '--family transformer "
                         "--planned-kernels' trains the planned "
                         "transformer wing exactly like '--family cnn'")
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--remat", default="none", choices=["none", "dots", "block"])
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--grad-compression", default="none", choices=["none", "int8_ef"])
    ap.add_argument("--planned-kernels", action="store_true",
                    help="run the family's planned Pallas forward AND "
                         "backward kernels in the train step instead of "
                         "the XLA reference path (cnn: fused conv + "
                         "dgrad/wgrad + dX/dW matmul; transformer: every "
                         "block GEMM + flash attention + dX/dW)")
    ap.add_argument("--autotune", default="off",
                    choices=["off", "cache-only", "tune"],
                    help="schedule resolution policy: cached measured-time "
                         "winners override the planners' modeled argmin "
                         "('tune' additionally measures top-k candidates on "
                         "a cache miss; see repro.plan.autotune)")
    ap.add_argument("--autotune-cache", default=None,
                    help="autotune winner-cache file (default: "
                         "$REPRO_AUTOTUNE_CACHE or ~/.cache/repro/"
                         "autotune.json)")
    ap.add_argument("--chaos", default=None,
                    help="seeded fault injection, e.g. "
                         "'kill@5,straggle@3x0.2,corrupt@4,nan@7x3' "
                         "(runtime/chaos.py)")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--max-recoveries", type=int, default=3,
                    help="consecutive elastic recoveries before giving up")
    ap.add_argument("--recovery-backoff", type=float, default=0.0,
                    help="base seconds between recoveries (doubles each)")
    ap.add_argument("--nonfinite-patience", type=int, default=3,
                    help="consecutive non-finite losses skipped before "
                         "rolling back to the last good checkpoint")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.autotune != "off" or args.autotune_cache:
        from repro.plan import autotune as at

        at.set_policy(args.autotune, args.autotune_cache)
        print(f"autotune: policy={args.autotune} "
              f"cache={at.get_cache().path} ({len(at.get_cache())} cells)")

    if args.arch is None:
        if args.family is None:
            ap.error("one of --arch or --family is required")
        args.arch = FAMILY_DEFAULT_ARCH[args.family]
        args.smoke = True  # family mode trains the reduced reference config

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.family is not None:
        if FAMILIES[args.family] is not FAMILIES[cfg.family]:
            ap.error(f"--family {args.family} does not match arch "
                     f"{args.arch} (family {cfg.family!r})")
        # Address the family under the requested registry name (e.g.
        # "transformer" aliases "dense") so every hook dispatch uses it.
        cfg = dataclasses.replace(cfg, family=args.family)
    tcfg = TrainConfig(
        param_dtype="float32", compute_dtype="float32" if args.smoke else "bfloat16",
        learning_rate=args.lr, warmup_steps=min(100, args.steps // 10 + 1),
        total_steps=args.steps, remat=args.remat, microbatch=args.microbatch,
        loss_chunks=4, seed=args.seed, grad_compression=args.grad_compression,
        planned_kernels=args.planned_kernels,
    )

    shape0, axes = parse_mesh(args.mesh)
    n_dev_full = int(np.prod(shape0))
    if n_dev_full > len(jax.devices()):
        raise SystemExit(
            f"mesh {args.mesh} needs {n_dev_full} devices, have {len(jax.devices())} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N)"
        )

    # Everything family-specific comes through the registry hooks
    # (models/registry.py): params, data source, loss, batch sharding,
    # planned schedules — the launcher never branches on the family name.
    fam = get_family(cfg.family)
    defs = fam.param_defs(cfg)
    aparams = abstract_params(defs, jnp.dtype(tcfg.param_dtype))
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(aparams))
    print(f"params: {n_params/1e6:.1f}M | arch {cfg.name} "
          f"| {tcfg.compute_dtype} compute")

    # Data: one shard per data-parallel host group (single process here).
    source = make_data_source(cfg, args.batch, args.seq, ShardInfo(0, 1),
                              seed=tcfg.seed)

    runs: list[tr.ElasticRun] = []  # one incarnation per (re)build

    def build(n_devices: int | None) -> tr.ElasticRun:
        """One incarnation of the run for a device count: mesh, sharded
        step_fn, re-planned ShardedSchedules, state restored from the last
        intact committed checkpoint.  ``None`` = initial full mesh; an
        explicit count = elastic recovery onto the survivors."""
        degraded = n_devices is not None
        n_dev = n_dev_full if n_devices is None else n_devices
        if n_dev == n_dev_full:
            shape = shape0
        else:
            shape = shrink_mesh_shape(
                n_dev, model=shape0[-1],
                pod=shape0[0] if len(shape0) == 3 else None)
        mesh = make_auto_mesh(shape, axes)
        dp_axes = tuple(a for a in axes if a != "model")
        ctx = ParallelCtx(mesh=mesh, dp_axes=dp_axes, tp_axis="model")
        print(f"mesh {dict(mesh.shape)} ({n_dev} devices"
              f"{', degraded' if degraded else ''})")

        use_sharding = n_dev > 1
        specs = param_specs(defs)
        pspecs = fsdp_specs(specs, aparams, ctx) if use_sharding else None

        shardings = None
        with spans.span("setup.init"):
            params = init_params(defs, jax.random.PRNGKey(tcfg.seed),
                                 jnp.dtype(tcfg.param_dtype))
            state = tr.init_state(cfg, tcfg, params)
            if use_sharding:
                sstate = tr.TrainState(
                    params=pspecs,
                    opt=adamw.AdamWState(step=P(), m=pspecs, v=pspecs),
                    err=None if state.err is None else pspecs)
                shardings = jax.tree.map(
                    lambda sp: NamedSharding(mesh, sp), sstate)
                # Place the fresh state as the step expects it, so the
                # first and later steps share one compiled program.
                state = jax.device_put(state, shardings)

        # Resume from the newest *intact* committed step (corrupt steps
        # fall back with a logged warning — reshard-on-restore works even
        # if the mesh changed).
        start = 0
        if args.ckpt:
            astate = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
            restored, last = ckpt.restore_latest(args.ckpt, astate, shardings)
            if restored is not None:
                state, start = restored, last + 1
                print(f"resumed from step {last} ({args.ckpt})")

        if use_sharding and hasattr(fam, "plan_training"):
            # Re-plan the full schedule set against THIS mesh: the
            # mesh-aware planners' model of the run (the ring/psum argmin
            # can flip at the new device count).  A degraded (recovery)
            # build resolves autotune cache-only — never measure while
            # recovering; a cache miss falls back to the modeled argmin.
            # The hook signature is uniform across families (cnn ignores
            # the token axes; the transformer sizes its logits cell off
            # them) — docs/plan-layer.md.
            from repro.plan import validate_sharded_plan
            from repro.plan.autotune import recovery_policy

            tune = recovery_policy(args.autotune) if degraded else args.autotune
            splan = fam.plan_training(cfg, args.batch, seq=args.seq,
                                      loss_chunks=tcfg.loss_chunks,
                                      in_bytes=jnp.dtype(
                                          tcfg.compute_dtype).itemsize,
                                      mesh=ctx.plan_mesh(),
                                      shard_axis=dp_axes[-1], autotune=tune)
            validate_sharded_plan(splan, ctx.plan_mesh())
            hbm = sum(s.hbm_words for s in splan.values())
            ici = sum(s.ici_words for s in splan.values())
            print(f"sharded plan: {len(splan)} kernels | modeled step words "
                  f"hbm={hbm} ici={ici}")
        if tcfg.planned_kernels and hasattr(fam, "plan_training"):
            plan = splan if use_sharding else fam.plan_training(
                cfg, args.batch, seq=args.seq, loss_chunks=tcfg.loss_chunks,
                in_bytes=jnp.dtype(tcfg.compute_dtype).itemsize)
            line = rows_stages_line(plan)
            if line:
                print(f"plan: {line}")

        step_fn = tr.make_train_step(
            cfg, tcfg, parallel=ctx if use_sharding else None,
            grad_specs=pspecs)
        if use_sharding:
            dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
            # The family registry owns the batch sharding spec (cnn shards
            # its image batch, token families their token batch) — no
            # family branching in the launcher.
            bspec = {k: NamedSharding(mesh, s)
                     for k, s in batch_shard_specs(cfg, dp).items()}
            step_fn = tr.DonatingStep(step_fn, in_shardings=(shardings, bspec))
        else:
            step_fn = tr.DonatingStep(step_fn)

        hb = mon = save = None
        if args.ckpt:
            os.makedirs(os.path.join(args.ckpt, "hb"), exist_ok=True)
            hb = Heartbeat(f"host{jax.process_index()}",
                           os.path.join(args.ckpt, "hb"))
            mon = Monitor(os.path.join(args.ckpt, "hb"), timeout=600)

            def save(step, st):
                # Async commit: run_elastic joins this handle before the
                # next save / a restore / the end, so writer failures
                # surface there instead of stalling the step here.  retain
                # only touches *committed* step dirs (the in-flight write
                # lives under a .tmp name), so pruning now is safe.
                handle = ckpt.save_async(args.ckpt, step, st,
                                         n_chunks=max(1, min(8, n_dev)))
                ckpt.retain(args.ckpt, keep=3)
                return handle

        runs.append(tr.ElasticRun(
            step_fn=step_fn, state=state, start=start, n_devices=n_dev,
            mesh=mesh, save=save, ckpt_dir=args.ckpt,
            ckpt_every=args.ckpt_every if args.ckpt else 0,
            devices_per_host=shape0[-1], heartbeat=hb, monitor=mon,
            watchdog=StragglerWatchdog(factor=3.0),
            log_every=args.log_every))
        return runs[-1]

    chaos = None
    if args.chaos:
        ccfg = ChaosConfig.parse(args.chaos, seed=args.chaos_seed)
        chaos = ChaosMonkey(ccfg, devices_per_host=shape0[-1])
        print(f"chaos: {ccfg} (seed {ccfg.seed})")

    policy = tr.RecoveryPolicy(max_recoveries=args.max_recoveries,
                               backoff_seconds=args.recovery_backoff,
                               nonfinite_patience=args.nonfinite_patience)
    state, history = tr.run_elastic(build, source, args.steps,
                                    policy=policy, chaos=chaos)
    if args.ckpt:
        print(f"final checkpoint: step {args.steps - 1}")
    skipped = sum(h["skipped"] for h in history)
    print(f"done: {len(history)} steps executed ({skipped} skipped as "
          f"non-finite), final loss {history[-1]['loss']:.4f}"
          if history else "done")
    return LaunchResult(state=state, history=history,
                        step_fn=runs[-1].step_fn)


if __name__ == "__main__":
    main()
