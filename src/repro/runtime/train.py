"""Train-step builder: chunked cross-entropy, microbatch gradient
accumulation (lax.scan), AdamW, optional error-feedback int8 compression.

The FC-layer insight of the paper shows up twice here: the logits head is
a batched FC layer (vocab = D_O) computed in Delta_O-style *token chunks*
so the [tokens, vocab] logits volume is never resident at once; and the
gradient all-reduce over the data axes is Alg 4's private-output reduction
at datacenter scale.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, TrainConfig
from repro.models.registry import get_family
from repro.optim import adamw
from repro.optim.compression import compress_tree, init_error_buffers
from repro.runtime import spans


@dataclasses.dataclass(frozen=True)
class TrainState:
    params: Any
    opt: adamw.AdamWState
    err: Any = None  # error-feedback buffers (compression) or None


jax.tree_util.register_dataclass(
    TrainState, data_fields=["params", "opt", "err"], meta_fields=[]
)


def chunked_ce(cfg: ModelConfig, fam, params, hidden, labels, n_chunks: int,
               parallel=None, schedules: dict | None = None):
    """Cross-entropy without materializing [B, S, vocab]: scan over token
    chunks; labels < 0 are masked.  ``schedules`` (a planned-kernel
    schedule set with a "logits" entry, e.g. ``transformer.plan_training``)
    routes the per-chunk logits GEMM through the family's planned head —
    the plan layer sized that cell at exactly this chunk M."""
    from repro.runtime.parallel import constrain

    B, S, d = hidden.shape
    n = n_chunks
    while S % n:
        n -= 1
    hs = hidden.reshape(B, n, S // n, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, n, S // n).transpose(1, 0, 2)
    hs = constrain(hs, parallel, (None, "dp", None, None))
    ls = constrain(ls, parallel, (None, "dp", None))
    lkw = {"schedules": schedules} if schedules else {}

    def step(carry, xs):
        h, lab = xs
        logits = fam.logits(cfg, params, h, **lkw).astype(jnp.float32)
        logits = constrain(logits, parallel, ("dp", None, "tp?"))
        lse = jax.nn.logsumexp(logits, -1)
        tgt = jnp.take_along_axis(logits, jnp.maximum(lab, 0)[..., None], -1)[..., 0]
        mask = (lab >= 0).astype(jnp.float32)
        nll = (lse - tgt) * mask
        return (carry[0] + nll.sum(), carry[1] + mask.sum()), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.zeros(()), jnp.zeros(())), (hs, ls))
    return tot / jnp.maximum(cnt, 1.0)


def make_loss_fn(cfg: ModelConfig, tcfg: TrainConfig, parallel=None):
    """The family registry owns the loss: a family providing a
    ``make_loss_fn(cfg, tcfg, parallel)`` hook (cnn's image
    cross-entropy with planned conv/FC kernels, the dense transformer's
    planned-GEMM chunked CE) builds it here; every other token family
    falls back to the generic forward + chunked-CE composition below —
    no family branching at this call site."""
    fam = get_family(cfg.family)
    hook = getattr(fam, "make_loss_fn", None)
    if hook is not None:
        return hook(cfg, tcfg, parallel)

    dt = jnp.dtype(tcfg.compute_dtype)

    def loss_fn(params, batch):
        extra = {"frames": batch["frames"].astype(dt)} if "frames" in batch else {}
        h, _ = fam.forward(
            cfg, params, batch["tokens"], remat=tcfg.remat, compute_dtype=dt,
            parallel=parallel, **extra,
        )
        return chunked_ce(cfg, fam, params, h, batch["labels"], tcfg.loss_chunks,
                          parallel)

    return loss_fn


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig, parallel=None,
                    grad_specs=None):
    """Returns train_step(state, batch) -> (state, metrics).

    If the batch leaves have an extra leading accumulation dim
    ([n_accum, micro, ...]), gradients are accumulated over it with a scan.
    ``grad_specs`` (PartitionSpec pytree, usually the FSDP/ZeRO specs of
    the optimizer moments) pins the f32 accumulator's sharding: without it
    GSPMD replicates the accumulated gradient over the data axes (an extra
    full-param f32 buffer per device — 78 GiB on grok-1 — fed by an
    all-reduce per microbatch; pinned, the per-micro reduction becomes a
    reduce-scatter, ZeRO-2 style).
    """
    loss_fn = make_loss_fn(cfg, tcfg, parallel)

    def loss_and_counters(params, batch):
        # A family's loss may return (loss, counters): integer counts of
        # what the step did (rows each held expert computed), carried
        # out of the step beside the loss.
        out = loss_fn(params, batch)
        return out if isinstance(out, tuple) else (out, {})

    grad_fn = jax.value_and_grad(loss_and_counters, has_aux=True)

    def _pin(tree):
        if grad_specs is None or parallel is None:
            return tree
        return jax.tree.map(jax.lax.with_sharding_constraint, tree, grad_specs)

    def train_step(state: TrainState, batch: dict):
        params = state.params
        accum = "tokens" in batch and batch["tokens"].ndim == 3
        accum = accum or ("images" in batch and batch["images"].ndim == 5)

        if accum:
            n = jax.tree.leaves(batch)[0].shape[0]

            def acc(carry, mb):
                gsum, lsum = carry
                (l, c), g = grad_fn(params, mb)
                gsum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), gsum, g)
                return (_pin(gsum), lsum + l), c

            g0 = _pin(jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params))
            (grads, loss), counters = jax.lax.scan(acc, (g0, jnp.zeros(())),
                                                   batch)
            counters = jax.tree.map(lambda c: c.sum(0), counters)
            grads = jax.tree.map(lambda g: g / n, grads)
            loss = loss / n
        else:
            (loss, counters), grads = grad_fn(params, batch)
            grads = _pin(jax.tree.map(lambda g: g.astype(jnp.float32), grads))

        err = state.err
        if tcfg.grad_compression == "int8_ef" and err is not None:
            grads, err = compress_tree(
                jax.tree.map(lambda g: g.astype(jnp.float32), grads), err
            )

        new_params, opt, metrics = adamw.apply_updates(params, grads, state.opt,
                                                       tcfg)
        # A non-finite loss or gradient leaves the state as it was, here on
        # the device: a step that is given the optimizer state to update in
        # place (:class:`DonatingStep`) leaves the loop nothing to put back.
        # The choice fuses into the update, so it runs under its scope.
        with jax.named_scope("optimizer"):
            ok = jnp.isfinite(loss) & jnp.isfinite(metrics["grad_norm"])
            params, opt = jax.tree.map(
                lambda new, old: jnp.where(ok, new, old),
                (new_params, opt), (params, state.opt))
        metrics = dict(metrics, loss=loss)
        if counters:
            metrics["counters"] = counters
        return TrainState(params, opt, err), metrics

    return train_step


class DonatingStep:
    """A train step, ``(state, batch) -> (state, metrics)``, jitted with the
    optimizer state donated: the moments are updated in place, so a step
    holds one copy of them (8 bytes a parameter) and not two.  The
    parameters are not donated, so a caller may keep an earlier step's
    (a comparison with a reference does).  A step that sees a non-finite
    loss or gradient returns the state it was given (``make_train_step``),
    so nothing is lost with the donated buffers.  ``lower(state, batch)``
    lowers the step as ``jax.jit``'s does."""

    def __init__(self, step_fn: Callable, in_shardings=None):
        def on_parts(params, opt, err, batch):
            return step_fn(TrainState(params, opt, err), batch)

        kw = {}
        if in_shardings is not None:
            state, batch = in_shardings
            kw["in_shardings"] = (state.params, state.opt, state.err, batch)
        self._jit = jax.jit(on_parts, donate_argnums=(1,), **kw)

    def __call__(self, state: TrainState, batch: dict):
        return self._jit(state.params, state.opt, state.err, batch)

    def lower(self, state: TrainState, batch: dict):
        return self._jit.lower(state.params, state.opt, state.err, batch)


def _donated(tree) -> bool:
    """Whether a buffer of ``tree`` was given to a step that donated it."""
    return any(isinstance(x, jax.Array) and x.is_deleted()
               for x in jax.tree.leaves(tree))


def init_state(cfg: ModelConfig, tcfg: TrainConfig, params) -> TrainState:
    err = init_error_buffers(params) if tcfg.grad_compression == "int8_ef" else None
    return TrainState(params=params, opt=adamw.init(params), err=err)


# ---------------------------------------------------------------------------
# Elastic fault-tolerant training loop (DESIGN.md Sec. 7)
#
# The many-cluster premise of the paper meets production reality here: a
# host WILL die mid-run, and since PR 4 made partitioning a planner output,
# surviving is a *plan-layer* operation — a shrunk mesh is a new MeshSpec,
# so every ShardedSchedule must be re-planned (the ring/psum argmin can
# flip at the new device count) before the checkpoint restores with the
# new shardings.  run_elastic() owns the generic state machine; the
# launcher owns build() (mesh + step_fn + plans + restore for a given
# device count).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RecoveryPolicy:
    """Bounds on the recovery state machine: how many re-meshes before
    giving up, how long to back off between them (doubled per retry), how
    many consecutive non-finite losses are skipped before rolling back
    to the last committed checkpoint, and how many consecutive straggler
    watchdog trips escalate to a :class:`HostFailure` eviction
    (``straggler_patience=0``, the default, keeps the old report-only
    behavior: trips are logged but never acted on)."""

    max_recoveries: int = 3
    backoff_seconds: float = 0.0
    nonfinite_patience: int = 3
    straggler_patience: int = 0


@dataclasses.dataclass
class ElasticRun:
    """Everything run_elastic needs for one incarnation of the run — the
    launcher's ``build(n_devices)`` returns a fresh one after every
    re-mesh (new mesh, re-planned step_fn, restored state)."""

    step_fn: Callable  # (state, batch) -> (state, metrics)
    state: Any
    start: int  # first step this incarnation executes
    n_devices: int = 1
    mesh: Any = None  # context manager (jax Mesh); None -> nullcontext
    # save(step, state): commit a checkpoint.  May return an async handle
    # (anything with .join(), e.g. checkpoint.AsyncSave) — run_elastic then
    # overlaps the write with training and joins it before the *next*
    # commit, at recovery, and at the end, surfacing writer failures at
    # the join point.  A None return means the save was synchronous.
    save: Callable | None = None
    ckpt_dir: str | None = None
    ckpt_every: int = 0
    devices_per_host: int = 1  # devices lost per dead host (TP extent)
    heartbeat: Any = None  # fault_tolerance.Heartbeat
    monitor: Any = None  # fault_tolerance.Monitor
    watchdog: Any = None  # fault_tolerance.StragglerWatchdog
    log_every: int = 10


def run_elastic(build: Callable, source: Callable, steps: int, *,
                policy: RecoveryPolicy | None = None, chaos=None,
                log: Callable = print):
    """Drive training to ``steps`` through failures.

    ``build(n_devices | None)`` -> :class:`ElasticRun`; ``None`` means the
    initial (full) device set.  Per step: heartbeat, monitor poll,
    straggler watchdog; a detected host failure (stale heartbeats, or
    injected via ``chaos``) aborts the step and recovers — shrink to the
    survivors, ``build`` re-meshes + re-plans + restores the last
    committed checkpoint — with bounded retries/backoff.  A non-finite
    loss skips the update (the poisoned state is never committed) and
    after ``nonfinite_patience`` consecutive bad steps rolls back to the
    last good checkpoint.

    The next batch is brought in while the current step runs: step N is
    dispatched on a batch placed during step N-1, its new state is
    committed provisionally, and only then is the source called for
    step N+1 (so a source that reads ``run.state`` sees step N's
    result) and its batch placed, before step N's loss is read.  A
    non-finite loss or a host failure puts the previous state back; the
    prefetched batch stays after a skipped update, since data does not
    depend on state, and any recovery drops it (the rebuilt run fetches
    its start afresh).  The source is never called for a step the call
    does not run.  Where the step donated the previous state's optimizer
    buffers (:class:`DonatingStep`), the step itself returned that state
    for a non-finite loss, and it stays; a loss made non-finite on the host
    alone (the chaos monkey's) then skips nothing.

    Returns ``(final_state, history)`` where history is one record per
    *executed* step (with the step's ``counters`` where its loss gives
    any, e.g. the rows each held expert computed): its ``time`` is the
    ``step`` span
    (:mod:`repro.runtime.spans`), which holds the step's dispatch and
    wait and, but for the last step of a call, the next step's input;
    ``input_s``, ``dispatch_s`` and ``wait_s`` are the spans of the
    step's own batch, dispatch and wait, and ``prefetched`` says whether
    that batch was brought in during the previous step (so its
    ``input_s`` lies in the previous record's ``time``)."""
    from repro.runtime.fault_tolerance import HostFailure

    policy = policy or RecoveryPolicy()
    run: ElasticRun = build(None)
    recoveries = 0
    bad = 0  # consecutive non-finite losses
    slow = 0  # consecutive straggler watchdog trips
    history: list[dict] = []
    step = run.start
    pending = None  # in-flight async checkpoint write (ElasticRun.save)
    ahead = None  # (step, batch, input seconds) brought in by the last step

    def _fetch(at: int) -> tuple:
        """Call the source for step ``at`` and place its batch."""
        with spans.span("step.input", at) as fed:
            batch = {k: jnp.asarray(v) for k, v in source(at).items()}
        return at, batch, fed.seconds

    def _join_pending() -> None:
        """Wait for the in-flight checkpoint write.  This is THE join
        point: a writer-thread failure surfaces here (before the next
        commit / before a restore reads the directory / at the end) —
        never silently."""
        nonlocal pending
        if pending is not None:
            handle, pending = pending, None
            handle.join()

    def _commit(at_step: int, state) -> None:
        nonlocal pending
        _join_pending()
        handle = run.save(at_step, state)
        if handle is not None and hasattr(handle, "join"):
            pending = handle

    def _recover(survivors: int, why: str) -> None:
        nonlocal run, recoveries, bad, slow, step, ahead
        # The last committed write must be on disk before build() restores
        # from it (and a broken writer must not be papered over by
        # restoring something older).
        _join_pending()
        recoveries += 1
        if recoveries > policy.max_recoveries:
            raise RuntimeError(
                f"giving up after {policy.max_recoveries} recoveries ({why})")
        if policy.backoff_seconds:
            time.sleep(policy.backoff_seconds * 2 ** (recoveries - 1))
        log(f"[recover #{recoveries}] {why} -> rebuilding on "
            f"{survivors} device(s)")
        ahead = None  # the rebuilt run fetches its start afresh
        run = build(survivors)
        bad = 0
        slow = 0
        step = run.start

    while step < steps:
        prev = None  # the state before this step, while it is provisional
        try:
            with spans.span("step", step) as whole:
                if chaos is not None:
                    death = chaos.host_death(step, run.n_devices)
                    if death is not None:
                        raise HostFailure(dead=death[0], survivors=death[1])
                    chaos.on_step_start(step)  # straggle: counts into dt
                prefetched = ahead is not None and ahead[0] == step
                _, batch, input_s = ahead if prefetched else _fetch(step)
                ahead = None
                with spans.span("step.dispatch", step) as sent, \
                        (run.mesh if run.mesh is not None
                         else contextlib.nullcontext()):
                    new_state, metrics = run.step_fn(run.state, batch)
                # Provisional commit: the source for step + 1 sees this
                # step's result.  A non-finite loss or a host failure
                # puts ``prev`` back.
                prev, run.state = run.state, new_state
                if step + 1 < steps:
                    ahead = _fetch(step + 1)  # overlaps this step's work
                with spans.span("step.wait", step) as waited:
                    loss = float(jax.block_until_ready(metrics["loss"]))
            dt = whole.seconds
            times = {"time": dt, "input_s": input_s,
                     "dispatch_s": sent.seconds, "wait_s": waited.seconds,
                     "prefetched": prefetched}
            if chaos is not None:
                loss = chaos.poison_loss(step, loss)

            if run.heartbeat is not None:
                run.heartbeat.beat(step)
            if run.monitor is not None:
                stale = run.monitor.stale_hosts()
                if stale:
                    live = len(run.monitor.live_hosts())
                    raise HostFailure(dead=stale,
                                      survivors=live * run.devices_per_host)
            if run.watchdog is not None and run.watchdog.observe(dt):
                slow += 1
                log(f"  [watchdog] step {step} straggled ({dt:.2f}s; "
                    f"trip {slow})")
                # A log line nobody reads is not mitigation: after
                # straggler_patience consecutive trips the slow host is
                # treated as failed, so run_elastic actually evicts it
                # (shrink + re-plan + restore) instead of limping forever.
                if (policy.straggler_patience
                        and slow >= policy.straggler_patience):
                    host = (run.heartbeat.host if run.heartbeat is not None
                            else "straggler")
                    # Evicting the only host degenerates to a same-size
                    # rebuild (a restart is the sole mitigation left).
                    survivors = max(run.devices_per_host,
                                    run.n_devices - run.devices_per_host)
                    raise HostFailure(dead=[host], survivors=survivors)
            else:
                slow = 0

            if not math.isfinite(loss):
                if not _donated(prev):
                    run.state = prev  # the update is skipped
                bad += 1
                log(f"  [guard] step {step}: non-finite loss — update "
                    f"skipped ({bad}/{policy.nonfinite_patience})")
                history.append({"step": step, "loss": loss, **times,
                                "skipped": True})
                if bad >= policy.nonfinite_patience:
                    _recover(run.n_devices,
                             f"{bad} consecutive non-finite losses; rolling "
                             "back to the last committed checkpoint")
                else:
                    step += 1
                continue

            bad = 0
            recoveries = 0  # the cap is on CONSECUTIVE recoveries:
            # a committed step in between proves real progress
            record = {"step": step, "loss": loss, **times, "skipped": False}
            if "counters" in metrics:
                record["counters"] = jax.tree.map(
                    lambda c: c.tolist(), jax.device_get(metrics["counters"]))
            history.append(record)
            if step % run.log_every == 0 or step == steps - 1:
                extra = "".join(
                    f"  {k} {float(metrics[k]):.3g}"
                    for k in ("grad_norm", "lr") if k in metrics)
                extra += "".join(
                    f"  {name} rows {min(c['rows'])}-{max(c['rows'])} "
                    f"(sum {sum(c['rows'])}) pad {c['pad']}"
                    for name, c in record.get("counters", {}).items())
                log(f"step {step:5d}  loss {loss:.4f}{extra}  {dt:.2f}s "
                    f"(input {input_s * 1e3:.1f} ms"
                    f"{' prefetched' if prefetched else ''}, dispatch "
                    f"{sent.seconds * 1e3:.1f} ms, wait "
                    f"{waited.seconds * 1e3:.1f} ms)")
            if (run.save is not None and run.ckpt_every
                    and step and step % run.ckpt_every == 0):
                _commit(step, run.state)
                if chaos is not None and run.ckpt_dir:
                    # Chaos corrupts the checkpoint just written — it must
                    # be on disk first (no overlap under chaos).
                    _join_pending()
                    torn = chaos.after_save(run.ckpt_dir, step)
                    if torn:
                        log(f"  [chaos] tore checkpoint chunk {torn}")
            step += 1
        except HostFailure as e:
            if prev is not None:
                run.state = prev
            _recover(e.survivors, f"host failure: dead={e.dead}")

    if run.save is not None:
        _commit(steps - 1, run.state)
        _join_pending()
    return run.state, history
