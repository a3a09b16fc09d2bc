"""One-batch input prefetch in the elastic loop (runtime/train.py
run_elastic): the source for step N+1 is called after step N's dispatch
and provisional commit and before its loss read, never for a step the
call does not run; a non-finite loss rolls the state back and keeps the
prefetched batch; a recovery drops it; no state buffer is donated."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import train as tr
from repro.runtime.chaos import ChaosConfig, ChaosMonkey


class _Loss:
    """A loss whose read the loop's event list sees."""

    def __init__(self, events, step):
        self.events, self.step = events, step

    def block_until_ready(self):
        self.events.append(("read", self.step))
        return self

    def __float__(self):
        return 1.0


class Recorded:
    """A build and a source that log, in order, each source call (with
    the ``v`` of ``run.state`` it sees), each dispatch (with the state's
    ``v`` and the batch's step) and each loss read.  ``v`` counts the
    committed updates; ``starts`` gives each incarnation's first step."""

    def __init__(self, starts=(0,), **run_kw):
        self.starts = list(starts)
        self.run_kw = run_kw
        self.events: list = []
        self.runs: list = []
        self.fetched: list = []

    def build(self, n_devices):
        events = self.events

        def step_fn(state, batch):
            at = int(batch["x"][0])
            events.append(("dispatch", at, state["v"]))
            return {"v": state["v"] + 1}, {"loss": _Loss(events, at)}

        start = self.starts[min(len(self.runs), len(self.starts) - 1)]
        self.runs.append(tr.ElasticRun(
            step_fn=step_fn, state={"v": start}, start=start,
            n_devices=4 if n_devices is None else n_devices,
            devices_per_host=2, **self.run_kw))
        return self.runs[-1]

    def source(self, step):
        self.fetched.append(step)
        self.events.append(("source", step, self.runs[-1].state["v"]))
        return {"x": np.full((1,), step, np.float32)}


def test_source_for_the_next_step_sees_this_steps_result_before_its_read():
    rec = Recorded()
    state, history = tr.run_elastic(rec.build, rec.source, 4,
                                    log=lambda *_: None)
    assert rec.events == [
        ("source", 0, 0), ("dispatch", 0, 0),
        ("source", 1, 1), ("read", 0),
        ("dispatch", 1, 1), ("source", 2, 2), ("read", 1),
        ("dispatch", 2, 2), ("source", 3, 3), ("read", 2),
        ("dispatch", 3, 3), ("read", 3)]
    assert state["v"] == 4
    assert [h["step"] for h in history] == [0, 1, 2, 3]


def test_the_source_is_never_called_for_a_step_the_call_does_not_run():
    """Two calls on one run, as a warm-up and then a window: the first
    never fetches the second's first step, which is not prefetched."""
    rec = Recorded()

    def same(n_devices):
        return rec.runs[0] if rec.runs else rec.build(n_devices)

    _, warm = tr.run_elastic(same, rec.source, 5, log=lambda *_: None)
    assert rec.fetched == [0, 1, 2, 3, 4]
    rec.runs[0].start = 5
    state, window = tr.run_elastic(same, rec.source, 9, log=lambda *_: None)
    assert rec.fetched == list(range(9))
    assert state["v"] == 9
    assert [h["prefetched"] for h in warm] == [False, True, True, True, True]
    assert [h["prefetched"] for h in window] == [False, True, True, True]


def test_prefetched_in_records_and_in_the_log_line():
    rec, logs = Recorded(log_every=1), []
    _, history = tr.run_elastic(rec.build, rec.source, 3, log=logs.append)
    assert [h["prefetched"] for h in history] == [False, True, True]
    lines = [x for x in logs if x.startswith("step")]
    assert len(lines) == 3
    assert "prefetched" not in lines[0]
    assert all("prefetched" in x for x in lines[1:])


def test_nonfinite_rolls_back_and_the_next_step_runs_on_the_prefetched_batch():
    rec = Recorded()
    chaos = ChaosMonkey(ChaosConfig(nan_at_step=2, nan_steps=1))
    state, history = tr.run_elastic(
        rec.build, rec.source, 5,
        policy=tr.RecoveryPolicy(nonfinite_patience=3), chaos=chaos,
        log=lambda *_: None)
    # Step 3's batch was fetched once, while step 2 ran, from step 2's
    # provisional state; step 3 ran from the rolled-back state on it.
    assert rec.fetched == [0, 1, 2, 3, 4]
    assert ("source", 3, 3) in rec.events
    assert ("dispatch", 3, 2) in rec.events
    assert [h["skipped"] for h in history] == [False, False, True, False,
                                                False]
    assert [h["prefetched"] for h in history] == [False, True, True, True,
                                                  True]
    assert state["v"] == 4  # the skipped update never reached the state


@pytest.mark.parametrize("restored", [2, 3])
def test_host_death_drops_the_prefetched_batch_and_replays_from_the_start(
        restored):
    """The batch for step 3, fetched while step 2 ran, is dropped when
    the host dies at step 3; the rebuilt run, restored at step 2 or at
    step 3 itself, fetches from its start afresh."""
    rec, logs = Recorded(starts=(0, restored)), []
    chaos = ChaosMonkey(ChaosConfig(kill_at_step=3), devices_per_host=2)
    state, history = tr.run_elastic(rec.build, rec.source, 5, chaos=chaos,
                                    log=logs.append)
    assert rec.fetched == [0, 1, 2, 3, *range(restored, 5)]
    assert any("recover #1" in x for x in logs)
    replay = list(range(restored, 5))
    assert [h["step"] for h in history] == [0, 1, 2, *replay]
    assert [h["prefetched"] for h in history] == [
        False, True, True, False, *[True] * (len(replay) - 1)]
    # The replay's first step ran on a batch fetched after the rebuild.
    rebuilt = len(rec.events) - rec.events[::-1].index(
        ("source", restored, restored)) - 1
    assert rec.events[rebuilt + 1] == ("dispatch", restored, restored)
    assert state["v"] == 5


def test_a_host_failure_after_dispatch_leaves_no_provisional_state():
    """A watchdog eviction comes after the step's provisional commit:
    the aborted run holds the state of its last committed step."""

    class Trips:
        def __init__(self):
            self.verdicts = [False, True, True, True]

        def observe(self, dt):
            return self.verdicts.pop(0) if self.verdicts else False

    rec = Recorded(watchdog=Trips())
    state, _ = tr.run_elastic(
        rec.build, rec.source, 5,
        policy=tr.RecoveryPolicy(straggler_patience=3), log=lambda *_: None)
    assert len(rec.runs) == 2
    assert rec.runs[0].state["v"] == 3  # steps 0-2 committed, 3 aborted
    assert state["v"] == 5


def test_the_launchers_step_donates_no_state_buffer():
    from repro.launch import train as launcher
    from repro.models import cnn

    result = launcher.main(["--family", "cnn", "--steps", "1", "--batch",
                            "2", "--log-every", "1"])
    batch = {"images": jax.ShapeDtypeStruct((2, cnn.IMG, cnn.IMG, cnn.IN_CH),
                                            jnp.float32),
             "labels": jax.ShapeDtypeStruct((2,), jnp.int32)}
    lowered = result.step_fn.lower(result.state, batch)
    (params, opt, err, fed), _ = lowered.args_info
    # No buffer that a caller may keep is donated: the parameters (a
    # comparison holds an earlier step's), the batch.  The optimizer's
    # moments are updated in place.
    assert not any(a.donated for a in jax.tree.leaves((params, err, fed)))
    assert all(a.donated for a in jax.tree.leaves((opt.m, opt.v)))


def test_a_non_finite_step_returns_the_state_it_was_given():
    """The optimizer state is donated to the step, so the step itself
    skips an update whose loss is not finite."""
    from repro.configs.base import TrainConfig
    from repro.configs.registry import smoke_config
    from repro.models import cnn
    from repro.models.module import init_params

    cfg = smoke_config("cnn-vgg11")
    tcfg = TrainConfig(param_dtype="float32", compute_dtype="float32",
                       loss_chunks=1)
    state = tr.init_state(cfg, tcfg, init_params(
        cnn.param_defs(cfg), jax.random.PRNGKey(0), jnp.float32))
    want = jax.tree.map(np.asarray, state)
    step = tr.DonatingStep(tr.make_train_step(cfg, tcfg))
    batch = {"images": jnp.full((2, cnn.IMG, cnn.IMG, cnn.IN_CH), jnp.nan),
             "labels": jnp.zeros((2,), jnp.int32)}
    new, metrics = step(state, batch)
    assert not np.isfinite(float(metrics["loss"]))
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), b)
