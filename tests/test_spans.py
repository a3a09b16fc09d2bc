"""Host spans of the training loop (repro.runtime.spans): recorded only
under a recorder, nested with parent and step, one ``step`` span per
executed step of ``run_elastic`` with its three parts, and on the
profiler's host plane while a profile is taken."""

from __future__ import annotations

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import spans
from repro.runtime import train as tr

PARTS = ("step.input", "step.dispatch", "step.wait")


def test_no_recorder_records_nothing_and_still_times():
    with spans.recording() as rec:
        pass
    with spans.span("step", 0) as s:
        sum(range(1000))
    assert s.end_ns > s.start_ns and s.seconds > 0
    assert rec.spans == []
    assert spans._recorder is None


def test_nested_spans_carry_parent_and_step():
    with spans.recording() as rec:
        with spans.span("step", 3) as outer:
            with spans.span("step.input", 3) as inner:
                pass
        with spans.span("setup.init"):
            pass
    assert [(s.name, s.parent, s.step) for s in rec.spans] == [
        ("step.input", "step", 3), ("step", None, 3),
        ("setup.init", None, None)]
    assert rec.spans[0][3:] == (inner.start_ns, inner.end_ns)
    assert rec.spans[1][3:] == (outer.start_ns, outer.end_ns)
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert rec.open == []


def test_recorders_nest_and_a_raising_block_is_not_recorded():
    with spans.recording() as outer:
        with spans.recording() as inner:
            with pytest.raises(ValueError):
                with spans.span("step", 0):
                    raise ValueError
            with spans.span("step.wait", 1):
                pass
        with spans.span("step", 2):
            pass
    assert [s.name for s in inner.spans] == ["step.wait"]
    assert [(s.name, s.step) for s in outer.spans] == [("step", 2)]
    assert inner.open == [] and outer.open == []


def _toy_build(n_devices):
    @jax.jit
    def step_fn(state, batch):
        loss = jnp.sum(batch["x"]) + state["v"]
        return {"v": state["v"] + 1.0}, {"loss": loss}

    return tr.ElasticRun(step_fn=step_fn, state={"v": jnp.zeros(())},
                         start=0, log_every=2)


def _toy_source(step):
    return {"x": np.full((4,), step, np.float32)}


def test_run_elastic_records_one_step_span_per_executed_step():
    logs = []
    with spans.recording() as rec:
        _, history = tr.run_elastic(_toy_build, _toy_source, 5,
                                    log=logs.append)
    steps = [s for s in rec.spans if s.name == "step"]
    assert [s.step for s in steps] == [h["step"] for h in history] == [
        0, 1, 2, 3, 4]
    assert [h["prefetched"] for h in history] == [False] + [True] * 4
    parts = {(c.name, c.step): c for c in rec.spans if c.parent == "step"}
    assert sorted(parts) == sorted((name, k) for name in PARTS
                                   for k in range(5))
    for s, h in zip(steps, history):
        k = s.step
        # A step's span holds, in order: its own input for the first
        # step, its dispatch, the next step's input but for the last
        # step, and its wait.
        held = ([("step.input", k)] if k == 0 else []) + [
            ("step.dispatch", k)] + (
            [("step.input", k + 1)] if k < 4 else []) + [("step.wait", k)]
        inside = sorted((c for c in parts.values()
                         if s.start_ns <= c.start_ns <= s.end_ns),
                        key=lambda c: c.start_ns)
        assert [(c.name, c.step) for c in inside] == held
        for a, b in zip(inside, inside[1:]):
            assert a.end_ns <= b.start_ns
        assert inside[-1].end_ns <= s.end_ns
        assert h["time"] == pytest.approx((s.end_ns - s.start_ns) * 1e-9,
                                          abs=1e-12)
        for name, key in zip(PARTS, ("input_s", "dispatch_s", "wait_s")):
            c = parts[name, k]
            assert h[key] == pytest.approx((c.end_ns - c.start_ns) * 1e-9,
                                           abs=1e-12)
        assert sum((c.end_ns - c.start_ns) * 1e-9 for c in inside) <= h["time"]
    assert {s.name for s in rec.spans} == {"step", *PARTS}
    # The periodic log line says where each logged step went.
    lines = [x for x in logs if x.startswith("step")]
    assert len(lines) == 3  # steps 0, 2 and the last
    assert all("input" in x and "dispatch" in x and "wait" in x
               for x in lines)
    assert ["prefetched" in x for x in lines] == [False, True, True]


def test_run_elastic_without_a_recorder_keeps_its_step_records():
    _, history = tr.run_elastic(_toy_build, _toy_source, 3,
                                log=lambda *_: None)
    assert [h["step"] for h in history] == [0, 1, 2]
    assert [h["prefetched"] for h in history] == [False, True, True]
    for h, after in zip(history, history[1:] + [None]):
        # Its dispatch and wait, its own input if it was not prefetched,
        # and the next step's input all lie in a step's time.
        held = h["dispatch_s"] + h["wait_s"]
        held += 0.0 if h["prefetched"] else h["input_s"]
        held += after["input_s"] if after is not None else 0.0
        assert h["time"] >= held
        assert min(h["input_s"], h["dispatch_s"], h["wait_s"]) >= 0


def test_step_spans_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    with spans.recording() as rec:
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            tr.run_elastic(_toy_build, _toy_source, 3, log=lambda *_: None)
        finally:
            jax.profiler.stop_trace()
    path = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    names = [ev.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events]
    for name in ("step", *PARTS):
        assert names.count(name) == 3, name
    assert len(rec.spans) == 12


def test_launcher_records_its_initialisation_once():
    from repro.launch import train as launcher

    with spans.recording() as rec:
        result = launcher.main(["--family", "cnn", "--steps", "2",
                                "--batch", "2", "--log-every", "1"])
    assert len(result.history) == 2
    names = [s.name for s in rec.spans]
    assert names.count("setup.init") == 1
    init = rec.spans[names.index("setup.init")]
    first = min((s for s in rec.spans if s.name == "step"),
                key=lambda s: s.start_ns)
    assert init.parent is None and init.end_ns <= first.start_ns
