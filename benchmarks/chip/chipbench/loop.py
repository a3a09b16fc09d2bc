"""Drive the program's own launcher and training loop, and time it.

``repro.launch.train.main`` builds the run as a user's command line would
(mesh, sharded state, jitted step with planned kernels) and hands it to
``repro.runtime.train.run_elastic``.  Two things are replaced for the
duration of the call, and nothing else: the launcher's data source, by a
:class:`Feed` over the benchmark's seeded batches, and ``run_elastic``,
by a wrapper that calls the original twice on the run the launcher built:
first for the warm-up steps, then for the measured window, which the feed
closes by raising :class:`WindowClosed` once ``seconds`` have passed.

Each step is timed at the loop boundary, from one call of the feed to the
next, on ``time.perf_counter``.  The warm-up steps also read the state
the comparison with the reference needs: the parameters before the first
step, the optimizer's first moment after it, and the parameters after
``REFERENCE_STEPS`` steps.
"""

from __future__ import annotations

import contextlib
import time
from unittest import mock

import jax
import jax.numpy as jnp

from chipbench.reference import Side, norms

REFERENCE_STEPS = 3
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class WindowClosed(Exception):
    """Raised by the feed at the first step boundary after the window."""


_norms = jax.jit(norms)


@jax.jit
def _change_norms(new, old):
    return norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), new, old))


class Capture:
    """The program's side of the comparison, read from its state during
    the warm-up steps."""

    def __init__(self, beta1: float):
        self.beta1 = beta1
        self.start = None  # parameters before the first step
        self.grad = None
        self.update = None

    def reads(self) -> dict:
        return {0: self._first, 1: self._after_one,
                REFERENCE_STEPS: self._after_all}

    def _first(self, state):
        self.start = state.params

    def _after_one(self, state):
        # After one step from zero moments, m = (1 - beta1) * clipped grad.
        self.grad = {k: float(v) / (1.0 - self.beta1)
                     for k, v in _norms(state.opt.m).items()}

    def _after_all(self, state):
        self.update = {k: float(v) for k, v in
                       _change_norms(state.params, self.start).items()}
        self.start = None

    def side(self, history) -> Side:
        return Side([h["loss"] for h in history[:REFERENCE_STEPS]],
                    self.grad, self.update)


class Feed:
    """The loop's data source: batch ``step % len(pool)`` of the pool."""

    def __init__(self, pool, warmup_steps: int, seconds: float, reads: dict):
        if warmup_steps <= REFERENCE_STEPS:
            raise ValueError(f"warm-up of {warmup_steps} steps does not reach "
                             f"the state after {REFERENCE_STEPS} steps")
        self.pool, self.warmup_steps, self.seconds = pool, warmup_steps, seconds
        self.reads = reads
        self.run = None  # the ElasticRun being driven
        self.marks: list[float] = []  # perf_counter at each window boundary
        self.in_window = False
        self.nonfinite = 0  # window steps the loop skipped as non-finite
        self.first = None  # perf_counter at the first step's handout

    def log(self, message: str) -> None:
        """The loop's log: printed, and its non-finite guard counted."""
        if self.in_window and "non-finite" in message:
            self.nonfinite += 1
        print(message)

    def __call__(self, step: int) -> dict:
        now = time.perf_counter()
        if self.first is None:
            self.first = now
        if self.in_window:
            self.marks.append(now)
            if now - self.marks[0] >= self.seconds:
                raise WindowClosed
        elif step in self.reads:
            self.reads[step](self.run.state)
        return self.pool[step]

    @property
    def intervals(self) -> list[float]:
        return [b - a for a, b in zip(self.marks, self.marks[1:])]


class CompileLog:
    """Backend compiles (including persistent-cache reads) in the window,
    and the persistent cache's hits and misses."""

    def __init__(self, feed: Feed):
        self.feed = feed
        self.window_n = 0
        self.hits = 0
        self.misses = 0

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE and self.feed.in_window:
            self.window_n += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @contextlib.contextmanager
    def listening(self):
        from jax._src import monitoring

        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)
        try:
            yield self
        finally:
            monitoring.unregister_event_duration_listener(self._duration)
            monitoring.unregister_event_listener(self._event)


def launch(argv: list, feed: Feed, trace_dir: str | None = None):
    """Run the launcher on ``argv`` with ``feed`` as its data source.
    Returns ``(LaunchResult, warm-up history)``; the window's boundaries
    are in ``feed.marks``."""
    from repro.launch import train as launcher
    from repro.runtime import train as tr

    original = tr.run_elastic
    warm: list = []

    def driven(build, source, steps, **kw):
        run = feed.run = build(None)

        def same(n_devices):
            if n_devices is not None:
                raise RuntimeError(
                    f"the loop asked to recover onto {n_devices} devices; "
                    "a measured run allows no recovery")
            return run

        _, history = original(same, feed, feed.warmup_steps, log=feed.log,
                              **kw)
        warm.extend(history)
        run.start = feed.warmup_steps
        feed.in_window = True
        if trace_dir is not None:
            options = jax.profiler.ProfileOptions()
            # Host tracing off: it records every chunk of the host-side
            # layout transpose of each batch, a million spans a second,
            # and slows the loop it traces several times over.
            options.python_tracer_level = 0
            options.host_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            original(same, feed, steps, log=feed.log, **kw)
        except WindowClosed:
            pass
        else:
            raise RuntimeError(f"the loop ran out of its {steps} steps "
                               "before the window closed")
        finally:
            feed.in_window = False
            if trace_dir is not None:
                jax.profiler.stop_trace()
        return run.state, history

    with mock.patch.object(tr, "run_elastic", driven), \
            mock.patch.object(launcher, "make_data_source",
                              lambda *a, **k: feed):
        result = launcher.main(argv)
    return result, warm
