"""Host spans of the training loop and the launcher's set-up.

``span(name, step)`` times its block on ``time.perf_counter_ns`` and
always exposes the duration (``seconds``); the elastic loop's step timer
is one.  While a recorder is installed (``with recording() as rec:``)
each span that completes is also appended to ``rec.spans`` with its
parent and step, and opens a ``jax.profiler.TraceAnnotation`` of the
same name, so that a profile taken meanwhile holds it on the host plane
(``/host:CPU``), on the device trace's clock.  With no recorder a span
costs two clock reads and one check of the module's recorder.

The span names, and what reads each:

* ``step``          - one executed step of ``run_elastic``, from the
                      chaos check to the loss read, the next step's
                      input included (its ``time`` record, the straggler
                      watchdog, the log line);
* ``step.input``    - the data source and ``jnp.asarray`` of the batch.
                      The loop brings each batch in while the previous
                      step runs, so the span of step k's batch lies in
                      step k-1's ``step`` span, between that step's
                      dispatch and wait, and carries step number k; only
                      the first step of a ``run_elastic`` call, or of a
                      run rebuilt after a recovery, fetches in its own;
* ``step.dispatch`` - the call of the jitted step; the first one holds
                      the trace, lowering and compile (or cache read);
* ``step.wait``     - ``block_until_ready`` and the read of the loss;
* ``setup.init``    - the launcher's parameter and optimizer-state
                      initialisation and their placement on the mesh.

The three ``step.*`` durations of step k are also in step k's history
record (``input_s``, ``dispatch_s``, ``wait_s``, with ``prefetched``
true where its input lies in step k-1's span) and in the loop's log
line.

The recorder belongs to the process, as the profiler does, and spans
nest on the thread that runs the loop.
"""

from __future__ import annotations

import contextlib
import time
from typing import NamedTuple

import jax


class Span(NamedTuple):
    name: str
    parent: str | None  # name of the enclosing recorded span
    step: int | None
    start_ns: int  # time.perf_counter_ns
    end_ns: int


class Recorder:
    """The spans completed while it was installed, in order of their end."""

    def __init__(self):
        self.spans: list[Span] = []
        self.open: list[str] = []  # names of the spans open now, innermost last


_recorder: Recorder | None = None


@contextlib.contextmanager
def recording():
    """Install a fresh recorder for the block and yield it."""
    global _recorder
    outer, _recorder = _recorder, Recorder()
    try:
        yield _recorder
    finally:
        _recorder = outer


class span:
    """Time a block; record it while a recorder is installed.  A block
    that raises is not recorded (the loop's records are of steps that
    completed), though its profiler annotation still closes."""

    __slots__ = ("name", "step", "start_ns", "end_ns", "_rec", "_parent",
                 "_annotation")

    def __init__(self, name: str, step: int | None = None):
        self.name, self.step = name, step
        self.start_ns = self.end_ns = 0

    def __enter__(self) -> span:
        self._rec = rec = _recorder
        if rec is not None:
            self._parent = rec.open[-1] if rec.open else None
            rec.open.append(self.name)
            self._annotation = jax.profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end_ns = time.perf_counter_ns()
        rec = self._rec
        if rec is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            rec.open.pop()
            if exc_type is None:
                rec.spans.append(Span(self.name, self._parent, self.step,
                                      self.start_ns, self.end_ns))

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9
