"""Put the device's idle time down to the program's host spans, and split
the rest of the step by the loss and optimizer scopes.

The program records its loop's spans (``repro.runtime.spans``) while a
recorder is installed, and each opens a ``jax.profiler.TraceAnnotation``
of its name, so a profile taken with the host tracer on (level 1) holds
them on the ``/host:CPU`` plane.  Each idle interval of a chip's window
(``trace``'s window: first to last start of the step's program) is put
down to the innermost host span over it: one of ``PARTS``, the rest of
``step``, or no span.

That holds only if the host plane and the device planes share a clock.
Every execution of the step's program must begin inside a
``step.dispatch`` or ``step.wait`` span, since the host neither sends a
step before it dispatches it nor reads the loss before it ran; when more
than ``ALIGN_SHARE`` of a window's executions do not, to within
``ALIGN_NS``, the clocks are not shared and nothing is put down.

The recorder's own list (``host_ms``, ``first``) needs no device clock.
"""

from __future__ import annotations

import collections
import dataclasses
import re

from chipbench import trace

HOST_PLANE = "/host:CPU"
PARTS = ("step.input", "step.dispatch", "step.wait")
STEP_REST = "step (rest)"
NO_SPAN = "no host span"
ALIGN_NS = 50_000.0
ALIGN_SHARE = 0.01
_REST = re.compile(r"(?:^|[/(])(loss|optimizer)(?:[/)]|$)")


@dataclasses.dataclass
class HostIdle:
    """Idle time by host span, mean over chips."""

    idle_s: dict  # PARTS / STEP_REST / NO_SPAN -> idle seconds in the window
    per_step_ms: dict  # the same, in ms per step of each chip
    unaligned: float  # largest share, over chips, of executions outside


def read_host_spans(path: str) -> dict:
    """``step`` and ``step.*`` annotations of the host plane of an
    ``.xplane.pb``: name -> ``[(start_ns, end_ns), ...]``."""
    from jax.profiler import ProfileData

    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "step" or ev.name in PARTS:
                    out[ev.name].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return dict(out)


def _steps(modules) -> list:
    """Executions of the chip's most frequent program, by start."""
    counts = collections.Counter(m.name for m in modules)
    if not counts:
        return []
    name = counts.most_common(1)[0][0]
    return sorted((m for m in modules if m.name == name),
                  key=lambda m: m.start_ns)


def _unaligned(starts, host: dict) -> float:
    """Share of ``starts`` outside every dispatch and wait span."""
    spans = trace.union([(s - ALIGN_NS, e + ALIGN_NS)
                         for name in ("step.dispatch", "step.wait")
                         for s, e in host.get(name, [])])
    outside = sum(1 for t in starts
                  if not any(s <= t <= e for s, e in spans))
    return outside / len(starts)


def idle_by_host_span(chip_ops: dict, chip_modules: dict,
                      host: dict) -> HostIdle | None:
    """The window's idle time by host span, or None when no chip has a
    window or the clocks are not shared."""
    idle, per_step, unaligned = [], [], 0.0
    for chip, modules in sorted(chip_modules.items()):
        steps = _steps(modules)
        if len(steps) < 2:
            continue
        lo, hi = steps[0].start_ns, steps[-1].start_ns
        unaligned = max(unaligned,
                        _unaligned([m.start_ns for m in steps], host))
        busy = trace.union(trace.clip(
            [(o.start_ns, o.end_ns) for o in chip_ops.get(chip, [])], lo, hi))
        left = trace.gaps(busy, lo, hi)
        ns = {}
        for name in (*PARTS, "step"):
            # Parts do not overlap, so what is left after each is taken
            # lies outside it; ``step`` takes what its parts left.
            cover = trace.union(host.get(name, []))
            ns[STEP_REST if name == "step" else name] = (
                trace.length(left) - trace.length(trace.subtract(left, cover)))
            left = trace.subtract(left, cover)
        ns[NO_SPAN] = trace.length(left)
        idle.append(ns)
        per_step.append({k: v / (len(steps) - 1) for k, v in ns.items()})
    if not idle or unaligned > ALIGN_SHARE:
        return None

    def mean(rows, scale):
        return {k: sum(r[k] for r in rows) / len(rows) * scale
                for k in rows[0]}

    return HostIdle(idle_s=mean(idle, 1e-9), per_step_ms=mean(per_step, 1e-6),
                    unaligned=unaligned)


def rest_scopes(scopes: dict, stage_pattern: re.Pattern) -> dict:
    """``scopes`` (HLO instruction -> op_name) with the op_name of every
    operation under no stage but under the ``loss`` or ``optimizer``
    scope replaced by that scope's name.  ``trace.read_xplane`` then
    labels those operations ``other.<direction>:loss`` or ``...:optimizer``,
    and leaves every bucket, ``other`` included, as it was."""
    out = {}
    for hlo_op, op_name in scopes.items():
        staged = trace.classify(hlo_op, op_name, stage_pattern).stage
        m = None if staged else _REST.search(op_name)
        out[hlo_op] = m.group(1) if m else op_name
    return out


def rest_ms(reduction, scope: str) -> float:
    """Device ms per step, per chip, of the ``other`` operations under
    ``scope`` (``loss`` or ``optimizer``), read with ``rest_scopes``."""
    return reduction.per_step_ms(lambda c: sum(
        ns for label, ns in c.label_ns.items()
        if label.startswith("other.") and label.endswith(":" + scope)))


def host_ms(spans, name: str, first_step: int) -> float | None:
    """Mean duration in ms of the recorder's ``name`` spans of steps from
    ``first_step`` on, or None when there are none."""
    ms = [(s.end_ns - s.start_ns) * 1e-6 for s in spans
          if s.name == name and s.step is not None and s.step >= first_step]
    return sum(ms) / len(ms) if ms else None


def first(spans, name: str) -> float | None:
    """Seconds of the recorder's first ``name`` span, or None."""
    for s in spans:
        if s.name == name:
            return (s.end_ns - s.start_ns) * 1e-9
    return None
