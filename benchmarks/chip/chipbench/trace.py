"""Reduce a profiler trace of the measured window to per-layer numbers.

Device operations come from each chip's ``XLA Ops`` line.  Each is tied
to the compiled step's HLO instruction of the same name, whose ``op_name``
metadata carries the model's ``jax.named_scope`` stage (found by the
family's ``STAGE_PATTERN``) and, under ``transpose(``, the backward pass.
Collectives are kept apart from the stages.  The window of a chip runs
from the start of the first to the start of the last execution of the
step's program on its ``XLA Modules`` line (the program executed most
often), so its steps are the executions between.  An idle gap lies either inside a step's
program or between two of them, where the chip waits on the host loop.

The trace is taken with host tracing off: with it on, the host records
every chunk of the layout transpose of each batch that it sends, and the
loop it traces slows several times over.
"""

from __future__ import annotations

import collections
import dataclasses
import re

BETWEEN = "between steps (host loop: batch transfer, dispatch, loss read)"
INSIDE = "inside a step"
_KERNEL = re.compile(r"jit\((\w+)\)/pallas_call")
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
_EVENT = re.compile(r"\s*%?([\w.\-]+)(?:\s*=|$)")
_HLO_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?metadata=\{[^}]*?op_name=\"([^\"]*)\"")


@dataclasses.dataclass(frozen=True)
class Op:
    """One device operation: its interval on the trace clock and where it
    belongs."""

    start_ns: float
    end_ns: float
    label: str  # "<stage>.<fwd|bwd>:<kernel or op>", for the breakdown
    stage: str | None  # the stage's scope, or None
    collective: bool


@dataclasses.dataclass(frozen=True)
class Module:
    """One execution of a compiled program on a chip."""

    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Chip:
    """One chip's window: busy union, time by bucket, collectives, gaps."""

    busy_ns: float
    bucket_ns: dict  # stage name / "other" / "collective" -> ns
    collective_ns: float
    exposed_ns: float  # collective time with no other op on the chip
    label_ns: dict  # op label -> ns
    gap_ns: dict  # INSIDE / BETWEEN -> idle ns
    window_ns: float
    steps: int


@dataclasses.dataclass
class Reduction:
    chips: list

    def mean(self, fn) -> float:
        return sum(fn(c) for c in self.chips) / len(self.chips)

    @property
    def steps(self) -> int:
        return self.chips[0].steps

    @property
    def busy_s(self) -> float:
        return self.mean(lambda c: c.busy_ns) * 1e-9

    @property
    def window_s(self) -> float:
        return self.mean(lambda c: c.window_ns) * 1e-9

    def per_step_ms(self, fn) -> float:
        """Mean over chips of ``fn(chip)`` ns per step of that chip, in ms."""
        return self.mean(lambda c: fn(c) / c.steps) * 1e-6

    def stage_ms(self, stages) -> float:
        return self.per_step_ms(
            lambda c: sum(c.bucket_ns.get(s, 0.0) for s in stages))

    def top(self, attr: str, n: int = 10) -> list:
        """The ``n`` largest entries of a per-chip dict, as mean seconds."""
        total = collections.Counter()
        for c in self.chips:
            total.update(getattr(c, attr))
        return [[k, v * 1e-9 / len(self.chips)]
                for k, v in total.most_common(n)]


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    b = union(b)
    out = []
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo: float, hi: float) -> list:
    """Idle intervals of ``[lo, hi]`` outside the disjoint sorted ``busy``."""
    return subtract([(lo, hi)], busy)


def attribute(gap_list, modules) -> dict:
    """Idle ns inside the step's program executions and between them."""
    inside = length(union(
        [(max(s, m.start_ns), min(e, m.end_ns)) for s, e in gap_list
         for m in modules if min(e, m.end_ns) > max(s, m.start_ns)]))
    return {INSIDE: inside, BETWEEN: length(gap_list) - inside}


def reduce_chip(ops, modules) -> Chip | None:
    """One chip's window: from the first to the last start of its most
    frequent program, or None when it ran fewer than two."""
    counts = collections.Counter(m.name for m in modules)
    if not counts:
        return None
    name, n = counts.most_common(1)[0]
    steps = sorted((m for m in modules if m.name == name),
                   key=lambda m: m.start_ns)
    if n < 2:
        return None
    lo, hi = steps[0].start_ns, steps[-1].start_ns
    ops = [o for o in ops if o.end_ns > lo and o.start_ns < hi]
    ivs = lambda sel: clip([(o.start_ns, o.end_ns) for o in sel], lo, hi)
    busy = union(ivs(ops))
    bucket = collections.Counter()
    labels = collections.Counter()
    for o in ops:
        d = min(o.end_ns, hi) - max(o.start_ns, lo)
        bucket["collective" if o.collective else (o.stage or "other")] += d
        labels[o.label] += d
    coll = union(ivs([o for o in ops if o.collective]))
    compute = ivs([o for o in ops if not o.collective])
    return Chip(
        busy_ns=length(busy), bucket_ns=dict(bucket),
        collective_ns=float(bucket.get("collective", 0.0)),
        exposed_ns=length(subtract(coll, compute)),
        label_ns=dict(labels),
        gap_ns=attribute(gaps(busy, lo, hi), steps[:-1]),
        window_ns=hi - lo, steps=n - 1)


def reduce(chip_ops: dict, chip_modules: dict) -> Reduction | None:
    """The window's reduction over the chips that ran the step, or None
    when none ran it twice with a device operation in between."""
    chips = [reduce_chip(chip_ops.get(c, []), mods)
             for c, mods in sorted(chip_modules.items())]
    chips = [c for c in chips if c is not None and c.busy_ns > 0]
    return Reduction(chips=chips) if chips else None


def hlo_scopes(hlo_text: str) -> dict:
    """HLO instruction name -> ``op_name`` metadata of a compiled module."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def kernel_sites(hlo_text: str, stage_pattern: re.Pattern) -> set:
    """``(stage, "fwd" | "bwd")`` of every Mosaic kernel
    (``tpu_custom_call``) of a compiled module that lies under a stage."""
    sites = set()
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m and 'custom_call_target="tpu_custom_call"' in line:
            stage = stage_pattern.search(m.group(2))
            if stage:
                sites.add((stage.group(1),
                           "bwd" if "transpose(" in m.group(2) else "fwd"))
    return sites


def classify(hlo_op: str, op_name: str, stage_pattern: re.Pattern) -> Op:
    """Stage, direction and label of one operation (interval left 0)."""
    stage = stage_pattern.search(op_name)
    stage = stage.group(1) if stage else None
    kernel = _KERNEL.search(op_name)
    if kernel:
        what = kernel.group(1)
    elif op_name:
        what = op_name.rsplit("/", 1)[-1]
    else:
        what = re.sub(r"\.\d+$", "", hlo_op)
    direction = "bwd" if "transpose(" in op_name else "fwd"
    collective = bool(_COLLECTIVE.search(hlo_op))
    label = f"{'collective' if collective else stage or 'other'}.{direction}:{what}"
    return Op(0.0, 0.0, label, stage, collective)


def instruction(event_name: str) -> str:
    """The HLO instruction an ``XLA Ops`` event stands for: the TPU trace
    names each event by the instruction's text, ``%name = shape op(...)``."""
    m = _EVENT.match(event_name)
    return m.group(1) if m else event_name


def read_xplane(path: str, scopes: dict, stage_pattern: re.Pattern):
    """Device operations and program executions by chip from an
    ``.xplane.pb``.  ``scopes`` maps HLO instruction names to their
    ``op_name``; ``stage_pattern`` finds a stage in one."""
    from jax.profiler import ProfileData

    chip_ops: dict = collections.defaultdict(list)
    chip_modules: dict = collections.defaultdict(list)
    kinds: dict = {}
    for plane in ProfileData.from_file(path).planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if not m:
            continue
        chip = int(m.group(1))
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    chip_modules[chip].append(Module(
                        re.sub(r"\(\d+\)$", "", ev.name), ev.start_ns,
                        ev.start_ns + ev.duration_ns))
            elif line.name == "XLA Ops":
                for ev in line.events:
                    k = kinds.get(ev.name)
                    if k is None:
                        hlo_op = instruction(ev.name)
                        k = kinds[ev.name] = classify(
                            hlo_op, scopes.get(hlo_op, ""), stage_pattern)
                    chip_ops[chip].append(dataclasses.replace(
                        k, start_ns=ev.start_ns,
                        end_ns=ev.start_ns + ev.duration_ns))
    return dict(chip_ops), dict(chip_modules)
