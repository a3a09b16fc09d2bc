"""Idle time put down to the program's host spans, the alignment of the
host and device clocks, and the rest of the step split by scope, on
small synthetic traces; and one traced window at the small size on the
CPU."""

from __future__ import annotations

import pathlib

import pytest

from chipbench import cell as cells
from chipbench import hostspans, trace
from chipbench.hostspans import NO_SPAN, STEP_REST
from chipbench.trace import Module, Op
from families.cnn import STAGE_PATTERN

ROOT = pathlib.Path(__file__).resolve().parents[3]


def op(start, end, stage="conv0"):
    return Op(start, end, f"{stage}.fwd:k", stage, False)


def host_steps(offset=0.0):
    """Three steps of 100 ns: input 0..30, dispatch 30..40, wait 40..95,
    then 5 ns of loop between steps."""
    spans = {"step": [], "step.input": [], "step.dispatch": [],
             "step.wait": []}
    for k in range(3):
        t = 100 * k + offset
        spans["step"].append((t, t + 95))
        spans["step.input"].append((t, t + 30))
        spans["step.dispatch"].append((t + 30, t + 40))
        spans["step.wait"].append((t + 40, t + 95))
    return spans


def device(starts=(35, 135, 235)):
    """The step's program begins in each dispatch and runs 50 ns; one op
    covers 35..85 of each execution, so the chip idles from each
    execution's end to the next one's start."""
    modules = {0: [Module("jit_train_step", s, s + 50) for s in starts]}
    ops = {0: [op(s, s + 50) for s in starts]}
    return ops, modules


def test_idle_goes_to_the_innermost_host_span():
    ops, modules = device()
    idle = hostspans.idle_by_host_span(ops, modules, host_steps())
    # Window 35..235; idle 85..135 and 185..235, each: wait 85..95, the
    # loop between steps 95..100, input 100..130, dispatch 130..135.
    assert idle.idle_s == pytest.approx({
        "step.input": 60e-9, "step.dispatch": 10e-9, "step.wait": 20e-9,
        STEP_REST: 0.0, NO_SPAN: 10e-9})
    assert idle.per_step_ms["step.input"] == pytest.approx(30e-6)
    assert sum(idle.idle_s.values()) == pytest.approx(
        trace.reduce(ops, modules).window_s - trace.reduce(ops,
                                                           modules).busy_s)
    assert idle.unaligned == 0.0


def test_idle_in_the_step_outside_its_parts():
    ops, modules = device()
    host = host_steps()
    # The chaos check and straggle sit in the step before its input.
    host["step.input"] = [(s + 20, e) for s, e in host["step.input"]]
    idle = hostspans.idle_by_host_span(ops, modules, host)
    assert idle.idle_s["step.input"] == pytest.approx(20e-9)
    assert idle.idle_s[STEP_REST] == pytest.approx(40e-9)


def test_shifted_host_clock_reads_nothing():
    ops, modules = device()
    # Shifted by 60 us, beyond the 50 us allowed, every execution begins
    # outside its dispatch and wait.
    host = {k: [(s + 60_000, e + 60_000) for s, e in v]
            for k, v in host_steps().items()}
    assert hostspans.idle_by_host_span(ops, modules, host) is None
    # Within the allowance the clocks count as shared.
    near = {k: [(s + 40_000, e + 40_000) for s, e in v]
            for k, v in host_steps().items()}
    assert hostspans.idle_by_host_span(ops, modules, near) is not None
    # No host spans at all (the host tracer off): nothing either.
    assert hostspans.idle_by_host_span(ops, modules, {}) is None


def test_rest_split_by_scope_keeps_the_other_total():
    scopes = {
        "custom-call.1": "jit(train_step)/conv0/jit(_conv2d_impl)/pallas_call",
        "fusion.2": "jit(train_step)/jvp(loss)/reduce_max",
        "fusion.3": "jit(train_step)/transpose(jvp(loss))/mul",
        "fusion.4": "jit(train_step)/optimizer/sqrt",
        "convert.5": "jit(train_step)/convert_element_type",
        "fusion.6": "jit(train_step)/conv1/lossy/add",
        "all-reduce.7": "jit(train_step)/optimizer/psum",
    }
    events = [("custom-call.1", 0, 10), ("fusion.2", 10, 12),
              ("fusion.3", 12, 15), ("fusion.4", 15, 25),
              ("convert.5", 25, 27), ("fusion.6", 27, 30),
              ("all-reduce.7", 30, 34)]
    modules = {0: [Module("jit_train_step", 0, 40),
                   Module("jit_train_step", 40, 80)]}

    def reduced(names):
        ops = [trace.classify(h, names.get(h, ""), STAGE_PATTERN)
               for h, _, _ in events]
        ops = [Op(s, e, o.label, o.stage, o.collective)
               for o, (_, s, e) in zip(ops, events)]
        return trace.reduce({0: ops}, modules)

    plain = reduced(scopes)
    split = reduced(hostspans.rest_scopes(scopes, STAGE_PATTERN))
    assert split.chips[0].bucket_ns == plain.chips[0].bucket_ns
    assert split.stage_ms(["other"]) == pytest.approx(17e-6)
    assert hostspans.rest_ms(split, "loss") == pytest.approx(5e-6)
    assert hostspans.rest_ms(split, "optimizer") == pytest.approx(10e-6)
    assert (hostspans.rest_ms(split, "loss")
            + hostspans.rest_ms(split, "optimizer")
            <= split.stage_ms(["other"]))
    # A stage's op whose scope only looks alike stays with its stage, and
    # the plain scopes split nothing.
    assert split.chips[0].bucket_ns["conv1"] == 3
    assert hostspans.rest_ms(plain, "loss") == 0.0


def test_recorder_means_and_first_spans():
    from repro.runtime.spans import Span

    spans = [Span("setup.init", None, None, 0, 2_000_000_000),
             Span("step.dispatch", "step", 0, 0, 9_000_000_000),
             Span("step.dispatch", "step", 5, 0, 300_000),
             Span("step.dispatch", "step", 6, 0, 500_000),
             Span("step.input", "step", 6, 0, 4_000_000)]
    assert hostspans.first(spans, "setup.init") == pytest.approx(2.0)
    assert hostspans.first(spans, "step.dispatch") == pytest.approx(9.0)
    assert hostspans.host_ms(spans, "step.dispatch", 5) == pytest.approx(0.4)
    assert hostspans.host_ms(spans, "step.wait", 5) is None
    assert hostspans.first(spans, "step.wait") is None


def test_traced_window_off_the_chip_reads_the_host(small_program,
                                                   small_config):
    import trace_host

    cell = cells.load(ROOT, "vgg11-b256")
    cell.config = small_config
    cell.traffic = dict(cell.traffic, batch=8)
    line = trace_host.traced(cell, 2**31 + 7, 0.3, host_level=1)
    assert line["window_steps"] > 0
    parts = [line[k] for k in ("host_input_ms", "host_dispatch_ms",
                               "host_wait_ms")]
    assert all(p > 0 for p in parts)
    # The feed closes the window between a step's dispatch and its loss
    # read (the loop fetches the next batch in between), so the window's
    # intervals hold one wait fewer than the spans' means count.
    assert sum(parts) <= (line["loop_step_ms"]
                          + line["host_wait_ms"] / line["window_steps"])
    assert line["setup_init_s"] > 0
    assert line["setup_compile_s"] * 1e3 > line["host_dispatch_ms"]
    # The CPU's trace has no TPU plane: no device number is made up.
    assert "other_device_ms" not in line and "idle_in_input_ms" not in line
