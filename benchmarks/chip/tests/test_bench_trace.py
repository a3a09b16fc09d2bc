"""The trace reduction on small synthetic traces."""

from __future__ import annotations

import pytest

from chipbench import trace
from chipbench.trace import Module, Op
from families.cnn import STAGE_PATTERN


def op(start, end, stage=None, collective=False, label=None):
    return Op(start, end, label or f"{stage}.fwd:k", stage, collective)


def steps(*starts, length=45, name="jit_train_step"):
    """The step program's executions, one starting at each of ``starts``,
    with another program run once in between."""
    return ([Module(name, s, s + length) for s in starts]
            + [Module("jit_other", starts[0] + 1, starts[0] + 2)])


@pytest.mark.parametrize("intervals, want", [
    ([(0, 2), (1, 3), (5, 6)], [(0, 3), (5, 6)]),
    ([(5, 6), (0, 1)], [(0, 1), (5, 6)]),
    ([(0, 4), (1, 2)], [(0, 4)]),
    ([(0, 1), (1, 2)], [(0, 2)]),
])
def test_union(intervals, want):
    assert trace.union(intervals) == want


@pytest.mark.parametrize("a, b, want", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 10)], [(-5, 15)], []),
    ([(0, 4), (6, 10)], [(3, 7)], [(0, 3), (7, 10)]),
    ([(0, 10)], [], [(0, 10)]),
])
def test_subtract(a, b, want):
    assert trace.subtract(a, b) == want


def test_busy_union_and_idle_share():
    # Window 0..100 (three step boundaries); ops overlap at 10..30 and
    # 20..40, one op sticks out past the window's end.
    ops = {0: [op(10, 30, "conv0"), op(20, 40, "conv1"), op(60, 70),
               op(90, 120, "fc1")]}
    r = trace.reduce(ops, {0: steps(0, 50, 100)})
    assert r.steps == 2 and r.window_s == pytest.approx(100e-9)
    assert r.chips[0].busy_ns == 30 + 10 + 10  # 10..40, 60..70, 90..100
    assert r.busy_s == pytest.approx(50e-9)
    assert 1 - r.busy_s / r.window_s == pytest.approx(0.5)


def test_scope_attribution_per_step_and_chip():
    ops = {0: [op(0, 10, "conv0"), op(10, 30, "conv1"), op(30, 40, "fc1"),
               op(40, 44)],
           1: [op(0, 20, "conv0"), op(20, 30, "fc2"), op(30, 36)]}
    r = trace.reduce(ops, {0: steps(0, 50, 100), 1: steps(0, 50, 100)})
    # Mean over two chips, per step (2 steps), in ms.
    assert r.stage_ms(["conv0", "conv1"]) == pytest.approx((30 + 20) / 2 / 2 * 1e-6)
    assert r.stage_ms(["fc1", "fc2"]) == pytest.approx((10 + 10) / 2 / 2 * 1e-6)
    assert r.stage_ms(["other"]) == pytest.approx((4 + 6) / 2 / 2 * 1e-6)


def test_exposed_collective_time():
    # A collective over 10..50; compute covers 0..20 and 30..35, so
    # 20..30 and 35..50 of it run with nothing else on the chip.
    ops = {0: [op(0, 20, "conv0"), op(30, 35, "fc1"),
               op(10, 50, collective=True, label="collective.bwd:x")]}
    r = trace.reduce(ops, {0: steps(0, 100)})
    chip = r.chips[0]
    assert chip.collective_ns == 40
    assert chip.exposed_ns == 25
    assert chip.bucket_ns["conv0"] == 20 and chip.bucket_ns["fc1"] == 5


def test_idle_gaps_inside_and_between_steps():
    # Steps run 0..45 and 50..95 (the window ends at the last start, 100);
    # ops cover 0..10, 40..60 and 80..100.
    ops = {0: [op(0, 10, "conv0"), op(40, 60, "conv0"), op(80, 100, "fc1")]}
    r = trace.reduce(ops, {0: steps(0, 50, 100)})
    gaps = dict(r.top("gap_ns"))
    assert gaps[trace.INSIDE] == pytest.approx(50e-9)  # 10..40, 60..80
    assert gaps[trace.BETWEEN] == pytest.approx(0.0)
    r = trace.reduce({0: [op(0, 30, "conv0"), op(55, 70)]},
                     {0: steps(0, 50, 100, length=30)})
    gaps = dict(r.top("gap_ns"))
    assert gaps[trace.BETWEEN] == pytest.approx(40e-9)  # 30..50, 80..100
    assert gaps[trace.INSIDE] == pytest.approx(15e-9)  # 50..55, 70..80


def test_chips_with_a_window_of_their_own():
    ops = {0: [op(0, 10, "conv0")], 1: [op(100, 130, "conv0")]}
    r = trace.reduce(ops, {0: steps(0, 20), 1: steps(100, 150)})
    assert r.window_s == pytest.approx(35e-9)
    assert r.busy_s == pytest.approx(20e-9)


def test_no_window_or_no_device_op_reads_nothing():
    assert trace.reduce({0: [op(0, 10, "conv0")]}, {0: steps(0)}) is None
    assert trace.reduce({0: []}, {0: steps(0, 10)}) is None
    assert trace.reduce({}, {}) is None


@pytest.mark.parametrize("hlo_op, op_name, stage, label", [
    ("custom-call.3", "jit(train_step)/jit(main)/conv1/jit(_conv2d_impl)/pallas_call",
     "conv1", "conv1.fwd:_conv2d_impl"),
    ("custom-call.9", "jit(train_step)/transpose(jvp(fc2))/jit(_dxdw_impl_jit)/pallas_call",
     "fc2", "fc2.bwd:_dxdw_impl_jit"),
    ("fusion.4", "jit(train_step)/jit(main)/mul", None, "other.fwd:mul"),
    ("all-reduce.1", "jit(train_step)/transpose(jvp(conv0))/psum", "conv0",
     "collective.bwd:psum"),
    ("copy.2", "", None, "other.fwd:copy"),
])
def test_classify(hlo_op, op_name, stage, label):
    c = trace.classify(hlo_op, op_name, STAGE_PATTERN)
    assert (c.stage, c.label) == (stage, label)
    assert c.collective == hlo_op.startswith("all-reduce")


def test_hlo_scopes():
    text = (
        '  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
        'calls=%fc, metadata={op_name="jit(step)/conv0/add" source_file="x"}\n'
        '  ROOT %custom-call.2 = bf16[4]{0} custom-call(), '
        'custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(step)/fc1/jit(_fc_matmul_impl)/pallas_call"}\n'
        '  %param = f32[8]{0} parameter(0)\n')
    assert trace.hlo_scopes(text) == {
        "fusion.1": "jit(step)/conv0/add",
        "custom-call.2": "jit(step)/fc1/jit(_fc_matmul_impl)/pallas_call"}


def test_missing_kernels():
    from chipbench.measure import missing_kernels

    def call(op_name):
        return ('  %custom-call.1 = bf16[4]{0} custom-call(), '
                'custom_call_target="tpu_custom_call", '
                f'metadata={{op_name="{op_name}"}}\n')

    text = (call("jit(step)/conv0/jit(_conv2d_impl)/pallas_call")
            + call("jit(step)/transpose(jvp(conv0))/jit(_wgrad_impl_jit)/pallas_call")
            + call("jit(step)/fc1/jit(_fc_matmul_impl)/pallas_call")
            # fc1's backward runs on XLA: a dot, no Mosaic kernel.
            + '  %dot.3 = f32[4]{0} dot(), '
              'metadata={op_name="jit(step)/transpose(jvp(fc1))/dot_general"}\n')
    assert trace.kernel_sites(text, STAGE_PATTERN) == {
        ("conv0", "fwd"), ("conv0", "bwd"), ("fc1", "fwd")}
    assert missing_kernels(text, ["conv0", "fc1"], STAGE_PATTERN) == [
        ("fc1", "bwd")]
