"""Compile the planned cnn-vgg11 kernels for a described TPU v5e.

Interpret mode runs a kernel on the CPU and hides what the chip's
compiler refuses: slices not aligned to the tiling, scoped VMEM over the
limit, block specs the lowering does not take.  These tests compile each
planned kernel of the cnn-vgg11 training step at its published widths,
batch 256, bf16, against a ``v5e:2x2`` topology that is described but not
attached, and check that each lowers to a Mosaic ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library.
"""

from __future__ import annotations

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

BATCH = 256
STAGES = ("conv0", "conv1", "conv2", "conv3")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def plan():
    from repro.configs.registry import get_config
    from repro.models import cnn

    cfg = get_config("cnn-vgg11")
    geometry = {name: (x, w) for name, x, w in cnn._stage_geometry(cfg, BATCH)}
    return cnn.plan_training(cfg, BATCH, in_bytes=2), geometry


@pytest.fixture(scope="module")
def no_cache():
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it.
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *specs):
    """Compile ``fn`` for the described chip; return the optimized HLO."""
    return jax.jit(fn).lower(*specs).compile().as_text()


def _conv_specs(one_chip, geometry, stage):
    x_shape, f_shape = geometry[stage]
    B, H, W, _ = x_shape
    co = f_shape[3]
    pooled = (B, H // 2, W // 2, co)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return (s(x_shape, jnp.bfloat16), s(f_shape, jnp.bfloat16),
            s((co,), jnp.bfloat16), s(pooled, jnp.bfloat16),
            s(pooled, jnp.int8), s((B, H, W, co), jnp.bfloat16))


@pytest.mark.parametrize("stage", STAGES)
def test_conv_forward_compiles(topo, one_chip, plan, no_cache, stage):
    """The planned fused forward (conv + bias + ReLU + 2x2 pool, plus the
    int8 epilogue-VJP mask where the direct kernel runs)."""
    from repro.kernels.conv2d.ops import conv2d_with_mask

    sched, geometry = plan
    x, f, b, *_ = _conv_specs(one_chip, geometry, stage)

    def fwd(x, f, b):
        return conv2d_with_mask(x, f, bias=b, stride=1, padding=1, pool=2,
                                schedule=sched[stage], interpret=False)

    assert "tpu_custom_call" in _compile(fwd, x, f, b)


def _mask_for(sched, stage):
    """Does the forward of ``stage`` emit the mask residual?  The direct
    kernel does; an im2col forward does not, and its backward then runs
    on the full-rate cotangent (see ``conv_layer._conv_block_bwd``)."""
    return sched[stage].algorithm != "im2col"


@pytest.mark.parametrize("stage", STAGES)
def test_conv_dgrad_compiles(topo, one_chip, plan, no_cache, stage):
    from repro.kernels.conv2d.bwd import conv2d_dgrad

    sched, geometry = plan
    x, f, _, g, mask, g_full = _conv_specs(one_chip, geometry, stage)
    out_hw = x.shape[1:3]
    if _mask_for(sched, stage):
        def dgrad(g, f, mask):
            return conv2d_dgrad(g, f, stride=1, padding=1, out_hw=out_hw,
                                mask=mask, pool=2,
                                schedule=sched[f"{stage}.dgrad"],
                                out_dtype=jnp.float32, interpret=False)

        hlo = _compile(dgrad, g, f, mask)
    else:
        def dgrad(g, f):
            return conv2d_dgrad(g, f, stride=1, padding=1, out_hw=out_hw,
                                schedule=sched[f"{stage}.dgrad"],
                                out_dtype=jnp.float32, interpret=False)

        hlo = _compile(dgrad, g_full, f)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("stage", STAGES)
def test_conv_wgrad_compiles(topo, one_chip, plan, no_cache, stage):
    from repro.kernels.conv2d.bwd import conv2d_wgrad

    sched, geometry = plan
    x, _, _, g, mask, g_full = _conv_specs(one_chip, geometry, stage)
    if _mask_for(sched, stage):
        def wgrad(x, g, mask):
            return conv2d_wgrad(x, g, F=3, stride=1, padding=1, mask=mask,
                                pool=2, schedule=sched[f"{stage}.wgrad"],
                                out_dtype=jnp.float32, interpret=False)

        hlo = _compile(wgrad, x, g, mask)
    else:
        def wgrad(x, g):
            return conv2d_wgrad(x, g, F=3, stride=1, padding=1,
                                schedule=sched[f"{stage}.wgrad"],
                                out_dtype=jnp.float32, interpret=False)

        hlo = _compile(wgrad, x, g_full)
    assert "tpu_custom_call" in hlo


def _fc_specs(one_chip, geometry, stage):
    x_shape, w_shape = geometry[stage]

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    return s(x_shape), s(w_shape), s((x_shape[0], w_shape[1]))


@pytest.mark.parametrize("stage", ("fc1", "fc2"))
def test_fc_forward_compiles(topo, one_chip, plan, no_cache, stage):
    from repro.kernels.matmul.ops import fc_matmul

    sched, geometry = plan
    x, w, _ = _fc_specs(one_chip, geometry, stage)

    def fwd(x, w):
        return fc_matmul(x, w, schedule=sched[stage], interpret=False)

    assert "tpu_custom_call" in _compile(fwd, x, w)


@pytest.mark.parametrize("stage", ("fc1", "fc2"))
def test_fc_fused_dxdw_compiles(topo, one_chip, plan, no_cache, stage):
    from repro.kernels.matmul.bwd import matmul_dx_dw

    sched, geometry = plan
    s_dx = sched[f"{stage}.dx"]
    assert s_dx.algorithm == "fused_dxdw"
    x, w, g = _fc_specs(one_chip, geometry, stage)

    def bwd(g, w, x):
        return matmul_dx_dw(g, w, x, schedule=s_dx, out_dtype=jnp.float32,
                            interpret=False)

    assert "tpu_custom_call" in _compile(bwd, g, w, x)


def test_planned_train_step_compiles(topo, one_chip, no_cache, monkeypatch):
    """The whole planned train step (forward, backward, optimizer) at batch
    256, bf16: a Mosaic kernel in the forward and in the backward of every
    stage.  ``default_interpret`` sees the CPU here, so the test steers it
    to compile the kernels for the described chip."""
    import re

    import repro.plan as rp
    import repro.plan.registry as reg
    from repro.configs.base import TrainConfig
    from repro.configs.registry import get_config
    from repro.models import cnn
    from repro.models.module import init_params
    from repro.runtime import train as tr

    def compiled(interpret):
        return False if interpret is None else interpret

    monkeypatch.setattr(reg, "default_interpret", compiled)
    monkeypatch.setattr(rp, "default_interpret", compiled)
    cfg = get_config("cnn-vgg11")
    tcfg = TrainConfig(param_dtype="float32", compute_dtype="bfloat16",
                       planned_kernels=True)
    state = jax.eval_shape(lambda: tr.init_state(cfg, tcfg, init_params(
        cnn.param_defs(cfg), jax.random.PRNGKey(0), jnp.float32)))
    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), state)
    batch = {"images": jax.ShapeDtypeStruct((BATCH, 32, 32, 3), jnp.float32,
                                            sharding=one_chip),
             "labels": jax.ShapeDtypeStruct((BATCH,), jnp.int32,
                                            sharding=one_chip)}
    hlo = _compile(tr.make_train_step(cfg, tcfg), state, batch)
    sites = set()
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = re.search(r'op_name="([^"]*)"', line).group(1)
            stage = re.search(r"[/(]((?:conv|fc)\d+)[/)]", name)
            sites.add((stage and stage.group(1), "transpose(" in name))
    want = {(s, bwd) for s in (*STAGES, "fc1", "fc2") for bwd in (False, True)}
    assert want <= sites, sorted(want - sites)
    # The loss and the optimizer run under scopes of their own, which the
    # stage pattern above does not take for a stage.
    for scope in ("loss", "optimizer"):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)]', hlo), scope


@pytest.fixture(scope="module")
def lfm2_plan():
    from repro.configs.registry import get_config
    from repro.models import lfm2

    # The lfm2-8b-a1b share's step at batch 2 x 8192, bf16.
    return lfm2.plan_training(get_config("lfm2-8b-a1b-ep4"), 2, 8192,
                              loss_chunks=4, in_bytes=2)


@pytest.mark.parametrize("cell,k,n", [("experts_up", 2048, 3584),
                                      ("experts_down", 1792, 2048)])
@pytest.mark.parametrize("direction", ("fwd", "dx", "dw"))
def test_grouped_matmul_compiles(topo, one_chip, no_cache, lfm2_plan, cell,
                                 k, n, direction):
    """The held experts' grouped GEMMs at the published widths, every
    pass, under the compiler's default scoped VMEM (megablox sets none)."""
    from repro.plan import get_op

    s = lfm2_plan[cell][direction]
    m = 16384 * 4 + 8 * s.block("block_m")

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    sizes = spec((9,), jnp.int32)
    if direction == "fwd":
        fn = lambda x, w, gs: get_op("grouped_matmul")(  # noqa: E731
            x, w, gs, schedule=s, interpret=False)
        args = spec((m, k)), spec((8, k, n)), sizes
    elif direction == "dx":
        fn = lambda g, w, gs: get_op("grouped_matmul_dx")(  # noqa: E731
            g, w, gs, schedule=s, interpret=False)
        args = spec((m, n)), spec((8, k, n)), sizes
    else:
        fn = lambda x, g, gs: get_op("grouped_matmul_dw")(  # noqa: E731
            x, g, gs, schedule=s, groups=8, out_dtype=jnp.float32,
            interpret=False)
        args = spec((m, k)), spec((m, n)), sizes
    assert "tpu_custom_call" in _compile(fn, *args)


def test_lfm2_attention_forward_compiles(topo, one_chip, no_cache, lfm2_plan):
    from repro.kernels.flash_attention.ops import flash_attention

    q = jax.ShapeDtypeStruct((2, 32, 8192, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 8, 8192, 64), jnp.bfloat16,
                              sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               schedule=lfm2_plan["attn"], interpret=False)

    assert "tpu_custom_call" in _compile(fwd, q, kv, kv)


@pytest.mark.parametrize("cell", ("in_proj", "out_proj", "qkv", "wo",
                                  "ffn_up", "ffn_down", "logits"))
def test_lfm2_gemm_forward_and_backward_compile(topo, one_chip, no_cache,
                                                lfm2_plan, cell):
    """Every fc_layer GEMM of the lfm2 share's step, forward and its
    planned backward (fused dX/dW where the planner's count fits)."""
    from repro.configs.registry import get_config
    from repro.kernels.matmul.bwd import matmul_dw, matmul_dx, matmul_dx_dw
    from repro.kernels.matmul.ops import fc_matmul
    from repro.models import lfm2

    m, k, n = lfm2.gemm_shapes(get_config("lfm2-8b-a1b-ep4"), 2, 8192,
                               loss_chunks=4)[cell]

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    x, w, g = spec(m, k), spec(k, n), spec(m, n)
    fwd = lambda x, w: fc_matmul(  # noqa: E731
        x, w, schedule=lfm2_plan[cell], interpret=False)
    assert "tpu_custom_call" in _compile(fwd, x, w)
    s_dx, s_dw = lfm2_plan[f"{cell}.dx"], lfm2_plan[f"{cell}.dw"]
    if s_dx.algorithm == "fused_dxdw":
        bwd = lambda g, w, x: matmul_dx_dw(  # noqa: E731
            g, w, x, schedule=s_dx, out_dtype=jnp.float32, interpret=False)
    else:
        bwd = lambda g, w, x: (  # noqa: E731
            matmul_dx(g, w, schedule=s_dx, out_dtype=jnp.float32,
                      interpret=False),
            matmul_dw(x, g, schedule=s_dw, out_dtype=jnp.float32,
                      interpret=False))
    assert "tpu_custom_call" in _compile(bwd, g, w, x)


ROWS_BATCH = 6144  # vgg11-b6144's batch


@pytest.fixture(scope="module")
def rows_plan():
    from repro.configs.registry import get_config
    from repro.models import cnn

    return cnn.plan_training(get_config("cnn-vgg11"), ROWS_BATCH, in_bytes=2)


@pytest.mark.parametrize("direction", ("fwd", "wgrad", "dgrad"))
def test_conv0_rows_kernels_compile_and_fit(topo, one_chip, no_cache,
                                            rows_plan, direction):
    """conv0 at vgg11-b6144's shapes runs the row-GEMM family: each of its
    kernels lowers to Mosaic, and each planned working set fits VMEM."""
    from repro.core.machine import TPU_V5E
    from repro.kernels.conv2d import rows

    s = rows_plan["conv0" if direction == "fwd" else f"conv0.{direction}"]
    assert s.algorithm == "rows" and s.fits(TPU_V5E), (s, s.vmem_bytes)
    geo = rows.rows_geometry((ROWS_BATCH, 32, 32, 3), (3, 3, 3, 64),
                             padding=1, pool=2, dtype=jnp.bfloat16,
                             block_b=s.block("block_b"))
    K, Nh, V = geo.K, geo.Nh, geo.V
    kw = dict(geo=geo, interpret=False, **rows._blocking(s, geo))

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x4 = spec((V, geo.Bp * geo.Hq, K))
    y2, m2 = spec((geo.Bp * geo.Hq, Nh)), spec((geo.Bp * geo.Hq, Nh), jnp.int8)
    t = spec((3, 2, K, Nh))
    if direction == "fwd":
        def fn(x4, t, b):
            return rows._conv_rows_impl(x4, t, b, relu=True, emit_mask=True,
                                        out_dtype=jnp.bfloat16, **kw)

        args = x4, t, spec((1, Nh), jnp.float32)
    elif direction == "wgrad":
        def fn(x4, g, m):
            return rows._conv_rows_wgrad_impl(x4, g, m, **kw)

        args = x4, y2, m2
    else:
        def fn(g, m, t):
            return rows._conv_rows_dgrad_impl(g, m, t, **kw)

        args = y2, m2, t
    assert "tpu_custom_call" in _compile(fn, *args)
