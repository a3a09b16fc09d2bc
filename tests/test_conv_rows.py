"""The row-GEMM conv family (kernels/conv2d/rows.py): parity with the XLA
reference and its VJP in interpret mode, and the planner's choice of it
for inputs far narrower than a lane."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import ccr
from repro.core import schedule_sim as sim
from repro.core.conv_layer import conv_block
from repro.core.machine import MANTICORE, TPU_V5E
from repro.kernels.conv2d.bwd import (
    conv2d_dgrad, conv2d_dgrad_ref, conv2d_wgrad, conv2d_wgrad_ref,
    epilogue_scatter,
)
from repro.kernels.conv2d.ops import conv2d_with_mask
from repro.kernels.conv2d.ref import conv2d_fused_ref
from repro.launch.train import rows_stages_line
from repro.models import cnn
from repro.plan import ConvDgradPlanner, ConvPlanner, ConvWgradPlanner

TOL = 1e-4


def _plans(B, H, W, d_in, d_out, pool, block_b):
    """The rows forward, dgrad and wgrad Schedules of a 3x3 same conv."""
    fused = pool if H % pool == 0 and W % pool == 0 else 1
    shape = dict(H_O=H, W_O=W, F=3, S=1, d_in=d_in, d_out=d_out, in_bytes=4,
                 batch=B, block_b=block_b)
    fwd = ConvPlanner(TPU_V5E).plan(**shape, pool=fused, padding=1,
                                    algorithm="rows")
    dgrad = ConvDgradPlanner(TPU_V5E).plan(**shape, pool=fused, P=1,
                                           algorithm="rows")
    wgrad = ConvWgradPlanner(TPU_V5E).plan(**shape, pool=fused,
                                           algorithm="rows")
    return fused, fwd, dgrad, wgrad


@pytest.mark.parametrize(
    "B,H,W,d_in,d_out,pool,block_b",
    [
        (2, 8, 8, 1, 8, 2, None),     # one input channel
        (2, 8, 32, 3, 64, 2, None),   # conv0's widths on a shorter plane
        (5, 8, 8, 4, 8, 2, 4),        # 5 images in blocks of 4: a part block
        (2, 7, 8, 3, 8, 1, None),     # odd H, no pool (ReLU liveness mask)
        (2, 8, 32, 4, 64, 1, None),   # wide rows, no pool
        (2, 7, 8, 4, 64, 2, None),    # odd H: ragged pool, tail op
    ],
)
def test_rows_family_matches_reference(B, H, W, d_in, d_out, pool, block_b):
    """Forward output, mask, dX, dW and db of the rows stage against
    ``conv2d_fused_ref`` and its XLA VJP; the ops' rows paths
    (``conv2d_with_mask``, ``conv2d_dgrad``, ``conv2d_wgrad``) against
    the direct kernel's mask and the dgrad/wgrad oracles."""
    rng = np.random.default_rng(B * 1000 + H * 100 + d_in * 10 + pool)
    x = jnp.asarray(rng.standard_normal((B, H, W, d_in)), jnp.float32)
    f = jnp.asarray(rng.standard_normal((3, 3, d_in, d_out)) / 3, jnp.float32)
    b = jnp.asarray(rng.standard_normal((d_out,)) * 0.1, jnp.float32)
    fused, s, s_dg, s_wg = _plans(B, H, W, d_in, d_out, pool, block_b)
    assert {p.algorithm for p in (s, s_dg, s_wg)} == {"rows"}
    if block_b:
        assert s.block("block_b") == block_b and s.grid == (-(-B // block_b),)

    def ref(x, f, b):
        return conv2d_fused_ref(x, f, b, stride=1, padding=1, relu=True,
                                pool=pool)

    want = ref(x, f, b)
    got = conv_block(x, f, b, 1, 1, pool, schedule=s,
                     bwd_schedules={"dgrad": s_dg, "wgrad": s_wg})
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=TOL, rtol=TOL)

    weight = jnp.asarray(rng.standard_normal(want.shape), jnp.float32)
    grads = jax.grad(lambda *a: (conv_block(*a, 1, 1, pool, schedule=s)
                                 * weight).sum(), argnums=(0, 1, 2))(x, f, b)
    oracle = jax.grad(lambda *a: (ref(*a) * weight).sum(),
                      argnums=(0, 1, 2))(x, f, b)
    for name, g, r in zip(("dX", "dW", "db"), grads, oracle):
        err = float(jnp.abs(g - r).max() / jnp.abs(r).max())
        assert err < TOL, (name, err)

    out, mask = conv2d_with_mask(x, f, bias=b, padding=1, pool=pool,
                                 schedule=s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=TOL, rtol=TOL)
    if fused != pool:  # ragged pool: no mask, the recompute backward
        assert mask is None
        return
    direct = ConvPlanner(TPU_V5E).plan(
        H_O=H, W_O=W, F=3, S=1, d_in=d_in, d_out=d_out, in_bytes=4,
        pool=pool, batch=B, padding=1, algorithm="direct")
    _, mask_direct = conv2d_with_mask(x, f, bias=b, padding=1, pool=pool,
                                      schedule=direct)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(mask_direct))
    dy = epilogue_scatter(weight, mask, pool)
    dx = conv2d_dgrad(weight, f, padding=1, out_hw=(H, W), mask=mask,
                      pool=pool, schedule=s_dg, out_dtype=jnp.float32)
    dw = conv2d_wgrad(x, weight, F=3, padding=1, mask=mask, pool=pool,
                      schedule=s_wg, out_dtype=jnp.float32)
    for g, r in ((dx, conv2d_dgrad_ref(dy, f, padding=1, out_hw=(H, W))),
                 (dw, conv2d_wgrad_ref(x, dy, F=3, padding=1))):
        assert float(jnp.abs(g - r).max() / jnp.abs(r).max()) < TOL


def test_rows_family_is_the_argmin_for_narrow_inputs_only():
    """The images' 3 channels (conv0 of cnn-vgg11, at both cells'
    batches) take the rows family; 64 input channels, a stride of 2, or
    a machine without lanes keep the other families.  Each rows pass
    models its words and MXU passes by ``ccr.conv_rows_traffic``, the
    band's zeros included, and its grid walk by the plain pipeline."""
    conv0 = dict(H_O=32, W_O=32, F=3, S=1, d_in=3, d_out=64, in_bytes=2,
                 pool=2, padding=1)
    p = ConvPlanner(TPU_V5E)
    for batch in (256, 6144):
        s = p.plan(**conv0, batch=batch)
        assert s.algorithm == "rows" and s.fits(TPU_V5E)
        t = ccr.conv_rows_traffic(
            H_O=32, W_O=32, F=3, d_in=3, d_out=64, pool=2, batch=batch,
            lane=128, row_tile=16)
        assert (s.macs, s.loads, s.stores) == (
            t.macs, t.main_loads, t.main_stores)
        # K = 128 lanes hold the 34 * 3 words of a padded row where a tap
        # needs 9: the band's zeros are MXU work.
        assert s.macs == batch * 2 * 2 * 3 * 16 * 128 * 1024
        assert s.critical_path_steps == sim.simulate_grid_steps(s.grid)
    assert p.plan(**{**conv0, "d_in": 64}, batch=256).algorithm != "rows"
    for batch in (256, 6144):  # the launcher's plan log of both cells
        plan = cnn.plan_training(get_config("cnn-vgg11"), batch, in_bytes=2)
        assert rows_stages_line(plan) == (
            "conv stages on the rows family: 1 of 4 (conv0)")
    assert p.plan(**{**conv0, "S": 2, "H_O": 16, "W_O": 16},
                  batch=256).algorithm != "rows"
    assert ConvPlanner(MANTICORE).plan(**conv0, batch=256).algorithm != "rows"
    with pytest.raises(ValueError, match="stride 1"):
        ConvPlanner(MANTICORE).plan(**conv0, batch=256, algorithm="rows")
    with pytest.raises(ValueError, match="pool mask"):
        ConvWgradPlanner(TPU_V5E).plan(H_O=32, W_O=32, F=3, d_in=3,
                                       d_out=64, algorithm="rows")
