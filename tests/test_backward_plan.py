"""Gradient-and-traffic harness for the planned backward kernels (ISSUE 3).

What it pins:

* parity — planned dgrad/wgrad/dX/dW vs ``jax.grad`` of the XLA reference
  across stride/padding/ragged-strip/odd-channel cases, within 1e-4 (f32);
* execution — ``jax.grad`` through :func:`conv_block` / :func:`fc_layer`
  actually runs the planned Pallas backward kernels, not the XLA fallback,
  and a user-passed ``bwd_schedules=`` reaches them (the old
  ``with_reference_vjp`` gap);
* capacity — pinned Manticore-model backward Schedules: the transposed
  ops respect the same Delta_O <= 24/12-style fit bounds as the forward
  (dgrad on the running example *is* the Sec. 2.2.2 rule; dX reproduces
  the 768/384 FC stack);
* traffic — backward ``modeled_words`` equals the closed forms in
  core/ccr.py equals the executed word counts in core/schedule_sim.py for
  every pinned case.

``scripts/tier1.sh --grad-smoke`` runs only :class:`TestGradSmoke`; the
default tier-1 invocation runs it first so backward regressions fail fast.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ccr
from repro.core import schedule_sim as sim
from repro.core.conv_layer import conv_block, conv_layer
from repro.core.conv_layer import plan_bwd as conv_plan_bwd
from repro.core.fc_layer import fc_layer
from repro.core.fc_layer import plan_bwd as fc_plan_bwd
from repro.core.machine import MANTICORE, TPU_V5E, word_bytes
from repro.kernels.conv2d.bwd import (
    conv2d_dgrad,
    conv2d_dgrad_ref,
    conv2d_wgrad,
    conv2d_wgrad_ref,
)
from repro.kernels.conv2d.ref import conv2d_fused_ref, conv2d_ref
from repro.kernels.matmul.bwd import matmul_dw, matmul_dw_ref, matmul_dx, matmul_dx_ref
from repro.kernels.matmul.ref import fc_matmul_ref
from repro.plan import (
    ConvDgradPlanner,
    ConvWgradPlanner,
    MatmulDwPlanner,
    MatmulDxPlanner,
    with_reference_vjp,
)

TOL = 1e-4
S32 = ccr.ConvShape(W_I=32, D_I=128, D_O=128, F=3, S=1, P=1)

# (B, H, W, d_in, d_out, F, S, P): stride, padding, ragged planes (stride
# does not divide the extent), odd channel counts, 1x1 and 5x5 filters.
CONV_CASES = [
    (1, 8, 8, 4, 4, 3, 1, 1),
    (2, 9, 7, 3, 5, 3, 1, 1),     # ragged rectangular plane, odd channels
    (1, 10, 10, 4, 6, 3, 2, 1),   # stride 2
    (2, 7, 7, 5, 3, 5, 1, 2),     # F=5, P=2
    (1, 8, 8, 3, 4, 3, 2, 0),     # stride 2, no padding, ragged cover
    (1, 11, 10, 7, 5, 3, 2, 1),   # stride 2 over an odd extent
    (1, 5, 5, 2, 3, 1, 1, 0),     # 1x1 filter
]


def _rand(rng, shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _conv_operands(case, seed=0):
    B, H, W, di, do, F, S, P = case
    rng = np.random.default_rng(seed)
    x = _rand(rng, (B, H, W, di))
    f = _rand(rng, (F, F, di, do))
    H_O, W_O = (H + 2 * P - F) // S + 1, (W + 2 * P - F) // S + 1
    dy = _rand(rng, (B, H_O, W_O, do))
    return x, f, dy


def _ref_conv_grads(x, f, dy, S, P):
    _, vjp = jax.vjp(
        lambda xx, ff: conv2d_ref(xx, ff, stride=S, padding=P), x, f)
    return vjp(dy)


# ---------------------------------------------------------------------------
# Fast subset: scripts/tier1.sh --grad-smoke (and first in default tier-1)
# ---------------------------------------------------------------------------


class TestGradSmoke:
    def test_conv_block_grad_parity(self):
        rng = np.random.default_rng(42)
        x, f, b = _rand(rng, (2, 8, 8, 3)), _rand(rng, (3, 3, 3, 4)), _rand(rng, (4,))
        got = jax.grad(lambda x, f, b: conv_block(x, f, b, 1, 1, 2, "strip").sum(),
                       argnums=(0, 1, 2))(x, f, b)
        want = jax.grad(
            lambda x, f, b: conv2d_fused_ref(x, f, b, stride=1, padding=1,
                                             relu=True, pool=2).sum(),
            argnums=(0, 1, 2))(x, f, b)
        for g, r in zip(got, want):
            assert float(jnp.abs(g - r).max()) < TOL

    def test_fc_layer_grad_parity(self):
        rng = np.random.default_rng(43)
        x, w = _rand(rng, (5, 24)), _rand(rng, (24, 13))
        got = jax.grad(lambda x, w: (fc_layer(x, w) ** 2).sum(),
                       argnums=(0, 1))(x, w)
        want = jax.grad(lambda x, w: (fc_matmul_ref(x, w) ** 2).sum(),
                        argnums=(0, 1))(x, w)
        for g, r in zip(got, want):
            assert float(jnp.abs(g - r).max() / jnp.abs(r).max()) < TOL

    def test_grad_runs_planned_kernels(self, monkeypatch):
        """jax.grad through conv_block / fc_layer must execute the planned
        Pallas backward ops, not the XLA fallback (acceptance criterion).
        Unique shapes defeat jit caching so the spies see the trace."""
        import repro.core.conv_layer as cl
        import repro.core.fc_layer as fl

        calls = []

        def spy(name, orig):
            def wrapped(*a, **k):
                calls.append(name)
                return orig(*a, **k)
            return wrapped

        monkeypatch.setattr(cl, "conv2d_dgrad", spy("dgrad", cl.conv2d_dgrad))
        monkeypatch.setattr(cl, "conv2d_wgrad", spy("wgrad", cl.conv2d_wgrad))
        monkeypatch.setattr(fl, "matmul_dx", spy("dx", fl.matmul_dx))
        monkeypatch.setattr(fl, "matmul_dw", spy("dw", fl.matmul_dw))

        rng = np.random.default_rng(44)
        x, f, b = _rand(rng, (1, 13, 13, 2)), _rand(rng, (3, 3, 2, 3)), _rand(rng, (3,))
        jax.grad(lambda x, f, b: conv_block(x, f, b, 1, 1, 1, "strip").sum(),
                 argnums=(0, 1, 2))(x, f, b)
        xm, wm = _rand(rng, (3, 29)), _rand(rng, (29, 17))
        jax.grad(lambda x, w: fc_layer(x, w).sum(), argnums=(0, 1))(xm, wm)
        assert {"dgrad", "wgrad", "dx", "dw"} <= set(calls), calls

    def test_manticore_dgrad_is_the_paper_capacity_rule(self):
        """dgrad of the running example is the same Sec. 2.2.2 geometry, so
        its stack bound is the paper's Delta_O <= 24 (sp) / 12 (dp)."""
        for prec, want in (("sp", 24), ("dp", 12)):
            sched = ConvDgradPlanner(MANTICORE).plan(
                H_O=32, W_O=32, F=3, S=1, P=1, d_in=128, d_out=128,
                in_bytes=word_bytes(prec), block_h=32)
            assert sched.block("block_do") == want
            assert sched.fits(MANTICORE)


# ---------------------------------------------------------------------------
# Op-level parity: planned kernels vs the XLA oracles
# ---------------------------------------------------------------------------


class TestBackwardOpParity:
    @pytest.mark.parametrize("case", CONV_CASES)
    def test_dgrad_matches_ref(self, case):
        B, H, W, di, do, F, S, P = case
        x, f, dy = _conv_operands(case)
        dx_ref, _ = _ref_conv_grads(x, f, dy, S, P)
        dx = conv2d_dgrad(dy, f, stride=S, padding=P, out_hw=(H, W))
        assert dx.shape == x.shape
        assert float(jnp.abs(dx - dx_ref).max()) < TOL
        # ... and the registered reference oracle agrees with jax.vjp.
        np.testing.assert_allclose(
            np.asarray(conv2d_dgrad_ref(dy, f, stride=S, padding=P, out_hw=(H, W))),
            np.asarray(dx_ref), rtol=TOL, atol=TOL)

    @pytest.mark.parametrize("case", CONV_CASES)
    def test_wgrad_matches_ref(self, case):
        B, H, W, di, do, F, S, P = case
        x, f, dy = _conv_operands(case)
        _, df_ref = _ref_conv_grads(x, f, dy, S, P)
        df = conv2d_wgrad(x, dy, F=F, stride=S, padding=P)
        assert df.shape == f.shape
        assert float(jnp.abs(df - df_ref).max()) < TOL
        np.testing.assert_allclose(
            np.asarray(conv2d_wgrad_ref(x, dy, F=F, stride=S, padding=P)),
            np.asarray(df_ref), rtol=TOL, atol=TOL)

    def test_ragged_strips(self):
        """Explicit block_h that does not divide the plane (ragged strips)
        keeps both backward kernels exact."""
        case = (2, 9, 7, 3, 5, 3, 1, 1)
        B, H, W, di, do, F, S, P = case
        x, f, dy = _conv_operands(case)
        dx_ref, df_ref = _ref_conv_grads(x, f, dy, S, P)
        for hb in (2, 4, 5):
            dx = conv2d_dgrad(dy, f, stride=S, padding=P, out_hw=(H, W), block_h=hb)
            df = conv2d_wgrad(x, dy, F=F, stride=S, padding=P, block_h=hb)
            assert float(jnp.abs(dx - dx_ref).max()) < TOL, hb
            assert float(jnp.abs(df - df_ref).max()) < TOL, hb

    def test_unbatched_operands(self):
        x, f, dy = _conv_operands((1, 8, 8, 4, 4, 3, 1, 1))
        dx_ref, df_ref = _ref_conv_grads(x, f, dy, 1, 1)
        dx = conv2d_dgrad(dy[0], f, stride=1, padding=1, out_hw=(8, 8))
        df = conv2d_wgrad(x[0], dy[0], F=3, stride=1, padding=1)
        assert dx.shape == x.shape[1:]
        assert float(jnp.abs(dx - dx_ref[0]).max()) < TOL
        assert float(jnp.abs(df - df_ref).max()) < TOL

    @pytest.mark.parametrize("m,k,n", [(8, 16, 8), (37, 70, 90), (1, 17, 300),
                                       (130, 257, 129)])
    def test_matmul_dx_dw_match_ref(self, m, k, n):
        rng = np.random.default_rng(m * 1000 + n)
        x, w, g = _rand(rng, (m, k)), _rand(rng, (k, n)), _rand(rng, (m, n))
        _, vjp = jax.vjp(fc_matmul_ref, x, w)
        dx_ref, dw_ref = vjp(g)
        scale = max(float(jnp.abs(dx_ref).max()), float(jnp.abs(dw_ref).max()))
        assert float(jnp.abs(matmul_dx(g, w) - dx_ref).max()) / scale < TOL
        assert float(jnp.abs(matmul_dw(x, g) - dw_ref).max()) / scale < TOL
        np.testing.assert_allclose(np.asarray(matmul_dx_ref(g, w)),
                                   np.asarray(dx_ref), rtol=TOL, atol=TOL)
        np.testing.assert_allclose(np.asarray(matmul_dw_ref(x, g)),
                                   np.asarray(dw_ref), rtol=TOL, atol=TOL)

    def test_matmul_bwd_leading_dims(self):
        rng = np.random.default_rng(77)
        x, w = _rand(rng, (2, 3, 10)), _rand(rng, (10, 7))
        g = _rand(rng, (2, 3, 7))
        _, vjp = jax.vjp(fc_matmul_ref, x, w)
        dx_ref, dw_ref = vjp(g)
        assert float(jnp.abs(matmul_dx(g, w) - dx_ref).max()) < TOL
        assert float(jnp.abs(matmul_dw(x, g) - dw_ref).max()) < TOL


# ---------------------------------------------------------------------------
# Layer-level parity: jax.grad through the rewired custom_vjps
# ---------------------------------------------------------------------------


class TestLayerGradParity:
    @pytest.mark.parametrize("case", CONV_CASES[:5])
    def test_conv_layer_grads(self, case):
        B, H, W, di, do, F, S, P = case
        x, f, _ = _conv_operands(case, seed=1)
        got = jax.grad(lambda x, f: (conv_layer(x, f, S, P, "strip") ** 2).sum(),
                       argnums=(0, 1))(x, f)
        want = jax.grad(
            lambda x, f: (conv2d_ref(x, f, stride=S, padding=P) ** 2).sum(),
            argnums=(0, 1))(x, f)
        for g, r in zip(got, want):
            assert float(jnp.abs(g - r).max() / max(1.0, jnp.abs(r).max())) < TOL

    @pytest.mark.parametrize("pool", [1, 2])
    def test_conv_block_grads_pool(self, pool):
        """Fused bias+ReLU+pool epilogue backprop, even (8) and ragged-pool
        (pool over an odd H_O handled by the XLA tail) planes."""
        rng = np.random.default_rng(11)
        for H in (8, 9):
            x, f, b = (_rand(rng, (2, H, H, 3)), _rand(rng, (3, 3, 3, 4)),
                       _rand(rng, (4,)))
            got = jax.grad(
                lambda x, f, b: (conv_block(x, f, b, 1, 1, pool, "strip") ** 2).sum(),
                argnums=(0, 1, 2))(x, f, b)
            want = jax.grad(
                lambda x, f, b: (conv2d_fused_ref(x, f, b, stride=1, padding=1,
                                                  relu=True, pool=pool) ** 2).sum(),
                argnums=(0, 1, 2))(x, f, b)
            for g, r in zip(got, want):
                scale = max(1.0, float(jnp.abs(r).max()))
                assert float(jnp.abs(g - r).max()) / scale < TOL, (H, pool)

    def test_fc_layer_grads_leading_dims(self):
        rng = np.random.default_rng(12)
        x, w = _rand(rng, (2, 3, 20)), _rand(rng, (20, 11))
        got = jax.grad(lambda x, w: (fc_layer(x, w) ** 2).sum(), argnums=(0, 1))(x, w)
        want = jax.grad(lambda x, w: (fc_matmul_ref(x, w) ** 2).sum(),
                        argnums=(0, 1))(x, w)
        for g, r in zip(got, want):
            assert float(jnp.abs(g - r).max() / jnp.abs(r).max()) < TOL

    def test_bwd_schedules_reach_the_kernels(self, monkeypatch):
        """A user-passed bwd_schedules= must be the exact Schedule the
        backward ops execute (the with_reference_vjp gap this PR closes)."""
        import repro.core.conv_layer as cl

        seen = {}
        orig_dg, orig_wg = cl.conv2d_dgrad, cl.conv2d_wgrad

        def spy_dg(*a, **k):
            seen["dgrad"] = k.get("schedule")
            return orig_dg(*a, **k)

        def spy_wg(*a, **k):
            seen["wgrad"] = k.get("schedule")
            return orig_wg(*a, **k)

        monkeypatch.setattr(cl, "conv2d_dgrad", spy_dg)
        monkeypatch.setattr(cl, "conv2d_wgrad", spy_wg)

        rng = np.random.default_rng(13)
        x, f = _rand(rng, (1, 14, 14, 3)), _rand(rng, (3, 3, 3, 4))
        bwd = conv_plan_bwd(x.shape, f.shape, stride=1, padding=1)
        bwd = {"dgrad": bwd["dgrad"].evolve(block_h=3),
               "wgrad": bwd["wgrad"].evolve(block_h=5)}
        got = jax.grad(
            lambda x, f: conv_layer(x, f, 1, 1, "strip", None, bwd).sum(),
            argnums=(0, 1))(x, f)
        assert seen["dgrad"] is bwd["dgrad"] and seen["wgrad"] is bwd["wgrad"]
        want = jax.grad(
            lambda x, f: conv2d_ref(x, f, stride=1, padding=1).sum(),
            argnums=(0, 1))(x, f)
        for g, r in zip(got, want):  # pinned odd blocking stays exact
            assert float(jnp.abs(g - r).max()) < TOL

    def test_fc_bwd_schedules_roundtrip(self):
        rng = np.random.default_rng(14)
        x, w = _rand(rng, (6, 20)), _rand(rng, (20, 11))
        bwd = fc_plan_bwd(x.shape, w.shape)
        assert set(bwd) == {"dx", "dw"} and all(
            s.fits(TPU_V5E) for s in bwd.values())
        got = jax.grad(lambda x, w: (fc_layer(x, w, None, bwd) ** 2).sum(),
                       argnums=(0, 1))(x, w)
        want = jax.grad(lambda x, w: (fc_matmul_ref(x, w) ** 2).sum(),
                        argnums=(0, 1))(x, w)
        for g, r in zip(got, want):
            assert float(jnp.abs(g - r).max() / jnp.abs(r).max()) < TOL

    def test_unfit_bwd_schedule_falls_back_to_reference(self):
        """A pinned backward Schedule that does not fit the machine it was
        planned for must trigger the XLA reference VJP (checked against its
        *own* machine, not a hard-coded one) — gradients stay correct."""
        import dataclasses

        rng = np.random.default_rng(15)
        x, f = _rand(rng, (1, 15, 15, 3)), _rand(rng, (3, 3, 3, 4))
        bwd = conv_plan_bwd(x.shape, f.shape, stride=1, padding=1,
                            machine=MANTICORE)
        assert bwd["dgrad"].machine == "manticore"
        # Blow the modeled working set past the 128 KiB cluster budget.
        bwd = {k: dataclasses.replace(s, vmem_bytes=1 << 30)
               for k, s in bwd.items()}
        got = jax.grad(
            lambda x, f: conv_layer(x, f, 1, 1, "strip", None, bwd).sum(),
            argnums=(0, 1))(x, f)
        want = jax.grad(
            lambda x, f: conv2d_ref(x, f, stride=1, padding=1).sum(),
            argnums=(0, 1))(x, f)
        for g, r in zip(got, want):
            assert float(jnp.abs(g - r).max()) < TOL
        # conv_block: the unfit recompute schedule must be dropped (the
        # planner re-plans) and the epilogue backward stays correct too.
        bb = _rand(rng, (4,))
        got = jax.grad(
            lambda x, f, bb: conv_block(x, f, bb, 1, 1, 2, "strip", None,
                                        bwd).sum(),
            argnums=(0, 1, 2))(x, f, bb)
        want = jax.grad(
            lambda x, f, bb: conv2d_fused_ref(x, f, bb, stride=1, padding=1,
                                              relu=True, pool=2).sum(),
            argnums=(0, 1, 2))(x, f, bb)
        for g, r in zip(got, want):
            assert float(jnp.abs(g - r).max()) < TOL

    def test_unfit_schedule_warns_once_per_cell(self):
        """The fit gates' silent-fallback fix: the first unfit (role,
        schedule) cell warns, steady-state replays stay quiet (the
        autotune _warn_once discipline applied to the layers)."""
        import dataclasses
        import warnings as pywarn

        from repro.core.conv_layer import warn_unfit_schedule

        bwd = conv_plan_bwd((1, 8, 8, 3), (3, 3, 3, 4), stride=1, padding=1)
        big = dataclasses.replace(bwd["wgrad"], vmem_bytes=1 << 30)
        with pywarn.catch_warnings(record=True) as rec:
            pywarn.simplefilter("always")
            warn_unfit_schedule("wgrad", big, TPU_V5E)
            warn_unfit_schedule("wgrad", big, TPU_V5E)  # replay: quiet
        assert len(rec) == 1
        assert "overflows VMEM" in str(rec[0].message)

    def test_with_reference_vjp_threads_bwd_schedules(self):
        """Unit check of the registry fix: bwd_fn receives the trailing
        nondiff bwd_schedules argument verbatim."""
        seen = []

        def kern(x, sched, bwd_schedules):
            return x * 2.0

        def bwd(x, g, sched, bwd_schedules):
            seen.append(bwd_schedules)
            return (2.0 * g,)

        op = with_reference_vjp(kern, kern, nondiff_argnums=(1, 2), bwd_fn=bwd)
        frozen = (("dgrad", "sentinel"),)
        g = jax.grad(lambda x: op(x, "sched", frozen).sum())(jnp.ones(3))
        assert seen == [frozen]
        np.testing.assert_allclose(np.asarray(g), 2.0)


# ---------------------------------------------------------------------------
# Pinned Manticore/TPU backward Schedules + modeled == simulated words
# ---------------------------------------------------------------------------


class TestPinnedBackwardSchedules:
    @pytest.mark.parametrize("prec,want", [("sp", 24), ("dp", 12)])
    def test_dgrad_words_match_ccr_and_sim(self, prec, want):
        """Full-plane dgrad of the running example: the paper stack bound,
        and Schedule words == ccr closed form == executed walk."""
        sched = ConvDgradPlanner(MANTICORE).plan(
            H_O=32, W_O=32, F=3, S=1, P=1, d_in=128, d_out=128,
            in_bytes=word_bytes(prec), block_h=32)
        assert sched.block("block_do") == want
        assert sched.block("block_do") == ccr.alg2_max_stack(S32, MANTICORE, prec)
        t_ccr = ccr.conv_dgrad_traffic(S32, want, 32)
        t_sim = sim.simulate_conv_dgrad(S32, want, 32)
        assert sched.loads == t_ccr.main_loads == t_sim.main_loads
        assert sched.stores == t_ccr.main_stores == t_sim.main_stores

    @pytest.mark.parametrize("block_h", [32, 16, 8, 5])
    def test_dgrad_strip_words(self, block_h):
        sched = ConvDgradPlanner(MANTICORE).plan(
            H_O=32, W_O=32, F=3, S=1, P=1, d_in=128, d_out=128,
            in_bytes=4, block_h=block_h, batch=3)
        stack = sched.block("block_do")
        t_ccr = ccr.conv_dgrad_traffic(S32, stack, block_h, batch=3)
        t_sim = sim.simulate_conv_dgrad(S32, stack, block_h, batch=3)
        assert (sched.loads, sched.stores) == (t_ccr.main_loads, t_ccr.main_stores)
        assert (sched.loads, sched.stores) == (t_sim.main_loads, t_sim.main_stores)

    @pytest.mark.parametrize("block_h", [32, 16, 8, 5])
    def test_wgrad_words_match_ccr_and_sim(self, block_h):
        sched = ConvWgradPlanner(MANTICORE).plan(
            H_O=32, W_O=32, F=3, S=1, d_in=128, d_out=128, in_bytes=4,
            padding=1, H_I=32, W_I=32, block_h=block_h, batch=2)
        stack, bdi = sched.block("block_do"), sched.block("block_di")
        t_ccr = ccr.conv_wgrad_traffic(S32, stack, block_h, di_block=bdi, batch=2)
        t_sim = sim.simulate_conv_wgrad(S32, stack, block_h, di_block=bdi, batch=2)
        assert sched.fits(MANTICORE)
        assert (sched.loads, sched.stores, sched.macs) == (
            t_ccr.main_loads, t_ccr.main_stores, t_ccr.macs)
        assert (t_ccr.main_loads, t_ccr.main_stores, t_ccr.macs) == (
            t_sim.main_loads, t_sim.main_stores, t_sim.macs)

    @pytest.mark.parametrize("prec,want", [("sp", 768), ("dp", 384)])
    def test_fc_dx_reproduces_alg5_stack(self, prec, want):
        """dX's resident output stack on MANTICORE is the Sec. 3.1.2 bound:
        768 (sp) / 384 (dp) at batch 32 — the transposed Alg 5 rule."""
        fc = ccr.FCShape(W_I=7, D_I=512, D_O=4096, B=32)
        sched = MatmulDxPlanner(MANTICORE).plan(
            m=32, n=4096, k=7 * 7 * 512, in_bytes=word_bytes(prec))
        assert sched.block("block_k") == want
        assert sched.block("block_k") == ccr.alg45_max_stack(fc, MANTICORE, prec)
        assert sched.fits(MANTICORE)
        t = sim.simulate_matmul_blocks(
            32, 7 * 7 * 512, 4096, sched.block("block_m"),
            sched.block("block_k"), sched.block("block_n"))
        assert (sched.loads, sched.stores, sched.macs) == (
            t.main_loads, t.main_stores, t.macs)

    @pytest.mark.parametrize("m,n,k,ib", [(32, 4096, 25088, 4),
                                          (32, 4096, 25088, 8),
                                          (64, 1024, 512, 4),
                                          (1, 300, 17, 4)])
    def test_fc_dw_words_match_sim(self, m, n, k, ib):
        sched = MatmulDwPlanner(MANTICORE if ib == 8 else TPU_V5E).plan(
            m=m, n=n, k=k, in_bytes=ib)
        t = sim.simulate_matmul_blocks(
            k, n, m, sched.block("block_k"), sched.block("block_n"),
            sched.block("block_m"))
        assert (sched.loads, sched.stores, sched.macs) == (
            t.main_loads, t.main_stores, t.macs)

    def test_tpu_backward_schedules_fit(self):
        """Every backward Schedule of the CNN's training step fits the TPU
        machine model (so jax.grad runs the planned kernels, never the
        fallback)."""
        from repro.configs.base import ModelConfig
        from repro.models import cnn

        cfg = ModelConfig(name="t", family="cnn", n_layers=2, d_model=4,
                          d_ff=16, vocab=10)
        scheds = cnn.plan_training(cfg, batch=2)
        bwd_keys = [k for k in scheds if "." in k]
        # conv: dgrad/wgrad only — the even 32/16 planes plan the
        # fused-epilogue backward, so no recompute entry; fc: dx/dw.
        assert len(bwd_keys) == 2 * 2 + 2 * 2
        assert not any(k.endswith(".recompute") for k in bwd_keys)
        assert all(scheds[k].fits(TPU_V5E) for k in bwd_keys)
        assert all(scheds[k].modeled_words > 0 for k in bwd_keys)


# ---------------------------------------------------------------------------
# Fused epilogue VJP: the int8 mask residual vs the jax.vjp oracle
# ---------------------------------------------------------------------------


class TestFusedEpilogueVJP:
    # (B, H, W, d_in, d_out, F, S, P, pool, block_h): stride, padding,
    # pool 1 (ReLU-bit mask) and 2 (argmax mask), odd channel counts, a
    # strip height that does not divide the plane.
    EPI_CASES = [
        (1, 8, 8, 3, 4, 3, 1, 1, 2, None),
        (2, 9, 9, 3, 5, 3, 1, 1, 1, None),    # pool=1, odd channels
        (1, 11, 11, 4, 6, 3, 2, 1, 2, None),  # stride 2, even pooled plane
        (2, 8, 8, 5, 3, 5, 1, 2, 2, None),    # F=5, P=2
        (1, 9, 7, 7, 5, 3, 2, 0, 1, None),    # pool=1, no padding, ragged
        (1, 12, 12, 3, 4, 3, 1, 1, 2, 8),     # ragged strips (12 = 8 + 4)
    ]

    @staticmethod
    def _mask_and_oracle(case, seed=31):
        from repro.kernels.conv2d.ops import conv2d_with_mask
        from repro.kernels.conv2d.ref import maxpool_ref
        from repro.plan import get_op

        B, H, W, di, do, F, S, P, pool, block_h = case
        rng = np.random.default_rng(seed)
        x, f, b = (_rand(rng, (B, H, W, di)), _rand(rng, (F, F, di, do)),
                   _rand(rng, (do,)))
        schedule = None
        if block_h is not None:
            schedule = get_op("conv2d").plan(
                x, f, b, stride=S, padding=P, relu=True, pool=pool,
                block_h=block_h)
        out, mask = conv2d_with_mask(x, f, bias=b, stride=S, padding=P,
                                     pool=pool, schedule=schedule)
        g = _rand(rng, out.shape)
        y0 = conv2d_ref(x, f, stride=S, padding=P)

        def epi(y):
            y = jnp.maximum(y + b, 0.0)
            return maxpool_ref(y, pool) if pool > 1 else y

        _, vjp = jax.vjp(epi, y0)
        return out, mask, g, epi(y0), vjp(g)[0]

    @pytest.mark.parametrize("case", EPI_CASES)
    def test_scatter_matches_vjp_oracle(self, case):
        """epilogue_scatter(g, mask, pool) == jax.vjp of the epilogue at
        the true pre-pool activation — exact, since both route each pooled
        gradient element to the same (untied, random-data) argmax."""
        from repro.kernels.conv2d.bwd import epilogue_scatter

        pool = case[8]
        out, mask, g, want_out, want_dy = self._mask_and_oracle(case)
        assert mask is not None, "fused forward must emit the mask here"
        assert mask.dtype == jnp.int8
        np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                                   rtol=TOL, atol=TOL)
        dy = epilogue_scatter(g, mask, pool)
        assert dy.shape == want_dy.shape
        np.testing.assert_allclose(np.asarray(dy), np.asarray(want_dy),
                                   rtol=TOL, atol=TOL)

    def test_ragged_pool_yields_no_mask(self):
        """A pool that does not tile the output plane keeps the XLA pool
        tail — conv2d_with_mask must return mask=None (the backward then
        recomputes as before)."""
        from repro.kernels.conv2d.ops import conv2d_with_mask

        rng = np.random.default_rng(32)
        x, f, b = (_rand(rng, (1, 9, 9, 3)), _rand(rng, (3, 3, 3, 4)),
                   _rand(rng, (4,)))
        out, mask = conv2d_with_mask(x, f, bias=b, stride=1, padding=1, pool=2)
        assert mask is None
        want = conv2d_fused_ref(x, f, b, stride=1, padding=1, relu=True, pool=2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=TOL, atol=TOL)

    def test_mask_path_skips_recompute_conv(self, monkeypatch):
        """With the mask residual saved, the conv_block backward must not
        launch the recompute conv (recompute_words = 0); the ragged-pool
        geometry still does."""
        import repro.core.conv_layer as cl

        calls = []
        orig_conv, orig_sc = cl.conv2d, cl.epilogue_scatter
        monkeypatch.setattr(cl, "conv2d", lambda *a, **k: (
            calls.append("conv2d"), orig_conv(*a, **k))[1])
        monkeypatch.setattr(cl, "epilogue_scatter", lambda *a, **k: (
            calls.append("scatter"), orig_sc(*a, **k))[1])

        rng = np.random.default_rng(33)
        f, b = _rand(rng, (3, 3, 3, 4)), _rand(rng, (4,))

        def run(H):
            x = _rand(rng, (1, H, H, 3))
            out, vjp = jax.vjp(
                lambda x, f, b: conv_block(x, f, b, 1, 1, 2, "strip"), x, f, b)
            calls.clear()
            vjp(jnp.ones_like(out))
            return list(calls)

        even = run(8)   # mask residual: scatter, no recompute conv
        assert "scatter" in even and "conv2d" not in even, even
        ragged = run(9)  # no mask: the old recompute path
        assert "conv2d" in ragged and "scatter" not in ragged, ragged

    def test_fc_bwd_schedules_dispatch_fused_dxdw(self, monkeypatch):
        """fc plan_bwd pins the fused dX/dW kernel; the layer backward must
        run it (one dY read for both gradients) instead of the split pair,
        and stay exact."""
        import repro.core.fc_layer as fl

        calls = []
        for name in ("matmul_dx", "matmul_dw", "matmul_dx_dw"):
            orig = getattr(fl, name)
            monkeypatch.setattr(fl, name, (lambda o, n: lambda *a, **k: (
                calls.append(n), o(*a, **k))[1])(orig, name))

        rng = np.random.default_rng(34)
        x, w = _rand(rng, (6, 24)), _rand(rng, (24, 18))
        bwd = fc_plan_bwd(x.shape, w.shape)
        assert getattr(bwd["dx"], "algorithm", None) == "fused_dxdw"
        got = jax.grad(lambda x, w: (fc_layer(x, w, None, bwd) ** 2).sum(),
                       argnums=(0, 1))(x, w)
        assert "matmul_dx_dw" in calls, calls
        assert "matmul_dx" not in calls and "matmul_dw" not in calls, calls
        want = jax.grad(lambda x, w: (fc_matmul_ref(x, w) ** 2).sum(),
                        argnums=(0, 1))(x, w)
        for g, r in zip(got, want):
            assert float(jnp.abs(g - r).max() / jnp.abs(r).max()) < TOL


# ---------------------------------------------------------------------------
# Overlap-aware cost model: critical_path_steps == the executed walker
# ---------------------------------------------------------------------------


class TestCriticalPathSteps:
    """House rule for the new overlap objective: every emitted backward
    Schedule's ``critical_path_steps`` closed form must equal an executed
    ``schedule_sim`` walk of the same pipeline."""

    def _pin_conv(self, sched, *, H_I, H_O, d_in, d_out, batch):
        if sched.op == "conv2d_dgrad" and sched.algorithm == "fused_epilogue":
            kw = dict(H_I=H_I, d_in=d_in, block_h=sched.block("block_h"),
                      block_do=sched.block("block_do"), batch=batch)
            want = ccr.conv_dgrad_fused_steps(**kw)
            assert want == sim.simulate_conv_dgrad_fused_steps(**kw)
        elif sched.op == "conv2d_wgrad":
            kw = dict(H_O=H_O, d_in=d_in, d_out=d_out,
                      block_h=sched.block("block_h"),
                      block_di=sched.block("block_di"),
                      block_do=sched.block("block_do"), batch=batch,
                      pipelined=(sched.algorithm == "pipelined"))
            want = ccr.conv_wgrad_steps(**kw)
            assert want == sim.simulate_conv_wgrad_steps(**kw)
        else:
            want = ccr.grid_steps(sched.grid)
            assert want == sim.simulate_grid_steps(sched.grid)
        assert sched.critical_path_steps == want, (sched.op, sched.algorithm)

    @pytest.mark.parametrize("pool", [None, 2])
    def test_conv_bwd_schedules_match_walker(self, pool):
        # Channels wide enough that the forward plans the direct family
        # (narrower ones plan the rows family, whose backward is its own).
        bwd = conv_plan_bwd((4, 12, 12, 64), (3, 3, 64, 128), stride=1,
                            padding=1, pool=pool)
        if pool == 2:
            assert bwd["dgrad"].algorithm == "fused_epilogue"
            assert "recompute" not in bwd
        else:
            assert "recompute" in bwd
        for sched in bwd.values():
            self._pin_conv(sched, H_I=12, H_O=12, d_in=64, d_out=128,
                           batch=4)

    def test_conv_bwd_candidates_cover_both_variants(self):
        """The autotuner's search space carries *both* execution variants
        of each backward op, every one walker-checked."""
        shape = dict(H_O=12, W_O=12, F=3, S=1, P=1, d_in=8, d_out=16,
                     in_bytes=4, batch=4, H_I=12, W_I=12)
        dg = ConvDgradPlanner(TPU_V5E).candidates(**shape, pool=2)
        assert {s.algorithm for s in dg} >= {"fused_epilogue", "direct"}
        wg = ConvWgradPlanner(TPU_V5E).candidates(
            **{k: v for k, v in shape.items() if k != "P"}, padding=1)
        assert {s.algorithm for s in wg} >= {"pipelined", "direct"}
        for sched in dg + wg:
            self._pin_conv(sched, H_I=12, H_O=12, d_in=8, d_out=16, batch=4)

    def test_fc_bwd_schedules_match_walker(self):
        from repro.plan import get_op

        rng = np.random.default_rng(35)
        g, w, x = _rand(rng, (64, 1024)), _rand(rng, (512, 1024)), \
            _rand(rng, (64, 512))
        scheds = list(fc_plan_bwd(x.shape, w.shape).values())
        scheds.append(get_op("matmul_dx").plan(g, w))       # direct variant
        scheds.append(get_op("matmul_dw").plan(x, g))
        for c in MatmulDxPlanner(TPU_V5E).candidates(m=64, n=1024, k=512,
                                                     in_bytes=4):
            scheds.append(c)
        algs = {getattr(s, "algorithm", None) for s in scheds}
        assert {"fused_dxdw", None} <= algs or {"fused_dxdw", "direct"} <= algs
        for sched in scheds:
            want = ccr.grid_steps(sched.grid)
            assert want == sim.simulate_grid_steps(sched.grid)
            assert sched.critical_path_steps == want, (sched.op,
                                                       sched.algorithm)


# ---------------------------------------------------------------------------
# Training path: planned kernels end to end under jax.grad
# ---------------------------------------------------------------------------


class TestTrainingPath:
    def _tiny_cnn(self):
        from repro.configs.base import ModelConfig

        cfg = ModelConfig(name="t", family="cnn", n_layers=2, d_model=4,
                          d_ff=16, vocab=10)
        rng = np.random.default_rng(21)
        params = {}
        for i, (ci, co) in enumerate([(3, 4), (4, 8)]):
            params[f"conv{i}"] = _rand(rng, (3, 3, ci, co))
            params[f"bias{i}"] = _rand(rng, (co,))
        flat = 8 * 8 * 8
        params["fc1"] = _rand(rng, (flat, 16)) * 0.05
        params["fc1_b"] = jnp.zeros((16,), jnp.float32)
        params["fc2"] = _rand(rng, (16, 10)) * 0.05
        params["fc2_b"] = jnp.zeros((10,), jnp.float32)
        return cfg, params, _rand(rng, (2, 32, 32, 3))

    def test_cnn_grads_planned_vs_reference(self):
        from repro.models import cnn

        cfg, params, imgs = self._tiny_cnn()
        scheds = cnn.plan_training(cfg, batch=2)
        labels = jnp.array([1, 2])

        def loss(p, **kw):
            lg = cnn.forward(cfg, p, imgs, **kw)
            return -jax.nn.log_softmax(lg)[jnp.arange(2), labels].mean()

        gk = jax.grad(lambda p: loss(p, use_kernels=True, schedules=scheds))(params)
        gr = jax.grad(lambda p: loss(p, use_kernels=False))(params)
        for k in params:
            assert float(jnp.abs(gk[k] - gr[k]).max()) < TOL, k

    def test_planned_train_step(self):
        """make_train_step with planned_kernels=True runs one finite step
        (the launch/train.py --planned-kernels path)."""
        from repro.configs.base import TrainConfig
        from repro.runtime import train as tr

        cfg, params, imgs = self._tiny_cnn()
        tcfg = TrainConfig(compute_dtype="float32", planned_kernels=True,
                           total_steps=2)
        step = jax.jit(tr.make_train_step(cfg, tcfg))
        state = tr.init_state(cfg, tcfg, params)
        state, metrics = step(state, {"images": imgs, "labels": jnp.array([1, 2])})
        assert np.isfinite(float(metrics["loss"]))
        assert np.isfinite(float(metrics["grad_norm"]))
