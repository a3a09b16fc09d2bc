"""The repro.plan scheduling layer (DESIGN.md Secs. 3 and 5).

Covers the ISSUE acceptance criteria: planner picks are lane-aligned and
fit the machine budget; ConvPlanner reproduces the paper's Delta_O <= 24/12
on MANTICORE (core/ccr.py parity) and the recorded pre-plan chooser picks
on TPU_V5E; planner-emitted modeled words equal ccr.alg2_strip_traffic on
the strip schedule; an explicit Schedule round-trips through
conv2d/fc_matmul; and the mesh-aware planners' ShardedSchedules pin their
HBM/ICI word split against the executed schedule_sim walkers, with the
1-device mesh degenerating to today's Schedules exactly.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # optional-hypothesis shim

from repro.core import ccr
from repro.core import schedule_sim as sim
from repro.core.machine import MANTICORE, TPU_V5E, word_bytes
from repro.kernels.conv2d import conv2d, conv2d_ref
from repro.kernels.matmul import fc_matmul, fc_matmul_ref
from repro.plan import (
    AttentionPlanner,
    ConvPlanner,
    ConvWgradPlanner,
    MatmulDwPlanner,
    MatmulPlanner,
    MeshSpec,
    Planner,
    Schedule,
    ShardedSchedule,
    get_op,
    local_schedule,
    planner_for,
    registered_ops,
    to_roofline,
)

S32 = ccr.ConvShape(W_I=32, D_I=128, D_O=128, F=3, S=1, P=1)


def _rand(rng, shape, dtype=np.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


# ---------------------------------------------------------------------------
# Manticore parity: ccr quotes and device plans from the same code path
# ---------------------------------------------------------------------------


class TestManticoreParity:
    @pytest.mark.parametrize("prec,want", [("sp", 24), ("dp", 12)])
    def test_paper_delta_o(self, prec, want):
        """ConvPlanner at the full-plane strip == the paper's Sec. 2.2.2
        capacity rule: Delta_O = 24 (sp) / 12 (dp) on the running example."""
        sched = ConvPlanner(MANTICORE).plan(
            H_O=32, W_O=32, F=3, S=1, d_in=128, d_out=128,
            in_bytes=word_bytes(prec), padding=1, H_I=32, W_I=32, block_h=32,
        )
        assert sched.block("block_do") == want
        assert sched.block("block_do") == ccr.alg2_max_stack(S32, MANTICORE, prec)
        assert sched.fits(MANTICORE)
        # Full-plane strip words degenerate to Eq. (7) exactly.
        assert sched.modeled_words == ccr.alg2_traffic(S32, want).main_words

    @pytest.mark.parametrize("block_h", [32, 16, 8, 5])
    def test_strip_words_match_ccr(self, block_h):
        """Planner-emitted modeled words == ccr.alg2_strip_traffic at any
        pinned strip height (the acceptance criterion)."""
        sched = ConvPlanner(MANTICORE).plan(
            H_O=32, W_O=32, F=3, S=1, d_in=128, d_out=128,
            in_bytes=4, padding=1, H_I=32, W_I=32, block_h=block_h,
        )
        t = ccr.alg2_strip_traffic(S32, sched.block("block_do"), block_h)
        assert sched.modeled_words == t.main_words
        assert sched.loads == t.main_loads and sched.stores == t.main_stores

    def test_strip_words_match_ccr_auto_and_strided(self):
        """Parity holds when the planner chooses the strip itself, and on a
        strided shape."""
        sched = ConvPlanner(MANTICORE).plan(
            H_O=32, W_O=32, F=3, S=1, d_in=128, d_out=128,
            in_bytes=4, padding=1, H_I=32, W_I=32,
        )
        hb, bdo = sched.block("block_h"), sched.block("block_do")
        assert sched.modeled_words == ccr.alg2_strip_traffic(S32, bdo, hb).main_words

        s2 = ccr.ConvShape(W_I=33, D_I=16, D_O=32, F=3, S=2, P=1)
        sched2 = ConvPlanner(MANTICORE).plan(
            H_O=s2.W_O, W_O=s2.W_O, F=3, S=2, d_in=16, d_out=32,
            in_bytes=4, padding=1, H_I=33, W_I=33, block_h=4,
        )
        t2 = ccr.alg2_strip_traffic(s2, sched2.block("block_do"), 4)
        assert sched2.modeled_words == t2.main_words

    @pytest.mark.parametrize("prec,want", [("sp", 768), ("dp", 384)])
    def test_fc_delta_o(self, prec, want):
        """MatmulPlanner's block_n growth on MANTICORE == alg45_max_stack:
        D_O <= 768 (sp) / 384 (dp) at B = 32 (paper Sec. 3.1.2)."""
        fc = ccr.FCShape(W_I=7, D_I=512, D_O=4096, B=32)
        sched = MatmulPlanner(MANTICORE).plan(
            m=32, n=4096, k=7 * 7 * 512, in_bytes=word_bytes(prec)
        )
        assert sched.block("block_n") == want
        assert sched.block("block_n") == ccr.alg45_max_stack(fc, MANTICORE, prec)
        assert sched.fits(MANTICORE)


# ---------------------------------------------------------------------------
# TPU parity: the planners reproduce the pre-plan choosers' picks
# ---------------------------------------------------------------------------


class TestTpuParity:
    # (H_O, W_O, F, S, d_in, d_out, in_bytes, block_di, pool) -> (hb, bdo),
    # recorded from the pre-refactor choose_schedule on this machine model.
    OLD_CONV_PICKS = {
        (32, 32, 3, 1, 128, 256, 4, 128, 1): (32, 256),
        (32, 32, 3, 1, 64, 512, 2, 128, 2): (32, 512),
        (112, 112, 7, 2, 3, 64, 4, 128, 1): (56, 128),
        (224, 224, 3, 1, 64, 64, 2, 128, 1): (224, 128),
        # Deliberate divergence from the old chooser: its strip candidates
        # stopped at H_O/64, so on this plane it emitted a non-fitting
        # (8, 128) fallback; the planner keeps halving to the pool floor
        # and finds the single-row strip that actually fits VMEM.
        (4096, 4096, 3, 1, 128, 256, 4, 512, 1): (1, 128),
        (16, 16, 5, 1, 8, 16, 4, 128, 1): (16, 128),
        (56, 56, 3, 1, 256, 256, 2, 256, 1): (56, 256),
    }
    # (m, n, k, in_bytes) -> (bm, bn, bk), recorded from choose_blocks.
    OLD_MM_PICKS = {
        (4096, 16384, 8192, 2): (512, 2048, 512),
        (128, 256, 512, 4): (128, 256, 512),
        (32, 4096, 25088, 4): (128, 2048, 512),
        (1, 300, 17, 4): (128, 384, 128),
    }

    def test_conv_planner_reproduces_old_picks(self):
        for (H_O, W_O, F, S, di, do, ib, bdi, pool), want in self.OLD_CONV_PICKS.items():
            sched = ConvPlanner(TPU_V5E).plan(
                H_O=H_O, W_O=W_O, F=F, S=S, d_in=di, d_out=do,
                in_bytes=ib, block_di=bdi, pool=pool,
            )
            assert (sched.block("block_h"), sched.block("block_do")) == want
            assert sched.fits(TPU_V5E)

    def test_matmul_planner_reproduces_old_picks(self):
        for (m, n, k, ib), want in self.OLD_MM_PICKS.items():
            sched = MatmulPlanner(TPU_V5E).plan(m=m, n=n, k=k, in_bytes=ib)
            got = (sched.block("block_m"), sched.block("block_n"),
                   sched.block("block_k"))
            assert got == want


# ---------------------------------------------------------------------------
# Schedule properties: lane alignment, budget, model consistency
# ---------------------------------------------------------------------------

CONV_GRID = [
    (32, 32, 3, 1, 16, 64, 2, 1),
    (15, 15, 5, 1, 7, 40, 4, 1),
    (64, 64, 3, 2, 32, 128, 2, 2),
    (224, 224, 7, 2, 3, 64, 4, 1),
    (512, 512, 3, 1, 256, 512, 2, 2),
    (4096, 4096, 3, 1, 128, 256, 4, 1),  # only fits at single-row strips
    (9, 9, 1, 1, 3, 5, 4, 1),
]


class TestScheduleProperties:
    @pytest.mark.parametrize("H_O,W_O,F,S,di,do,ib,pool", CONV_GRID)
    def test_conv_schedules_aligned_and_fit(self, H_O, W_O, F, S, di, do, ib, pool):
        m = TPU_V5E
        sched = ConvPlanner(m).plan(
            H_O=H_O, W_O=W_O, F=F, S=S, d_in=di, d_out=do, in_bytes=ib, pool=pool
        )
        hb, bdo, bdi = (sched.block("block_h"), sched.block("block_do"),
                        sched.block("block_di"))
        assert bdo % m.lane == 0 and bdi % m.lane == 0
        assert hb % pool == 0 and 0 < hb <= -(-H_O // pool) * pool + pool
        assert sched.fits(m), "auto plans on fitting shapes must fit VMEM"
        assert sched.grid[1] == -(-H_O // hb)
        assert sched.modeled_words == sched.loads + sched.stores > 0
        assert sched.macs > 0 and sched.vmem_bytes > 0

    @pytest.mark.parametrize(
        "m,n,k,ib", [(8, 8, 8, 4), (37, 70, 90, 2), (4096, 16384, 8192, 2),
                     (1, 300, 17, 4), (130, 129, 257, 4)]
    )
    def test_matmul_schedules_aligned_and_fit(self, m, n, k, ib):
        sched = MatmulPlanner(TPU_V5E).plan(m=m, n=n, k=k, in_bytes=ib)
        for name in ("block_m", "block_n", "block_k"):
            assert sched.block(name) % TPU_V5E.lane == 0
        assert sched.fits(TPU_V5E)
        assert len(sched.grid) == 3 and all(g > 0 for g in sched.grid)

    @pytest.mark.parametrize("machine", [TPU_V5E, MANTICORE])
    @pytest.mark.parametrize("sq,skv,d", [(300, 300, 64), (33, 47, 16), (8, 2048, 128)])
    def test_attention_schedules_aligned_and_fit(self, machine, sq, skv, d):
        sched = AttentionPlanner(machine).plan(
            seq_q=sq, seq_kv=skv, head_dim=d, n_q_heads=4, n_kv_heads=2,
            batch=2, in_bytes=4,
        )
        assert sched.block("block_q") % 8 == 0
        assert sched.block("block_kv") % 8 == 0
        assert sched.fits(machine), "auto attention plans shrink to fit"

    @settings(max_examples=20, deadline=None)
    @given(st.integers(2, 300), st.integers(1, 512), st.integers(1, 1024),
           st.sampled_from([1, 3, 5, 7]), st.sampled_from([1, 2]),
           st.sampled_from([2, 4]))
    def test_property_conv_plan_always_legal(self, H_O, di, do, F, S, ib):
        """Whatever the shape, an auto conv plan is lane-aligned, within
        caps, and words-consistent with its own loads/stores split."""
        sched = ConvPlanner(TPU_V5E).plan(
            H_O=H_O, W_O=H_O, F=F, S=S, d_in=di, d_out=do, in_bytes=ib
        )
        # The two-level argmin may pick any family; each family's
        # output-channel block is lane-aligned.
        out_block = {"im2col": "block_n", "rows": "block_c"}.get(
            sched.algorithm, "block_do")
        assert sched.block(out_block) % TPU_V5E.lane == 0
        assert 0 < sched.block("block_h") <= H_O + 1
        assert sched.modeled_words == sched.loads + sched.stores
        if sched.fits(TPU_V5E):
            assert sched.vmem_bytes <= TPU_V5E.usable_for_working_set(2)

    def test_planner_protocol_and_registry(self):
        assert set(registered_ops()) >= {"conv2d", "matmul", "flash_attention"}
        for name in ("conv2d", "matmul", "flash_attention"):
            p = planner_for(name, TPU_V5E)
            assert isinstance(p, Planner) and p.op == name
            assert get_op(name).planner_for(TPU_V5E).op == name

    def test_to_roofline(self):
        sched = MatmulPlanner(TPU_V5E).plan(m=256, n=1024, k=512, in_bytes=4)
        roof = to_roofline(sched)
        assert roof.flops == 2 * sched.macs
        assert roof.bytes_hbm == sched.modeled_words * 4
        assert roof.t_memory > 0 and roof.bottleneck in ("compute", "memory")
        assert sched.bound_kind(TPU_V5E, "sp") in ("compute-bound", "memory-bound")


# ---------------------------------------------------------------------------
# Attention words validation: closed form == executed block walk (ROADMAP)
# ---------------------------------------------------------------------------


class TestAttentionWords:
    """AttentionPlanner's traffic model vs the schedule_sim block walker —
    the same closed-form == executed-count pin done for Algs 1-5, with the
    kernel's causal/window block-level skips included."""

    # Includes seq_q > seq_kv, where a small window leaves trailing q
    # blocks with zero KV fetches (the kernel's clamped BlockSpec pins one
    # residual fetch for such blocks — the model's documented +-1 boundary
    # slack; their rows are defined as zero output, flash_attention.py).
    CASES = [(256, 256, 64, 64), (120, 200, 32, 48), (8, 2048, 8, 128),
             (64, 64, 16, 24), (128, 64, 32, 32), (256, 40, 16, 8)]

    @pytest.mark.parametrize("sq,skv,bq,bkv", CASES)
    @pytest.mark.parametrize("causal,window", [(False, None), (True, None),
                                               (True, 64), (False, 33),
                                               (True, 7)])
    def test_closed_form_matches_walker(self, sq, skv, bq, bkv, causal, window):
        from repro.core import schedule_sim as sim

        sched = AttentionPlanner(TPU_V5E).plan(
            seq_q=sq, seq_kv=skv, head_dim=32, n_q_heads=2, n_kv_heads=1,
            batch=2, in_bytes=4, block_q=bq, block_kv=bkv,
            causal=causal, window=window)
        t = sim.simulate_attention_blocks(
            seq_q=sq, seq_kv=skv, head_dim=32, n_q_heads=2, batch=2,
            block_q=sched.block("block_q"), block_kv=sched.block("block_kv"),
            causal=causal, window=window)
        assert sched.loads == t.main_loads
        assert sched.stores == t.main_stores
        assert sched.macs == t.macs

    def test_dense_degenerates_to_upper_bound(self):
        """No mask -> the original dense closed form (q once per row block,
        every q block streams the whole padded KV twice)."""
        sched = AttentionPlanner(TPU_V5E).plan(
            seq_q=300, seq_kv=300, head_dim=64, n_q_heads=4, n_kv_heads=2,
            batch=2, in_bytes=4)
        bq, bkv = sched.block("block_q"), sched.block("block_kv")
        sqp = -(-300 // bq) * bq
        skvp = -(-300 // bkv) * bkv
        bhq = 2 * 4
        assert sched.loads == bhq * (sqp * 64 + (sqp // bq) * skvp * 64 * 2)
        assert sched.macs == bhq * sqp * skvp * 64 * 2

    def test_causal_and_window_reduce_words(self):
        kw = dict(seq_q=512, seq_kv=512, head_dim=32, block_q=64, block_kv=64)
        p = AttentionPlanner(TPU_V5E)
        dense = p.plan(**kw)
        causal = p.plan(**kw, causal=True)
        windowed = p.plan(**kw, causal=True, window=64)
        assert dense.loads > causal.loads > windowed.loads
        assert dense.macs > causal.macs > windowed.macs
        assert dense.stores == causal.stores == windowed.stores


# ---------------------------------------------------------------------------
# Explicit Schedule round-trips through the kernels (acceptance)
# ---------------------------------------------------------------------------


class TestExplicitScheduleRoundtrip:
    def test_conv2d_roundtrip(self):
        rng = np.random.default_rng(0)
        x = _rand(rng, (2, 10, 10, 6))
        f = _rand(rng, (3, 3, 6, 8))
        b = jnp.zeros((8,), jnp.float32)
        op = get_op("conv2d")
        auto = conv2d(x, f, padding=1)
        sched = op.plan(x, f, b, padding=1)
        via_sched = conv2d(x, f, padding=1, schedule=sched)
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(via_sched))
        np.testing.assert_allclose(
            np.asarray(via_sched), np.asarray(conv2d_ref(x, f, padding=1)),
            rtol=2e-4, atol=2e-4,
        )
        # A hand-built schedule (non-default blocking) also runs & matches.
        hand = sched.evolve(block_h=3, block_do=2, block_di=3)
        np.testing.assert_allclose(
            np.asarray(conv2d(x, f, padding=1, schedule=hand)),
            np.asarray(conv2d_ref(x, f, padding=1)), rtol=2e-4, atol=2e-4,
        )
        # ... even a *partial* one: missing blocks default to legal sizes.
        partial = Schedule(op="conv2d", grid=(), blocks=(("block_do", 2),))
        np.testing.assert_allclose(
            np.asarray(conv2d(x, f, padding=1, schedule=partial)),
            np.asarray(conv2d_ref(x, f, padding=1)), rtol=2e-4, atol=2e-4,
        )

    def test_fc_matmul_roundtrip(self):
        rng = np.random.default_rng(1)
        x = _rand(rng, (37, 70))
        w = _rand(rng, (70, 90))
        op = get_op("matmul")
        sched = op.plan(x, w)
        auto = fc_matmul(x, w)
        via_sched = fc_matmul(x, w, schedule=sched)
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(via_sched))
        np.testing.assert_allclose(
            np.asarray(via_sched), np.asarray(fc_matmul_ref(x, w)),
            rtol=2e-4, atol=2e-4,
        )

    def test_schedule_is_static_and_hashable(self):
        s1 = MatmulPlanner(TPU_V5E).plan(m=8, n=8, k=8, in_bytes=4)
        s2 = MatmulPlanner(TPU_V5E).plan(m=8, n=8, k=8, in_bytes=4)
        assert s1 == s2 and hash(s1) == hash(s2)
        assert isinstance(s1, Schedule)

    def test_layers_accept_schedule(self):
        from repro.core.conv_layer import conv_block, conv_layer
        from repro.core.conv_layer import plan as conv_plan
        from repro.core.fc_layer import fc_layer
        from repro.core.fc_layer import plan as fc_plan

        rng = np.random.default_rng(2)
        x = _rand(rng, (2, 8, 8, 4))
        f = _rand(rng, (3, 3, 4, 6))
        b = _rand(rng, (6,), np.float32)
        sched = conv_plan(x.shape, f.shape, padding=1, pool=2)
        got = conv_block(x, f, b, 1, 1, 2, "strip", sched)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(conv_block(x, f, b, 1, 1, 2, "strip"))
        )
        np.testing.assert_array_equal(
            np.asarray(conv_layer(x, f, 1, 1, "strip", conv_plan(x.shape, f.shape, padding=1))),
            np.asarray(conv_layer(x, f, 1, 1, "strip")),
        )
        xf = _rand(rng, (4, 24))
        wf = _rand(rng, (24, 16))
        np.testing.assert_array_equal(
            np.asarray(fc_layer(xf, wf, fc_plan(xf.shape, wf.shape))),
            np.asarray(fc_layer(xf, wf)),
        )

    def test_cnn_plan_forward(self):
        """models/cnn.plan_forward emits a fitting schedule per stage and
        forward(schedules=...) reproduces the planner-default numerics."""
        from repro.configs.base import ModelConfig
        from repro.models import cnn

        cfg = ModelConfig(name="t", family="cnn", n_layers=2, d_model=4,
                          d_ff=16, vocab=10)
        scheds = cnn.plan_forward(cfg, batch=2)
        assert set(scheds) == {"conv0", "conv1", "fc1", "fc2"}
        assert all(s.fits(TPU_V5E) for s in scheds.values())
        assert sum(s.modeled_words for s in scheds.values()) > 0

        rng = np.random.default_rng(3)
        params = {}
        for i, (ci, co) in enumerate([(3, 4), (4, 8)]):
            params[f"conv{i}"] = _rand(rng, (3, 3, ci, co))
            params[f"bias{i}"] = _rand(rng, (co,), np.float32)
        flat = 8 * 8 * 8
        params["fc1"] = _rand(rng, (flat, 16))
        params["fc1_b"] = _rand(rng, (16,), np.float32)
        params["fc2"] = _rand(rng, (16, 10))
        params["fc2_b"] = _rand(rng, (10,), np.float32)
        images = _rand(rng, (2, 32, 32, 3))
        a = cnn.forward(cfg, params, images)
        b = cnn.forward(cfg, params, images, schedules=scheds)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Sharded planning: partitioning as a planner output (DESIGN.md Sec. 5)
# ---------------------------------------------------------------------------

# The paper's running shapes on the 16-cluster L2 quadrant.
QUAD16 = MeshSpec((("cluster", 16),))
MESH4 = MeshSpec((("model", 4),))
MESH1 = MeshSpec((("model", 1),))
FC_SHAPE = dict(m=32, n=4096, k=25088, in_bytes=4)  # FC6-like, B=32


class TestShardedPlans:
    def test_one_device_mesh_degenerates_to_schedule(self):
        """A 1-device mesh must reproduce today's pinned Schedules exactly
        (wrapped in a trivial ShardedSchedule)."""
        base = ConvPlanner(MANTICORE).plan(
            H_O=32, W_O=32, F=3, S=1, d_in=128, d_out=128,
            in_bytes=4, padding=1, H_I=32, W_I=32, block_h=32)
        ss = ConvPlanner(MANTICORE, MESH1).plan(
            H_O=32, W_O=32, F=3, S=1, d_in=128, d_out=128,
            in_bytes=4, padding=1, H_I=32, W_I=32, block_h=32)
        assert isinstance(ss, ShardedSchedule)
        assert ss.schedule == base and local_schedule(ss) == base
        assert ss.strategy == "single" and ss.devices == 1
        assert ss.block("block_do") == 24  # the paper's Delta_O, unchanged
        assert (ss.hbm_loads, ss.hbm_stores) == (base.loads, base.stores)
        assert ss.ici_words == 0 and ss.macs == base.macs
        assert ss.modeled_words == base.modeled_words

        mb = MatmulPlanner(TPU_V5E).plan(**FC_SHAPE)
        ms = MatmulPlanner(TPU_V5E, MESH1).plan(**FC_SHAPE)
        assert ms.schedule == mb and ms.ici_words == 0
        assert ms.hbm_words == mb.modeled_words

    def test_one_device_strategy_pin_degenerates(self):
        """Pinning psum/ring on a 1-wide group degenerates to single —
        sharded call sites must keep working on one device."""
        for pin in ("psum", "ring"):
            ss = MatmulPlanner(TPU_V5E, MESH1, "model", pin).plan(**FC_SHAPE)
            assert ss.strategy == "single" and ss.ici_words == 0

    def test_manticore_quadrant_picks_ring(self):
        """On the paper's 16-cluster quadrant the argmin picks Alg 3's
        ring: its reuse converts ~1/3 of the psum strategy's main-memory
        words into neighbour hops — the Sec. 2.3 story, now a planner
        decision.  Counts pinned against ccr.ring_traffic."""
        ss = MatmulPlanner(MANTICORE, QUAD16, "cluster").plan(**FC_SHAPE)
        assert ss.strategy == "ring"
        assert ss.axis == "cluster" and ss.devices == 16
        t = ccr.ring_traffic(m=32, n=4096, k=25088, devices=16)
        assert (ss.hbm_loads, ss.hbm_stores) == (t.main_loads, t.main_stores)
        assert ss.ici_words == t.intercluster == 15 * 32 * 25088
        assert ss.macs == t.macs
        # vs the pinned psum alternative: ring moves fewer total words.
        ps = MatmulPlanner(MANTICORE, QUAD16, "cluster", "psum").plan(**FC_SHAPE)
        assert ps.strategy == "psum"
        assert ss.modeled_words < ps.modeled_words
        assert ss.hbm_words < ps.hbm_words  # the reuse is an HBM saving
        # partitioning is part of the plan: X K-sharded, W N-sharded, out
        # N-sharded for the ring; K/K/replicated for the psum.
        assert ss.partition == ((None, "cluster"), (None, "cluster"),
                                (None, "cluster"))
        assert ps.partition == ((None, "cluster"), ("cluster", None),
                                (None, None))

    def test_ring_words_equal_executed_walk(self):
        """modeled == simulated for the ring, at several mesh widths."""
        for devices in (2, 4, 16):
            mesh = MeshSpec((("model", devices),))
            ss = MatmulPlanner(MANTICORE, mesh, "model", "ring").plan(
                m=8, n=64, k=128, in_bytes=4)
            w = sim.simulate_ring(m=8, n=64, k=128, devices=devices)
            assert ss.hbm_loads == w.main_loads
            assert ss.hbm_stores == w.main_stores
            assert ss.ici_words == w.intercluster
            assert ss.macs == w.macs

    def test_psum_words_equal_executed_walk(self):
        ss = MatmulPlanner(TPU_V5E, MESH4, "model", "psum").plan(
            m=37, n=300, k=512, in_bytes=4)
        bd = ss.schedule.block_dict()
        w = sim.simulate_fc_psum(
            m=37, n=300, k=128, devices=4, block_m=bd["block_m"],
            block_n=bd["block_n"], block_k=bd["block_k"])
        # NB the walker takes the *local* k (the planner planned k/4).
        assert ss.hbm_loads == w.main_loads
        assert ss.hbm_stores == w.main_stores
        assert ss.ici_words == w.intercluster == ccr.tree_reduce_words(4, 37 * 300)

    def test_sharded_conv_words_equal_executed_walk(self):
        """The conv "batch" partition: mesh totals equal the per-device
        strip walks summed (and the unsharded words — pure data
        parallelism moves no extra HBM word)."""
        s = ccr.ConvShape(W_I=32, D_I=16, D_O=32, F=3, S=1, P=1)
        ss = ConvPlanner(MANTICORE, MeshSpec((("data", 4),)), "data").plan(
            H_O=32, W_O=32, F=3, S=1, d_in=16, d_out=32, in_bytes=4,
            padding=1, H_I=32, W_I=32, block_h=8, batch=8)
        assert ss.strategy == "batch"
        stack = ss.block("block_do")
        w = sim.simulate_sharded_conv_strip(s, stack, 8, devices=4,
                                            strategy="batch", batch=8)
        t = ccr.conv_sharded_traffic(s, stack, 8, devices=4,
                                     strategy="batch", batch=8)
        assert (ss.hbm_loads, ss.hbm_stores) == (w.main_loads, w.main_stores)
        assert (t.main_loads, t.main_stores) == (w.main_loads, w.main_stores)
        assert ss.ici_words == 0
        # == the unsharded schedule's words (data parallelism is free in
        # HBM terms; the win is 4x the bandwidth).
        base = ConvPlanner(MANTICORE).plan(
            H_O=32, W_O=32, F=3, S=1, d_in=16, d_out=32, in_bytes=4,
            padding=1, H_I=32, W_I=32, block_h=8, batch=8)
        assert ss.hbm_words == base.modeled_words

    def test_sharded_wgrad_charges_gradient_allreduce(self):
        """Data-parallel wgrad accumulates private dW per device: the
        sharded plan must charge the Alg-4 tree reduction as ici_words and
        one private dW store per device."""
        ss = ConvWgradPlanner(TPU_V5E, MeshSpec((("data", 4),)), "data").plan(
            H_O=8, W_O=8, F=3, d_in=8, d_out=16, in_bytes=4, batch=8,
            padding=1, H_I=8, W_I=8)
        assert ss.strategy == "batch"
        assert ss.ici_words == ccr.tree_reduce_words(4, 3 * 3 * 8 * 16)
        local = ss.schedule
        assert ss.hbm_stores == 4 * local.stores  # private dW per device
        dw = MatmulDwPlanner(TPU_V5E, MeshSpec((("data", 4),)), "data").plan(
            m=32, n=64, k=128, in_bytes=4)
        assert dw.strategy == "batch"
        assert dw.ici_words == ccr.tree_reduce_words(4, 128 * 64)

    def test_sharded_schedule_traffic_and_fits(self):
        ss = MatmulPlanner(MANTICORE, QUAD16, "cluster").plan(**FC_SHAPE)
        t = ss.traffic
        assert isinstance(t, ccr.Traffic)
        assert t.main_words == ss.hbm_words and t.intercluster == ss.ici_words
        assert t.ccr_offchip > t.ccr  # ring traffic is mostly on-chip
        assert ss.fits(MANTICORE) == ss.schedule.fits(MANTICORE)

    def test_plan_sharded_through_registry(self):
        """PallasOp.plan_sharded resolves the same cached ShardedSchedule
        the planner emits, from concrete operands."""
        rng = np.random.default_rng(0)
        x = _rand(rng, (8, 64))
        w = _rand(rng, (64, 40))
        op = get_op("matmul")
        ss = op.plan_sharded(x, w, mesh=MESH4, axis="model", strategy="ring")
        assert isinstance(ss, ShardedSchedule) and ss.strategy == "ring"
        ss2 = op.plan_sharded(x, w, mesh=MESH4, axis="model", strategy="ring")
        assert ss is ss2  # the plan cache covers sharded plans too
        # and a dict-shaped mesh resolves identically
        ss3 = op.plan_sharded(x, w, mesh={"model": 4}, axis="model",
                              strategy="ring")
        assert ss3 == ss

    def test_cnn_sharded_plan_training(self):
        """models/cnn.plan_training(mesh=) returns ShardedSchedules whose
        forward entries move no ICI words while wgrad/dw charge the
        gradient all-reduce; the 1-device mesh reproduces the meshless
        plans exactly."""
        from repro.configs.base import ModelConfig
        from repro.models import cnn

        cfg = ModelConfig(name="t", family="cnn", n_layers=2, d_model=4,
                          d_ff=16, vocab=10)
        mesh = MeshSpec((("data", 4),))
        scheds = cnn.plan_training(cfg, batch=8, mesh=mesh)
        assert all(isinstance(s, ShardedSchedule) for s in scheds.values())
        for name, s in scheds.items():
            if name.endswith(".wgrad") or name.endswith(".dw"):
                assert s.ici_words > 0, name  # gradient all-reduce
            elif "." not in name:
                # Forward stages, conv and FC alike, shard the batch: the
                # data-parallel step executes exactly these.
                assert s.strategy == "batch" and s.ici_words == 0, name
        base = cnn.plan_training(cfg, batch=8)
        one = cnn.plan_training(cfg, batch=8, mesh=MeshSpec((("data", 1),)))
        assert {k: s.schedule for k, s in one.items()} == base


def test_unfit_schedule_is_never_compiled():
    """A schedule over its machine's VMEM budget is refused before it
    reaches the compiler; interpret mode, which has no VMEM, still runs
    it."""
    x = jnp.ones((1, 8, 8, 4), jnp.float32)
    f = jnp.ones((3, 3, 4, 4), jnp.float32)
    unfit = ConvPlanner(MANTICORE).plan(H_O=8, W_O=8, F=3, S=1, d_in=4,
                                        d_out=4, in_bytes=4)
    unfit = dataclasses.replace(unfit, vmem_bytes=1 << 30)
    assert not unfit.fits(MANTICORE)
    with pytest.raises(ValueError, match="VMEM"):
        conv2d(x, f, padding=1, schedule=unfit, interpret=False)
    out = conv2d(x, f, padding=1, schedule=unfit, interpret=True)
    np.testing.assert_allclose(out, conv2d_ref(x, f, padding=1), rtol=1e-5)
