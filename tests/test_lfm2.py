"""The lfm2 family on the CPU (Pallas in interpret mode): the planned
training loss and every gradient leaf against the plain float32
reference, the held-expert share test, the grouped matmul and the
blocked attention backward against their XLA oracles, the planners'
fit, and the cnn-vgg11 cells' schedules pinned.

The reference is the benchmark's (``benchmarks/chip/families/lfm2.py``):
one plain LFM2, written from the published equations and importing
nothing of the program, serves the chip's comparison and these tests."""

from __future__ import annotations

import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, TrainConfig
from repro.configs.registry import get_config
from repro.core.machine import TPU_V5E_SCOPED
from repro.kernels.matmul import grouped
from repro.models import lfm2, moe
from repro.models import transformer as tf
from repro.models.module import init_params
from repro.plan import GroupedMatmulDwPlanner, GroupedMatmulDxPlanner
from repro.plan import GroupedMatmulPlanner
from repro.runtime import train as tr

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from families import lfm2 as ref  # noqa: E402

# A tiny share: layer 0 conv + dense FFN, then attention and conv over
# routed experts; experts 2-5 of 8 held, top 2.
TINY = ModelConfig(
    name="lfm2-tiny", family="lfm2", n_layers=3, d_model=64, vocab=256,
    d_ff=128, n_heads=4, n_kv_heads=2, head_dim=16, qk_norm=True,
    norm_eps=1e-5, rope_theta=1e6, n_experts=8, moe_top_k=2, d_ff_expert=32,
    layer_types=("conv", "full_attention", "conv"),
    n_dense_layers=1, conv_width=3, experts_held=(2, 4))


def tiny_config(cfg: ModelConfig = TINY) -> dict:
    """The reference's configuration file for a program config."""
    first, count = cfg.held
    return {"hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
            "moe_intermediate_size": cfg.d_ff_expert,
            "num_hidden_layers": cfg.n_layers,
            "layer_types": list(cfg.layer_types),
            "num_dense_layers": cfg.n_dense_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "vocab_size": cfg.vocab, "norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "conv_L_cache": cfg.conv_width,
            "num_experts": count, "held_experts": {"first": first},
            "published": {"num_experts": cfg.n_experts},
            "num_experts_per_tok": cfg.moe_top_k, "use_expert_bias": True}


def _batch(cfg, B=2, S=16, seed=0):
    toks = np.random.default_rng(seed).integers(0, cfg.vocab, (B, S + 1))
    return {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
            "labels": jnp.asarray(toks[:, 1:], jnp.int32)}


def test_published_and_share_configs():
    full, share = get_config("lfm2-8b-a1b"), get_config("lfm2-8b-a1b-ep4")
    assert full.layer_types.count("conv") == 18 and full.n_layers == 24
    changed = {f.name for f in dataclasses.fields(full)
               if getattr(full, f.name) != getattr(share, f.name)}
    assert changed == {"name", "n_layers", "layer_types", "n_dense_layers",
                       "experts_held", "vocab"}
    assert share.layer_types == ("conv", "full_attention", "conv", "conv",
                                 "conv")
    assert share.held == (0, 8) and share.n_experts == 32
    from repro.models.module import count_params
    assert count_params(lfm2.param_defs(share)) == 507_820_160
    assert count_params(lfm2.param_defs(full)) == 8_339_929_856


def test_planned_loss_and_every_gradient_leaf_match_the_reference():
    cfg = TINY
    params = init_params(lfm2.param_defs(cfg), jax.random.PRNGKey(3))
    config = tiny_config(cfg)
    want_params = ref.init_params(config, 3)
    assert jax.tree.structure(params) == jax.tree.structure(want_params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    batch = _batch(cfg)
    loss_fn = lfm2.make_loss_fn(cfg, TrainConfig(compute_dtype="float32",
                                                 remat="block", loss_chunks=2))
    (loss, counters), grads = jax.jit(jax.value_and_grad(
        loss_fn, has_aux=True))(params, batch)
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p, b: ref.loss_fn(p, b, config, lambda a: a)))(params,
                                                                 batch)
    np.testing.assert_allclose(float(loss), float(want), rtol=2e-5)
    flat = dict(jax.tree_util.tree_leaves_with_path(grads))
    for path, g in jax.tree_util.tree_leaves_with_path(want_grads):
        got = np.asarray(flat[path])
        scale = float(np.abs(np.asarray(g)).max()) + 1e-12
        np.testing.assert_allclose(got / scale, np.asarray(g) / scale,
                                   atol=2e-4, err_msg=str(path))
    # Both expert layers' counters ride beside the loss.
    assert set(counters) == {"experts1", "experts2"}
    for c in counters.values():
        assert int(c["rows"].sum()) > 0 and int(c["pad"]) >= 0


def test_counters_count_every_held_assignment_and_ride_the_step():
    cfg = TINY
    params = init_params(lfm2.param_defs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, cfg.d_model))
    mp = params["layers"]["1"]["moe"]
    sched = lfm2.plan_training(cfg, 2, 16, in_bytes=4)
    _, c = moe.held_expert_ffn(x, mp, cfg, sched, 1)
    idx, _ = moe.route(x, mp["router"], cfg)
    want = [int((idx == e).sum()) for e in range(2, 6)]
    assert c["rows"].tolist() == want
    tile = sched["experts_up"]["fwd"].block("block_m")
    assert int(c["pad"]) == sum(-(-r // tile) * tile - r for r in want)


def test_four_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of the held experts, each routing over all of
    them from the same router weights, sum to the layer that holds every
    expert (the router is computed once, alike on every chip)."""
    whole = dataclasses.replace(TINY, experts_held=None)
    params = init_params(lfm2.param_defs(whole), jax.random.PRNGKey(5))
    mp = params["layers"]["1"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(6), (32, whole.d_model))
    sched = lfm2.plan_training(whole, 2, 16, in_bytes=4)
    y_all, _ = moe.held_expert_ffn(x, mp, whole, sched, 1)
    parts = []
    for first in range(0, 8, 2):
        share = dataclasses.replace(whole, experts_held=(first, 2))
        sp = dict(mp, **{k: mp[k][first:first + 2]
                         for k in ("w_gate", "w_up", "w_down")})
        parts.append(moe.held_expert_ffn(
            x, sp, share, lfm2.plan_training(share, 2, 16, in_bytes=4), 1)[0])
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(y_all),
                               rtol=1e-5, atol=1e-5)
    config = dict(tiny_config(whole), num_experts=8,
                  held_experts={"first": 0})
    with jax.default_matmul_precision("highest"):
        want = ref.routed_experts(mp, x[None], config)[0]
    np.testing.assert_allclose(np.asarray(y_all), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_grouped_matmul_and_its_gradients_match_the_oracle():
    sizes = jnp.array([128, 0, 256, 128], jnp.int32)  # 3 held + tail
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((512, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 256, 384)), jnp.float32)
    sched = grouped.plan(512, 256, 384, 3, group_rows=128, in_bytes=4)
    got = jax.jit(jax.value_and_grad(
        lambda x, w: (grouped.grouped_matmul(x, w, sizes, sched) ** 2).sum(),
        (0, 1)))(x, w)
    want = jax.value_and_grad(
        lambda x, w: (grouped.grouped_matmul_ref(x, w, sizes) ** 2).sum(),
        (0, 1))(x, w)
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-3)
    out = grouped.grouped_matmul(x, w, sizes, sched)
    assert not np.asarray(out[384:]).any()  # the tail costs nothing


@pytest.mark.parametrize("planner", [GroupedMatmulPlanner,
                                     GroupedMatmulDxPlanner,
                                     GroupedMatmulDwPlanner])
@pytest.mark.parametrize("k,n", [(2048, 3584), (1792, 2048)])
def test_grouped_planners_fit_the_default_scoped_vmem(planner, k, n):
    s = planner().plan(m=66_560, k=k, n=n, groups=8, group_rows=2048,
                       out_bytes=4 if planner is GroupedMatmulDwPlanner
                       else 2)
    assert s.machine == TPU_V5E_SCOPED.name and s.fits(TPU_V5E_SCOPED)
    assert k % s.block("block_k") == 0 and n % s.block("block_n") == 0
    assert 66_560 % s.block("block_m") == 0


def test_planned_lfm2_step_uses_one_row_tile_and_fits():
    sched = lfm2.plan_training(get_config("lfm2-8b-a1b-ep4"), 2, 8192,
                               loss_chunks=4, in_bytes=2)
    tiles = {s.block("block_m") for cell in ("experts_up", "experts_down")
             for s in sched[cell].values()}
    assert len(tiles) == 1
    from repro.core.machine import TPU_V5E
    for name, s in sched.items():
        if not isinstance(s, dict):
            assert s.fits(TPU_V5E), name


@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 24)])
def test_blocked_attention_backward_is_the_exact_gradient(monkeypatch,
                                                          causal, window):
    from repro.kernels.flash_attention.ref import attention_ref

    monkeypatch.setattr(tf, "ATTN_BWD_BLOCK_BYTES", 2 * 4 * 64 * 4 * 16)
    rng = np.random.default_rng(0)
    q, k, v, g = (jnp.asarray(rng.standard_normal(s), jnp.float32) for s in
                  [(2, 4, 64, 16), (2, 2, 64, 16), (2, 2, 64, 16),
                   (2, 4, 64, 16)])
    assert tf.attn_bwd_block(2, 4, 64) == 16
    _, vjp = jax.vjp(lambda a, b, c: attention_ref(
        a, b, c, causal=causal, window=window), q, k, v)
    with jax.default_matmul_precision("highest"):
        got = tf._attn_bwd(q, k, v, g, causal, window, None)
    for a, b in zip(got, vjp(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_attention_backward_block_holds_no_full_score_matrix():
    assert tf.attn_bwd_block(2, 32, 8192) * 8192 < 8192 * 8192 // 32


# The cnn-vgg11 cells' planned schedules (op, algorithm, grid, blocks),
# as they were before the fused dX/dW kernel's VMEM count took its output
# blocks in: the corrected count leaves both cells' plans as they were.
# conv0's 3 input channels run the row-GEMM family in both cells; every
# other stage keeps its schedule.
VGG11_B256 = {
    "conv0": ("conv2d", "rows", (8,), (("block_b", 32), ("block_c", 256), ("block_h", 32))),
    "conv0.dgrad": ("conv2d_dgrad", "rows", (8,), (("block_b", 32), ("block_c", 256), ("block_h", 32))),
    "conv0.wgrad": ("conv2d_wgrad", "rows", (8,), (("block_b", 32), ("block_c", 256), ("block_h", 32))),
    "conv1": ("conv2d", "direct", (256, 1, 1, 1), (("block_di", 128), ("block_do", 128), ("block_h", 16))),
    "conv1.dgrad": ("conv2d_dgrad", "fused_epilogue", (256, 1, 1), (("block_di", 128), ("block_do", 128), ("block_h", 16))),
    "conv1.wgrad": ("conv2d_wgrad", "pipelined", (1, 1), (("block_di", 128), ("block_do", 128), ("block_h", 16))),
    "conv2": ("conv2d", "im2col", (1, 32, 1, 3), (("block_h", 8), ("block_k", 512), ("block_m", 512), ("block_n", 256))),
    "conv2.dgrad": ("conv2d_dgrad", "fused_epilogue", (256, 1, 1), (("block_di", 256), ("block_do", 128), ("block_h", 8))),
    "conv2.wgrad": ("conv2d_wgrad", "pipelined", (1, 1), (("block_di", 128), ("block_do", 256), ("block_h", 8))),
    "conv3": ("conv2d", "im2col", (1, 8, 1, 5), (("block_h", 4), ("block_k", 512), ("block_m", 512), ("block_n", 512))),
    "conv3.dgrad": ("conv2d_dgrad", "fused_epilogue", (256, 1, 1), (("block_di", 512), ("block_do", 256), ("block_h", 4))),
    "conv3.wgrad": ("conv2d_wgrad", "pipelined", (1, 1), (("block_di", 256), ("block_do", 512), ("block_h", 4))),
    "fc1": ("matmul", "direct", (1, 2, 4), (("block_k", 512), ("block_m", 256), ("block_n", 2048))),
    "fc1.dw": ("matmul_dw", "direct", (4, 2, 1), (("block_k", 512), ("block_m", 256), ("block_n", 2048))),
    "fc1.dx": ("matmul_dx", "fused_dxdw", (1, 8, 1), (("block_k", 2048), ("block_m", 256), ("block_n", 512))),
    "fc2": ("matmul", "direct", (1, 1, 8), (("block_k", 512), ("block_m", 256), ("block_n", 1024))),
    "fc2.dw": ("matmul_dw", "direct", (8, 1, 1), (("block_k", 512), ("block_m", 256), ("block_n", 1024))),
    "fc2.dx": ("matmul_dx", "fused_dxdw", (2, 2, 1), (("block_k", 2048), ("block_m", 256), ("block_n", 512))),
}
VGG11_B6144 = {
    "conv0": ("conv2d", "rows", (192,), (("block_b", 32), ("block_c", 256), ("block_h", 32))),
    "conv0.dgrad": ("conv2d_dgrad", "rows", (192,), (("block_b", 32), ("block_c", 256), ("block_h", 32))),
    "conv0.wgrad": ("conv2d_wgrad", "rows", (192,), (("block_b", 32), ("block_c", 256), ("block_h", 32))),
    "conv1": ("conv2d", "direct", (6144, 1, 1, 1), (("block_di", 128), ("block_do", 128), ("block_h", 16))),
    "conv1.dgrad": ("conv2d_dgrad", "fused_epilogue", (6144, 1, 1), (("block_di", 128), ("block_do", 128), ("block_h", 16))),
    "conv1.wgrad": ("conv2d_wgrad", "pipelined", (1, 1), (("block_di", 128), ("block_do", 128), ("block_h", 16))),
    "conv2": ("conv2d", "im2col", (1, 768, 1, 3), (("block_h", 8), ("block_k", 512), ("block_m", 512), ("block_n", 256))),
    "conv2.dgrad": ("conv2d_dgrad", "fused_epilogue", (6144, 1, 1), (("block_di", 256), ("block_do", 128), ("block_h", 8))),
    "conv2.wgrad": ("conv2d_wgrad", "pipelined", (1, 1), (("block_di", 128), ("block_do", 256), ("block_h", 8))),
    "conv3": ("conv2d", "im2col", (1, 192, 1, 5), (("block_h", 4), ("block_k", 512), ("block_m", 512), ("block_n", 512))),
    "conv3.dgrad": ("conv2d_dgrad", "fused_epilogue", (6144, 1, 1), (("block_di", 512), ("block_do", 256), ("block_h", 4))),
    "conv3.wgrad": ("conv2d_wgrad", "pipelined", (1, 1), (("block_di", 256), ("block_do", 512), ("block_h", 4))),
    "fc1": ("matmul", "direct", (12, 2, 4), (("block_k", 512), ("block_m", 512), ("block_n", 2048))),
    "fc1.dw": ("matmul_dw", "direct", (4, 2, 12), (("block_k", 512), ("block_m", 512), ("block_n", 2048))),
    "fc1.dx": ("matmul_dx", "direct", (12, 1, 8), (("block_k", 2048), ("block_m", 512), ("block_n", 512))),
    "fc2": ("matmul", "direct", (12, 1, 8), (("block_k", 512), ("block_m", 512), ("block_n", 1024))),
    "fc2.dw": ("matmul_dw", "direct", (8, 1, 12), (("block_k", 512), ("block_m", 512), ("block_n", 1024))),
    "fc2.dx": ("matmul_dx", "direct", (12, 2, 2), (("block_k", 2048), ("block_m", 512), ("block_n", 512))),
}


@pytest.mark.parametrize("batch,want", [(256, VGG11_B256), (6144, VGG11_B6144)])
def test_vgg11_cells_planned_schedules_are_pinned(batch, want):
    from repro.models import cnn

    got = cnn.plan_training(get_config("cnn-vgg11"), batch, in_bytes=2)
    assert {k: (s.op, s.algorithm, s.grid, s.blocks)
            for k, s in got.items()} == want


def test_fused_dxdw_count_takes_in_its_output_blocks():
    """At batch 2048 fc2's fused kernel asked the compiler for 69 MiB of
    scoped VMEM (a described v5e compile) against 64: its count now
    includes the double-buffered f32 output blocks, so plan_bwd falls
    back to the split dX/dW kernels there."""
    from repro.core import fc_layer as fl
    from repro.core.machine import TPU_V5E

    fused = fl.plan_bwd((2048, 4096), (4096, 1000), in_bytes=2)
    assert fused["dx"].algorithm == "direct"
    at256 = fl.plan_bwd((256, 4096), (4096, 1000), in_bytes=2)["dx"]
    assert at256.algorithm == "fused_dxdw" and at256.fits(TPU_V5E)


def test_counters_reach_the_loop_history():
    """A family's counters come out of the step beside the loss, and
    run_elastic records them; a loss without counters adds none."""
    def build(_):
        def step(state, batch):
            return state, {"loss": jnp.float32(1.0),
                           "counters": {"experts1": {
                               "rows": jnp.array([3, 4]),
                               "pad": jnp.array(9)}}}
        return tr.ElasticRun(step_fn=step, state=None, start=0)

    _, hist = tr.run_elastic(build, lambda s: {"x": np.zeros(1)}, 2,
                             log=lambda *_: None)
    assert hist[0]["counters"] == {"experts1": {"rows": [3, 4], "pad": 9}}
