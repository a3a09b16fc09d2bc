"""Model families: the lookup by name, the harness that names none of
them, leaf paths over nested trees, token throughput, and the transformer
family's reference against the program's dense decoder."""

from __future__ import annotations

import dataclasses
import json
import pathlib
import re
import types
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cell, loop, reference
from chipbench.measure import UNFIT
from chipbench.record import Record
from families import cnn
from families import transformer as dense

HERE = pathlib.Path(__file__).resolve().parents[1]
TINY = json.loads((HERE / "tests" / "token_cell" / "configs"
                   / "tiny-dense.json").read_text())
TRAFFIC = {"batch": 2, "seq": 16}
GENERIC = ["chipbench/measure.py", "chipbench/loop.py",
           "chipbench/reference.py", "chipbench/inputs.py",
           "chipbench/record.py", "chipbench/trace.py", "chipbench/work.py",
           "chipbench/faults.py", "chipbench/compare.py", "calibrate.py"]
# A model's word as a name or a word of prose: ``conv0``, ``fc_hidden``
# and ``images`` count, ``convolution`` and ``convert`` do not.
MODEL_WORD = re.compile(r"(?<![A-Za-z])(cnn|images|conv|fc)(?![a-z])")


def test_family_found_by_name():
    assert cell.family({"family": "cnn"}) is cnn
    assert cell.family({"family": "transformer"}) is dense
    with pytest.raises(SystemExit, match=r"no model family 'lfm2'.*"
                                         r"\['cnn', 'transformer'\]"):
        cell.family({"family": "lfm2"})


@pytest.mark.parametrize("path", GENERIC)
def test_generic_module_names_no_model(path):
    text = (HERE / path).read_text()
    assert MODEL_WORD.findall(text) == []


def test_only_the_cnn_family_names_the_program_cnn():
    named = re.compile(r"repro\.models\.cnn|repro\.models import cnn|"
                       r"\bimages\b|conv_layers|fc_hidden")
    files = [p for d in ("chipbench", "families")
             for p in sorted((HERE / d).glob("*.py"))
             if named.search(p.read_text())]
    assert files == [HERE / "families" / "cnn.py"]


def test_capture_and_reference_name_the_same_leaves():
    params = dense.init_params(TINY, 3)
    grads = jax.tree.map(lambda p: jnp.full_like(p, 0.5), params)
    capture = loop.Capture(beta1=0.9)
    reads = capture.reads()
    state = types.SimpleNamespace(params=params,
                                  opt=types.SimpleNamespace(m=grads))
    reads[0](state)
    reads[1](state)
    moved = types.SimpleNamespace(
        params=jax.tree.map(lambda p: p + 1.0, params), opt=state.opt)
    reads[loop.REFERENCE_STEPS](moved)
    paths = set(reference.leaves(params))
    assert "layers/attn/wq" in paths and "layers/mlp/w_down" in paths
    assert set(capture.grad) == set(capture.update) == paths
    assert set(reference.norms(params)) == paths
    batches = [dense.make_batch(3, i, TINY, TRAFFIC) for i in range(2)]
    side = reference.train(TINY, batches, 3)
    assert set(side.grad) == set(side.update) == set(side.grad_raw) == paths
    # A flat tree's leaves keep their keys.
    flat = cnn.init_params(dict(json.loads(
        (HERE / "configs" / "cnn-vgg11.json").read_text()), conv_layers=1,
        fc_hidden=8, classes=4), 0)
    assert set(reference.leaves(flat)) == set(flat)


def _record(tokens, intervals):
    return Record(config={"family": "transformer"}, traffic={}, batch=4,
                  tokens=tokens, chips=1, peak={}, setup_s=1.0,
                  intervals=intervals, trace=None)


def test_tokens_per_s_reads_the_tokens_of_every_step():
    read = cell.reader("tokens_per_s")
    # 4 steps of 64 tokens in 0.5 s.
    assert read(_record(64, [0.1, 0.1, 0.2, 0.1])) == pytest.approx(512.0)
    # A model that reads no tokens, or a window with no step: nothing.
    assert read(_record(None, [0.1])) is None
    assert read(_record(64, [])) is None


def test_unfit_schedule_warning_refuses_the_run():
    from repro.core.conv_layer import (UnfitScheduleWarning,
                                       warn_unfit_schedule)
    from repro.core.machine import TPU_V5E
    from repro.plan import Schedule

    sched = Schedule(op="matmul", grid=(1,), blocks=(), vmem_bytes=2**40)
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=UNFIT)
        with pytest.raises(UnfitScheduleWarning):
            warn_unfit_schedule("dx", sched, TPU_V5E)


def _program(config: dict, **changes):
    from repro.configs.base import ModelConfig

    c = dict(config, **changes)
    return c, ModelConfig(
        name="tiny", family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], vocab=c["vocab_size"],
        d_ff=c["intermediate_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        qk_norm=c["qk_norm"], qkv_bias=c["attention_bias"],
        norm_eps=c["rms_norm_eps"], tie_embeddings=c["tie_word_embeddings"],
        scale_embed=c["scale_embed"], rope_theta=c["rope_theta"])


@pytest.mark.parametrize("changes", [
    {},
    {"qk_norm": True, "attention_bias": False, "tie_word_embeddings": False},
    {"scale_embed": True, "num_key_value_heads": 4},
])
def test_dense_reference_matches_the_programs_xla_forward(changes):
    from repro.models import transformer
    from repro.models.module import count_params, init_params

    config, program = _program(TINY, **changes)
    want = init_params(transformer.param_defs(program), jax.random.PRNGKey(7))
    got = dense.init_params(config, 7)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert dense.param_count(config) == count_params(
        transformer.param_defs(program))
    tokens = jnp.asarray(dense.make_batch(7, 0, config, TRAFFIC)["tokens"])
    with jax.default_matmul_precision("highest"):
        h, _ = transformer.forward(program, want, tokens,
                                   compute_dtype=jnp.float32)
        prog = transformer.logits(program, want, h)
        ref = dense.logits(got, tokens, config)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(prog), rtol=1e-5,
                               atol=1e-5 * float(jnp.max(jnp.abs(prog))))


def test_dense_check_program_refuses_another_shape(monkeypatch):
    from repro.launch import train as launcher

    config, program = _program(TINY)
    monkeypatch.setattr(launcher, "get_config", lambda arch: program)
    dense.check_program(config)
    monkeypatch.setattr(launcher, "get_config", lambda arch: dataclasses.replace(
        program, n_kv_heads=4))
    with pytest.raises(SystemExit, match="num_key_value_heads"):
        dense.check_program(config)


def test_dense_step_flops():
    # Every GEMM 3 x 2 x its weights a token; causal attention 3 x 4 x
    # heads x head_dim a layer for each of the seq (seq + 1) / 2 pairs.
    n, d, ff, v = 2, 64, 128, 256
    hq, hkv, dh, seq = 4, 2, 16, 16
    gemm = n * (d * (hq + 2 * hkv) * dh + hq * dh * d + 3 * d * ff) + d * v
    attn = 3 * 4 * hq * dh * n * seq * (seq + 1) / 2
    want = 6 * seq * gemm + attn
    assert dense.step_flops(TINY, TRAFFIC, 1) == pytest.approx(want)
    assert dense.step_flops(TINY, TRAFFIC, 3) == pytest.approx(3 * want)
    assert dense.tokens(dense.make_batch(1, 0, TINY, TRAFFIC)) == 32
