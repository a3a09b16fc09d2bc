"""The plain reference against the program's own XLA path, and the
control: the reference in float8 fails the cells' limits."""

from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, reference
from families import cnn as family

HERE = pathlib.Path(__file__).resolve().parents[1]
SEEDS = (3, 2**31 + 11)


@pytest.mark.parametrize("seed", [s % 2**31 for s in SEEDS])
def test_reference_matches_the_programs_xla_forward(small_program,
                                                    small_config, seed):
    from repro.models import cnn
    from repro.models.module import init_params

    want = init_params(cnn.param_defs(small_program), jax.random.PRNGKey(seed))
    got = family.init_params(small_config, seed)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]))
    batch = family.make_batch(seed, 0, small_config, {"batch": 4})
    with jax.default_matmul_precision("highest"):
        prog = cnn.forward(small_program, want, jnp.asarray(batch["images"]),
                           use_kernels=False)
        ref = family.logits(got, jnp.asarray(batch["images"]), small_config)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(prog),
                               rtol=1e-5, atol=1e-5 * float(jnp.max(jnp.abs(prog))))


def test_reference_adamw_moves_every_leaf(small_config):
    batches = [family.make_batch(5, i, small_config, {"batch": 8})
               for i in range(3)]
    side = reference.train(small_config, batches, 5)
    assert len(side.losses) == 3 and all(np.isfinite(side.losses))
    # Clipped to global norm 1: the leaves' norms make up a unit vector.
    assert sum(g * g for g in side.grad.values()) == pytest.approx(1.0, rel=1e-4)
    assert all(u > 0 for u in side.update.values())


def test_reference_in_blocks_is_the_whole_batch(small_config, monkeypatch):
    batches = [family.make_batch(7, i, small_config, {"batch": 8})
               for i in range(2)]
    whole = reference.train(small_config, batches, 7)
    monkeypatch.setattr(family, "BLOCK_ROWS", 2)
    assert reference.blocks(8, family.BLOCK_ROWS) == 4
    blocked = reference.train(small_config, batches, 7)
    np.testing.assert_allclose(blocked.losses, whole.losses, rtol=1e-5)
    for k in whole.grad:
        assert blocked.grad[k] == pytest.approx(whole.grad[k], rel=1e-4)
        assert blocked.update[k] == pytest.approx(whole.update[k], rel=1e-4)
    with pytest.raises(ValueError):
        reference.blocks(7, family.BLOCK_ROWS)


@pytest.mark.parametrize("cell", ["vgg11-b256"])
def test_control_in_float8_is_not_correct(small_config, cell):
    limits = json.loads((HERE / "limits" / f"{cell}.json").read_text())["limits"]
    batches = [family.make_batch(9, i, small_config, {"batch": 16})
               for i in range(3)]
    want = reference.train(small_config, batches, 9)
    control = reference.train(small_config, batches, 9, precision="fp8")
    numbers = compare.readings(control, want)
    correct, _ = compare.judge(numbers, limits)
    assert not correct, numbers
    same, _ = compare.judge(compare.readings(want, want), limits)
    assert same
