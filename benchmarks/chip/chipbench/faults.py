"""Faults planted under the timed path, each a context manager.

The tests see ``correct`` come out false under each; the calibration on
the chip reads how far each moves the numbers compared.  A fault patches
the program for the duration of the ``with`` and nothing else.  The loss
faults wrap ``repro.runtime.train.make_loss_fn``, through which the train
step gets every family's loss, and cut every leaf of the batch on its
leading axis, so that they hold for any family:

* ``state_unchanged`` - the step returns the state it was given;
* ``half_batch``      - the loss is the mean over the first half of the
                        batch, the rest left out;
* ``loss_altered``    - the step reports a loss 1% off the one it computed;
* ``no_exchange``     - on several chips, the gradient is chip 0's own
                        (the cotangent share it gets, from its share of the
                        batch), as if the sum across chips were left out;
                        the loss reported is still the mean over all chips.
"""

from __future__ import annotations

import contextlib
from unittest import mock

import jax

from chipbench.reference import rows


@contextlib.contextmanager
def state_unchanged():
    from repro.runtime import train as tr

    original = tr.make_train_step

    def make(*args, **kwargs):
        step = original(*args, **kwargs)

        def unchanged(state, batch):
            _, metrics = step(state, batch)
            return state, metrics

        return unchanged

    with mock.patch.object(tr, "make_train_step", make):
        yield


@contextlib.contextmanager
def loss_altered():
    from repro.runtime import train as tr

    original = tr.make_train_step

    def make(*args, **kwargs):
        step = original(*args, **kwargs)

        def altered(state, batch):
            state, metrics = step(state, batch)
            return state, dict(metrics, loss=metrics["loss"] * 1.01)

        return altered

    with mock.patch.object(tr, "make_train_step", make):
        yield


@contextlib.contextmanager
def half_batch():
    from repro.runtime import train as tr

    original = tr.make_loss_fn

    def make(*args, **kwargs):
        loss_fn = original(*args, **kwargs)

        def half(params, batch):
            n = rows(batch) // 2
            return loss_fn(params, jax.tree.map(lambda v: v[:n], batch))

        return half

    with mock.patch.object(tr, "make_loss_fn", make):
        yield


@contextlib.contextmanager
def no_exchange():
    from repro.runtime import train as tr

    original = tr.make_loss_fn

    def make(cfg, tcfg, parallel=None):
        loss_fn = original(cfg, tcfg, parallel)
        if parallel is None or parallel.dp_size < 2:
            return loss_fn
        n = parallel.dp_size

        def own(params, batch):
            full = loss_fn(params, batch)
            # Chip 0's share of the batch, through the same sharded path.
            share = rows(batch) // n
            part = loss_fn(params, jax.tree.map(
                lambda v: jax.numpy.concatenate([v[:share]] * n), batch))
            # Value: the mean over all chips; gradient: chip 0's own, from
            # the 1/n of the cotangent it gets.
            return (jax.lax.stop_gradient(full)
                    + (part - jax.lax.stop_gradient(part)) / n)

        return own

    with mock.patch.object(tr, "make_loss_fn", make):
        yield


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "loss_altered": loss_altered, "no_exchange": no_exchange}
