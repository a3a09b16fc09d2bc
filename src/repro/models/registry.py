"""Model-family registry: family name -> module implementing
param_defs / forward / logits / init_cache / layer_meta.

Beyond that core protocol, the launcher and the train runtime dispatch
on *hooks* the family module may provide — no family branching at the
call sites (docs/plan-layer.md spells out the contract):

* ``batch_shard_specs(dp)`` — how the family's batch pytree shards over
  the data axes (:func:`batch_shard_specs`; LM token default);
* ``data_source(cfg, batch, shard, seed=)`` — the family's synthetic
  data source (:func:`make_data_source`; token-stream default);
* ``make_loss_fn(cfg, tcfg, parallel)`` — the family's training loss,
  including its planned-kernel path (``runtime.train.make_loss_fn``
  dispatches; generic forward + chunked-CE default);
* ``plan_training(cfg, batch, *, seq=, loss_chunks=, mesh=, ...)`` — the
  family's full planned schedule set (the launcher's sharded-plan
  re-plan keys off its presence).

The cnn family (the paper's own domain) and the dense ``transformer``
family provide all four; ``transformer`` is also registered under its
own name so ``--family transformer`` addresses it directly."""

from __future__ import annotations

from jax.sharding import PartitionSpec as P

from repro.models import cnn, encdec, lfm2, moe, rwkv6, transformer, zamba2

FAMILIES = {
    "dense": transformer,
    "transformer": transformer,  # the planned wing's first-class name
    "moe": moe,
    "lfm2": lfm2,
    "rwkv6": rwkv6,
    "zamba2": zamba2,
    "encdec": encdec,
    "cnn": cnn,
}


def get_family(name: str):
    try:
        return FAMILIES[name]
    except KeyError:
        raise ValueError(f"unknown model family {name!r}; have {list(FAMILIES)}") from None


def init_cache_slots(cfg, n_slots: int, max_seq: int, dtype):
    """Allocate the serving engine's decode-state slot pool: the family's
    ``init_cache`` with one batch row per slot.  Every registered LM
    family lays its cache leaves out with the batch (= slot) dimension on
    axis 1 — ``[L, B, ...]`` — which is what the engine's slot
    scatter/backfill relies on.  Families without a cache hook (cnn) are
    not servable and raise."""
    fam = FAMILIES.get(cfg.family)
    hook = getattr(fam, "init_cache", None) if fam else None
    if hook is None:
        raise ValueError(
            f"model family {cfg.family!r} has no init_cache hook; it cannot "
            "be served through repro.serve (no decode state to slot)")
    return hook(cfg, n_slots, max_seq, dtype)


def batch_shard_specs(cfg, dp) -> dict:
    """The family's batch sharding specs over the data axes ``dp`` (an
    axis name or tuple).  Families provide a ``batch_shard_specs(dp)``
    hook (models/cnn.py does — images shard their batch dim, matching the
    sharded ConvPlanner's "batch" partition); token families fall back to
    the LM default.  launch/train.py dispatches here instead of branching
    on the family name."""
    fam = FAMILIES.get(cfg.family)
    hook = getattr(fam, "batch_shard_specs", None) if fam else None
    if hook is not None:
        return hook(dp)
    return {k: P(dp, None) for k in ("tokens", "labels")}


def make_data_source(cfg, batch: int, seq: int, shard, seed: int = 0):
    """The family's synthetic data source.  Families provide a
    ``data_source(cfg, batch, shard, seed=)`` hook (models/cnn.py does —
    image/label batches); token families fall back to the LM default
    (``SyntheticSource`` over ``cfg.vocab``, where ``seq`` applies).
    launch/train.py dispatches here instead of branching on the family
    name."""
    fam = FAMILIES.get(cfg.family)
    hook = getattr(fam, "data_source", None) if fam else None
    if hook is not None:
        return hook(cfg, batch, shard, seed=seed)
    from repro.data.pipeline import SyntheticSource

    return SyntheticSource(cfg.vocab, seq, batch, shard, seed=seed)
