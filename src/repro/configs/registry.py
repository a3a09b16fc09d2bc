"""Architecture registry: ``--arch <id>`` -> ModelConfig, plus reduced
smoke-config derivation (same family features, tiny dims)."""

from __future__ import annotations

import dataclasses
import importlib

from repro.configs.base import ModelConfig, SHAPES, ShapeConfig

ARCH_IDS = [
    "gemma3-4b",
    "qwen3-1.7b",
    "qwen3-32b",
    "qwen1.5-0.5b",
    "grok-1-314b",
    "qwen3-moe-235b-a22b",
    "chameleon-34b",
    "rwkv6-1.6b",
    "seamless-m4t-medium",
    "zamba2-1.2b",
    "lfm2-8b-a1b",
    "lfm2-8b-a1b-ep4",  # one chip's share of a four-chip expert-parallel host
    "cnn-vgg11",  # the paper's own domain
]

_MODULE_FOR = {a: a.replace("-", "_").replace(".", "p") for a in ARCH_IDS}
_MODULE_FOR["lfm2-8b-a1b-ep4"] = _MODULE_FOR["lfm2-8b-a1b"]

# The reference arch per model family: what ``launch/train.py --family X``
# trains when no --arch is named (always as the reduced smoke config).
FAMILY_DEFAULT_ARCH = {
    "dense": "qwen1.5-0.5b",
    "transformer": "qwen1.5-0.5b",  # the planned wing's family name
    "moe": "qwen3-moe-235b-a22b",
    "lfm2": "lfm2-8b-a1b",
    "rwkv6": "rwkv6-1.6b",
    "zamba2": "zamba2-1.2b",
    "encdec": "seamless-m4t-medium",
    "cnn": "cnn-vgg11",
}


def get_config(arch: str) -> ModelConfig:
    if arch not in _MODULE_FOR:
        raise ValueError(f"unknown arch {arch!r}; have {ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{_MODULE_FOR[arch]}")
    # A module may hold a published configuration and shares derived from
    # it (``CONFIGS``, keyed by arch id).
    return getattr(mod, "CONFIGS", {}).get(arch, mod.CONFIG)


def smoke_config(arch: str) -> ModelConfig:
    """Reduced config of the same family: small layers/width, few experts,
    tiny vocab — runnable on CPU in one forward/train step."""
    cfg = get_config(arch)
    changes: dict = dict(
        n_layers=min(cfg.n_layers, 4 if cfg.family != "zamba2" else 5),
        d_model=128,
        vocab=256,
        d_ff=256,
        max_seq=512,
    )
    if cfg.n_heads:
        changes.update(n_heads=4, n_kv_heads=max(1, 4 * cfg.n_kv_heads // cfg.n_heads),
                       head_dim=32)
    if cfg.n_experts:
        changes.update(n_experts=4, moe_top_k=min(cfg.moe_top_k, 2))
    if cfg.n_enc_layers:
        changes.update(n_enc_layers=2, enc_seq=64)
    if cfg.family == "zamba2":
        changes.update(ssm_state=16, ssm_head_dim=32, shared_attn_every=2)
    if cfg.family == "rwkv6":
        changes.update(ssm_head_dim=32)
    if cfg.family == "cnn":
        changes.update(n_layers=2, d_model=8, d_ff=64, vocab=10)
    if cfg.family == "lfm2":
        n = changes["n_layers"]
        changes.update(layer_types=cfg.layer_types[:n], d_ff_expert=64,
                       n_experts=8, n_dense_layers=min(cfg.n_dense_layers, 1),
                       experts_held=(2, 4) if cfg.experts_held else None)
    if cfg.local_window:
        changes.update(local_window=64, global_every=cfg.global_every)
    return dataclasses.replace(cfg, name=cfg.name + "-smoke", **changes)


def get_shape(name: str) -> ShapeConfig:
    return SHAPES[name]


# long_500k needs sub-quadratic attention over the context. Runnable for
# recurrent/hybrid/local-attention archs; skipped (and documented) for pure
# full-attention archs per the assignment.
LONG_CONTEXT_OK = {"rwkv6-1.6b", "zamba2-1.2b", "gemma3-4b"}
# Decode shapes apply to everything here (all archs have a decoder);
# the CNN family has its own (image) shapes.
CNN_ARCHS = {"cnn-vgg11"}


def cells(arch: str) -> list[str]:
    """The assigned (shape) cells for an arch, with documented skips."""
    if arch in CNN_ARCHS:
        return ["train_4k"]  # batch-256 image training; seq axes n/a
    shapes = ["train_4k", "prefill_32k", "decode_32k"]
    if arch in LONG_CONTEXT_OK:
        shapes.append("long_500k")
    return shapes
