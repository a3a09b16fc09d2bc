"""Operations and bytes of the CNN's training step, from layer shapes alone.

Each stage's forward, input gradient and weight gradient is counted as
the work the layer defines, whatever algorithm the program picks for it:
a 3x3 convolution is ``2 * out_pixels * 9 * c_in * c_out`` operations in
every pass, and its least HBM traffic is reading the operands and writing
the result once.  The first stage's input gradient is never computed
(nothing upstream of the images is trained), so it is not counted.
Bias, ReLU, pooling, the loss and the optimizer are not counted as model
operations.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Pass:
    stage: str  # conv0.. / fc1, fc2
    kind: str  # fwd | dgrad | wgrad (conv) ; fwd | dx | dw (fc)
    flops: float
    bytes: float

    def least_s(self, peak: dict) -> float:
        """Least time on a chip: the larger of the compute bound and the
        HBM bound."""
        return max(self.flops / peak["bf16_flops_per_s"],
                   self.bytes / peak["hbm_bytes_per_s"])

    def bound(self, peak: dict) -> str:
        compute = self.flops / peak["bf16_flops_per_s"]
        memory = self.bytes / peak["hbm_bytes_per_s"]
        return "compute" if compute >= memory else "memory"


def stage_shapes(config: dict, batch: int):
    """``(name, x_shape, w_shape, y_shape)`` of every stage; conv shapes
    are NHWC / HWIO and ``y`` is the pooled output."""
    h = config["image_size"]
    c_in, c_out = config["in_channels"], config["first_channels"]
    f, pool = config["filter"], config["pool"]
    for i in range(config["conv_layers"]):
        yield (f"conv{i}", (batch, h, h, c_in), (f, f, c_in, c_out),
               (batch, h // pool, h // pool, c_out))
        h //= pool
        c_in, c_out = c_out, c_out * 2
    flat = h * h * c_in
    yield "fc1", (batch, flat), (flat, config["fc_hidden"]), (
        batch, config["fc_hidden"])
    yield "fc2", (batch, config["fc_hidden"]), (
        config["fc_hidden"], config["classes"]), (batch, config["classes"])


def _n(shape) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def passes(config: dict, batch: int, act_bytes: int = 2) -> list[Pass]:
    """Every pass of one training step on ``batch`` images, with operands
    and results of ``act_bytes`` bytes per element."""
    out = []
    for name, x, w, y in stage_shapes(config, batch):
        if name.startswith("conv"):
            b, h, wd, _ = x
            flops = 2.0 * b * h * wd * _n(w)  # stride 1, same padding
            kinds = (("fwd", (x, w, y)), ("dgrad", (y, w, x)),
                     ("wgrad", (x, y, w)))
        else:
            flops = 2.0 * x[0] * _n(w)
            kinds = (("fwd", (x, w, y)), ("dx", (y, w, x)), ("dw", (x, y, w)))
        for kind, operands in kinds:
            if name == "conv0" and kind == "dgrad":
                continue
            nbytes = act_bytes * sum(_n(s) for s in operands)
            out.append(Pass(name, kind, flops, float(nbytes)))
    return out


def step_flops(config: dict, batch: int) -> float:
    """Model operations of one training step (forward, input and weight
    gradients, no recompute)."""
    return sum(p.flops for p in passes(config, batch))


def param_count(config: dict) -> int:
    """Weights and biases of every stage."""
    return sum(_n(w) + w[-1] for _, _, w, _ in stage_shapes(config, 1))


def least_s(config: dict, batch: int, peak: dict, stages) -> float:
    """Least time of the named stages' passes on one chip."""
    return sum(p.least_s(peak) for p in passes(config, batch)
               if p.stage in stages)


def bounds(config: dict, batch: int, peak: dict, stages) -> dict:
    """Which bound sets each pass's least time: ``{"conv0.fwd": ...}``."""
    return {f"{p.stage}.{p.kind}": p.bound(peak)
            for p in passes(config, batch) if p.stage in stages}
