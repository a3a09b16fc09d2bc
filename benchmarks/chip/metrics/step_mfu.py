"""Model operations per step (forward, input and weight gradients of
every stage, from shapes) times the traced window's steps, over the
window and the chips' bf16 peak."""

from chipbench import work


def read(r):
    if r.trace is None:
        return None
    flops = work.step_flops(r.config, r.batch) * r.trace.steps
    return 100.0 * flops / r.trace.window_s / (
        r.chips * r.peak["bf16_flops_per_s"])
