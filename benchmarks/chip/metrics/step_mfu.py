"""Model operations per step (forward, input and weight gradients of
every stage, from shapes) times the traced window's steps, over the
window and the chips' bf16 peak."""


def read(r):
    if r.trace is None:
        return None
    flops = r.family.step_flops(r.config, r.traffic, r.batch) * r.trace.steps
    return 100.0 * flops / r.trace.window_s / (
        r.chips * r.peak["bf16_flops_per_s"])
