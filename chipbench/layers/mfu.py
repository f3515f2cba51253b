"""mfu: model FLOPs per training step (``chipbench.flops``) over the
cell's chips times the chip's bf16 peak times the traced run's step
time, in percent."""


def read(ctx):
    peaks = ctx.get("peaks")
    if peaks is None or not ctx.get("trace") or not ctx["trace"].devices():
        return None
    step_s = ctx["window_s"] / ctx["iters"]
    peak = ctx["chips"] * peaks["bf16_flops_per_s"]
    return ctx["step_flops"] / (peak * step_s) * 100.0
