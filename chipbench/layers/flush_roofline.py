"""flush_roofline: the barrier flush kernel's share of its memory
roofline.  The least bytes the flush must move per iteration
(``chipbench.flops.flush_min_bytes`` of the declared write sets) over the
chip's HBM bandwidth, over the flush kernel's device time."""


def read(ctx):
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    if tr is None or peaks is None:
        return None
    t = tr.module_s([ctx["kernel_modules"][ctx["flush_kernel"]]])
    if t <= 0:
        return None
    least_s = ctx["flush_min_bytes"] * ctx["iters"] / peaks["hbm_bytes_per_s"]
    return least_s / t * 100.0
