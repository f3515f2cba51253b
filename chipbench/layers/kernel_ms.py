"""kernel_ms: device milliseconds per iteration in which a protocol
kernel ran: the union of those kernels' module events in the trace."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    t = tr.module_s(list(ctx["kernel_modules"].values()))
    if t <= 0:
        return None
    return t / ctx["iters"] * 1e3
