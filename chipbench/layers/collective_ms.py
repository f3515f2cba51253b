"""collective_ms: device-0 milliseconds per training step in collective
operations (all-reduce and its kin), from the device trace."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr.devices():
        return None
    t = tr.op_s(list(ctx["collectives"]), device=tr.devices()[0])
    if t <= 0:
        return None
    return t / ctx["iters"] * 1e3
