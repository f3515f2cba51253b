"""dispatches: device-program dispatches per iteration, the change in the
runtime's ``jit_dispatches`` counter over the window."""


def read(ctx):
    n = ctx["counters"].get("jit_dispatches")
    if n is None:
        return None
    return n / ctx["iters"]
