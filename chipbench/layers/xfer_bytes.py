"""xfer_bytes: bytes per iteration that protocol kernel dispatches copy
between host and device, both ways: the change in the runtime's
``jit_h2d_bytes`` and ``jit_d2h_bytes`` counters over the window."""


def read(ctx):
    c = ctx["counters"]
    if "jit_h2d_bytes" not in c:
        return None
    return (c["jit_h2d_bytes"] + c["jit_d2h_bytes"]) / ctx["iters"]
