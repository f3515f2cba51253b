"""barrier_ms: host milliseconds per iteration inside ``rt.barrier()``
(the flush chain and its device kernels), from the harness spans."""


def read(ctx):
    spans = ctx.get("spans")
    if not spans:
        return None
    return sum(d for n, d in spans if n == "session.barrier") \
        / ctx["iters"] * 1e3
