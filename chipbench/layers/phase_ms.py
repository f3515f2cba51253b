"""phase_ms: host milliseconds per iteration inside ``Session.phase``,
``span`` and ``reduce``, from the harness spans of a traced run."""

NAMES = ("session.phase", "session.span", "session.reduce")


def read(ctx):
    spans = ctx.get("spans")
    if not spans:
        return None
    return sum(d for n, d in spans if n in NAMES) / ctx["iters"] * 1e3
