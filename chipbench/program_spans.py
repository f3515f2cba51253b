"""The program's own spans in a traced window, and their self times.

The runtime opens spans at its layer boundaries (``repro.utils.trace``):
``regc.*`` around its batched API calls, the barrier flush and its two
host halves, and the eviction engine; ``kernel.<name>`` around each
jitted protocol-kernel dispatch, from the first host-to-device copy to
the last copy back.  The harness reduces the trace with its own span
names only, so the readers of the program's spans read the window's
``.xplane.pb`` again with the program's names, once per run (the result
is kept in the readers' context), and add those spans to the harness's
trace: its idle-gap breakdown, which puts each gap down to the innermost
span open at the gap's middle, then names them.

A checkout whose program emits no spans (no ``repro.utils.trace``) gives
None, and so does every reader built on it.
"""
from __future__ import annotations

import glob
import os
from typing import Iterable, Optional, Sequence, Tuple

from chipbench import trace

KERNEL_PREFIX = "kernel."


def names() -> Optional[Tuple[str, ...]]:
    """Every span name the program emits, or None where it emits none."""
    try:
        from repro.utils.trace import SPAN_NAMES
    except ImportError:
        return None
    return tuple(SPAN_NAMES)


def kernel_names() -> Tuple[str, ...]:
    return tuple(n for n in names() or () if n.startswith(KERNEL_PREFIX))


def load(ctx) -> Optional[trace.Trace]:
    """The traced window with the program's spans (no device events), or
    None where the program emits none."""
    if "program_trace" not in ctx:
        ctx["program_trace"] = _load(ctx)
    return ctx["program_trace"]


def _load(ctx) -> Optional[trace.Trace]:
    from chipbench import harness
    tr, want = ctx.get("trace"), names()
    if tr is None or want is None:
        return None
    # the window's own file: the newest whose window is the harness's
    paths = glob.glob(os.path.join(str(harness.TRACE_DIR), "**",
                                   "*.xplane.pb"), recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        try:
            got = trace.load(path, want)
        except ValueError:          # no window span: not a harness trace
            continue
        if got.window == tr.window:
            own = [sp for sp in got.spans if sp.name != trace.WINDOW_SPAN]
            tr.spans.extend(own)
            return trace.Trace(tr.window, own, {}, {})
    return None


def subtract(a: Sequence[trace.Interval], b: Sequence[trace.Interval]
             ) -> list:
    """The parts of the merged intervals ``a`` that the merged intervals
    ``b`` do not cover."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        t, k = s, j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > t:
                out.append((t, b[k][0]))
            t = max(t, b[k][1])
            k += 1
        if t < e:
            out.append((t, e))
    return out


def self_s(tr: trace.Trace, outer: Iterable[str], inner: Iterable[str] = ()
           ) -> Optional[float]:
    """Seconds of the window inside a span named in ``outer`` and inside
    none named in ``inner``: the union of the outer spans, clipped to the
    window, less the union of the inner ones.  None where no outer span
    reaches into the window."""
    outer, inner = set(outer), set(inner)
    o = trace.clip(trace.union((sp.start, sp.end) for sp in tr.spans
                               if sp.name in outer), *tr.window)
    if not o:
        return None
    i = trace.union((sp.start, sp.end) for sp in tr.spans
                    if sp.name in inner)
    return trace.length(subtract(o, i))


def ms_per_iter(ctx, outer: Iterable[str], inner: Iterable[str] = ()
                ) -> Optional[float]:
    """``self_s`` of the program's spans, in milliseconds per iteration."""
    tr = load(ctx)
    s = None if tr is None else self_s(tr, outer, inner)
    return None if s is None else s / ctx["iters"] * 1e3
