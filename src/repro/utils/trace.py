"""Named host spans on the profiler's clock.

``span(name, **args)`` is ``jax.profiler.TraceAnnotation``: under the
profiler each span is written into the trace beside the device planes, on
the same clock, so a stretch in which the device runs nothing can be put
down to the host work open over it; with the profiler off a span costs
about a microsecond.  Without JAX (the numpy tier) it is a null context.

The runtime opens spans only at batch boundaries -- an API call, a
barrier flush and its two host halves, an eviction batch, a kernel
dispatch -- never per worker, page or run.  ``SPAN_NAMES`` is every name
it emits; a trace reader keeps these.
"""
from __future__ import annotations

import contextlib

# the jitted protocol kernels (repro.kernels.protocol_sweep), each
# dispatch spanned as ``kernel.<name>`` from its first host-to-device copy
# to its last device-to-host copy
KERNELS = ("phase_step", "take_and_cut", "popcount", "take_first_k",
           "kth_set_index", "coverage")
SPAN_NAMES = ("regc.phase", "regc.span", "regc.barrier", "regc.flush",
              "regc.flush.pack", "regc.flush.apply", "regc.evict",
              *(f"kernel.{k}" for k in KERNELS))

try:
    from jax.profiler import TraceAnnotation as span
except ImportError:
    def span(name: str, **args):
        return contextlib.nullcontext()
