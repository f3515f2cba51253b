"""evict_host_ms: host milliseconds per iteration in the batched eviction
engine outside its kernel dispatches: the program's ``regc.evict`` spans
less the ``kernel.*`` spans inside them."""
from chipbench import program_spans


def read(ctx):
    return program_spans.ms_per_iter(ctx, ("regc.evict",),
                                     program_spans.kernel_names())
