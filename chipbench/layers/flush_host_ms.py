"""flush_host_ms: host milliseconds per iteration in the barrier flush
chain outside its kernel dispatches (packing the batch, applying the
kernel's outputs): the program's ``regc.flush`` spans less the
``kernel.*`` spans inside them."""
from chipbench import program_spans


def read(ctx):
    return program_spans.ms_per_iter(ctx, ("regc.flush",),
                                     program_spans.kernel_names())
