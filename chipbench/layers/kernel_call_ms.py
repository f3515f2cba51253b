"""kernel_call_ms: host milliseconds per iteration blocked in protocol
kernel dispatches, copies both ways included: the union of the program's
``kernel.*`` spans.  Less ``kernel_ms`` it is the copy and dispatch
cost."""
from chipbench import program_spans


def read(ctx):
    return program_spans.ms_per_iter(ctx, program_spans.kernel_names())
