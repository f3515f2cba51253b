"""Where the Pallas kernels run: compiled on a TPU, interpreted on the CPU.

Every ``pallas_call`` in the repository takes its ``interpret`` flag from
``pallas_interpret`` — the one place that maps the process's JAX backend
to a kernel execution mode.  The CPU runs the same kernel bodies in
interpret mode (tests, ``JAX_PLATFORMS=cpu``); the TPU compiles them
through Mosaic.  Any other backend is an error rather than a silent
interpret-mode run on an accelerator the kernels were never built for.
"""
from __future__ import annotations


def pallas_interpret() -> bool:
    """True on the CPU backend, False on TPU; raises elsewhere."""
    import jax
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default JAX backend here is {platform!r}")
