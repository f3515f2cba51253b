"""Small utilities: pytree helpers (``repro.utils.tree``, JAX) and the
runtime's named spans (``repro.utils.trace``, usable without JAX)."""
