"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to ``platform.pallas_interpret()``: True on the CPU
backend and False on a TPU — the kernel *code* is identical; interpret mode
executes the same kernel body with pure-JAX semantics for validation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import page_diff as _pd
from repro.kernels import ssd_chunk as _sc
from repro.kernels.platform import pallas_interpret as _default_interpret


@functools.partial(jax.jit, static_argnames=("interpret",))
def diff_encode(curr, twin, *, interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    return _pd.diff_encode(curr, twin, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def diff_apply(dst, mask, vals, *, interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    return _pd.diff_apply(dst, mask, vals, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "window", "softcap", "q_block", "kv_block", "interpret"))
def flash_attention(q, k, v, *, scale=None, causal=True, window=None,
                    softcap=None, q_block=128, kv_block=128, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return _fa.flash_attention(
        q, k, v, scale=scale, causal=causal, window=window, softcap=softcap,
        q_block=q_block, kv_block=kv_block, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk(x, dt, cum, B_, C_, *, interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    return _sc.ssd_chunk(x, dt, cum, B_, C_, interpret=interpret)
