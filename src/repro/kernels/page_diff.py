"""Pallas TPU kernels for RegC page twin-diffing — the consistency-region
hot spot (DESIGN.md §4.2).

The paper instruments every store with an LLVM pass to track consistency-
region modifications.  On TPU there are no store traps; instead, a span
snapshots *twins* of the pages it may write and, at release, diffs the
current page content against the twin at word granularity:

* ``diff_encode``  — mask = (curr != twin); vals = curr*mask; count per page.
  The protocol layer transmits ``count*4 + W/8`` bytes per dirty page
  (packed values + bitmask) instead of the full page — the fine-grained
  update of the `samhita` protocol.
* ``diff_apply``   — applies (mask, vals) onto the home copy at the memory
  server (or onto a stale cached copy at an acquiring worker).

Pages are (page_words,) fp32 rows; a page of 4 KiB = 1024 words maps onto
(8, 128) VMEM tiles exactly.  Grid tiles PAGES_PER_BLOCK pages per step so
arbitrary page counts stream through VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

PAGES_PER_BLOCK = 8
_LANE = 128        # per-page counts are written lane-broadcast (TPU tiling)


def _diff_encode_kernel(curr_ref, twin_ref, mask_ref, vals_ref, count_ref):
    curr = curr_ref[...]
    twin = twin_ref[...]
    # bitwise comparison (memcmp semantics): float equality would miss
    # denormals under FTZ and mis-handle -0.0 / NaN
    changed = jax.lax.bitcast_convert_type(curr, jnp.int32) != \
        jax.lax.bitcast_convert_type(twin, jnp.int32)
    mask_ref[...] = changed.astype(jnp.int8)
    vals_ref[...] = jnp.where(changed, curr, 0.0)
    count = jnp.sum(changed.astype(jnp.int32), axis=1, keepdims=True)
    count_ref[...] = jnp.broadcast_to(count, count_ref.shape)


def _diff_apply_kernel(dst_ref, mask_ref, vals_ref, out_ref):
    mask = mask_ref[...] != 0
    out_ref[...] = jnp.where(mask, vals_ref[...], dst_ref[...])


def _grid_for(n_pages: int):
    ppb = min(PAGES_PER_BLOCK, n_pages)
    assert n_pages % ppb == 0, (n_pages, ppb)
    return n_pages // ppb, ppb


def diff_encode(curr, twin, *, interpret: bool = False):
    """curr/twin: (n_pages, page_words) f32.
    Returns (mask i8 (n,W), vals f32 (n,W), count i32 (n,))."""
    n, w = curr.shape
    g, ppb = _grid_for(n)
    page_spec = pl.BlockSpec((ppb, w), lambda i: (i, 0))
    mask, vals, count = pl.pallas_call(
        _diff_encode_kernel,
        grid=(g,),
        in_specs=[page_spec, page_spec],
        out_specs=[
            pl.BlockSpec((ppb, w), lambda i: (i, 0)),
            pl.BlockSpec((ppb, w), lambda i: (i, 0)),
            pl.BlockSpec((ppb, _LANE), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, w), jnp.int8),
            jax.ShapeDtypeStruct((n, w), curr.dtype),
            jax.ShapeDtypeStruct((n, _LANE), jnp.int32),
        ],
        interpret=interpret,
    )(curr, twin)
    return mask, vals, count[:, 0]


def diff_apply(dst, mask, vals, *, interpret: bool = False):
    """dst (n,W) f32; mask (n,W) i8; vals (n,W) f32 -> updated dst."""
    n, w = dst.shape
    g, ppb = _grid_for(n)
    spec = pl.BlockSpec((ppb, w), lambda i: (i, 0))
    return pl.pallas_call(
        _diff_apply_kernel,
        grid=(g,),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n, w), dst.dtype),
        interpret=interpret,
    )(dst, mask, vals)
