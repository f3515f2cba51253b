"""Bitmask protocol-sweep kernels for the RegC sharing directory.

The directory's boolean page-state planes (valid/dirty/wprot, one row per
worker — see ``core.directory.RegionDirectory``) pack 32 pages per lane as
little-endian ``uint32`` bitmasks: bit ``j`` of word ``k`` in row ``w`` is
directory column ``32*k + j`` of worker ``w``.  At 256 workers x millions
of pages that turns the whole-plane reductions the barrier flush and the
batched eviction engine need into dense integer kernels that run on the
accelerator:

* ``popcount_rows``  — per-worker dirty-page counts (the barrier-flush
  writeback charge and the eviction engine's dirty-victim counts), a SWAR
  popcount + row reduction over the packed plane;
* ``coverage_multi`` — the shared-interval sweep's coverage cumsum over the
  2W sorted window bounds (pages under >= 2 worker windows are the only
  candidates for sharer invalidation);
* ``take_first_k``   — per-row rank-select (each row's first k set bits in
  little-endian column order): the batched eviction engine's segment-LRU
  victim selection over packed run-liveness masks;
* ``kth_set_index``  — per-row rank query (column of the k-th set bit):
  the mid-op refetch replay engine's scan cut — how far a victim run's
  live mask must be consumed to satisfy an eviction demand.

All tiers are integer-exact, so protocol traffic is identical on every
backend (``tests/test_directory.py`` oracles the packed kernels against the
boolean planes).  Three execution tiers share the kernel algebra:

* ``numpy``      — boolean-plane / SWAR reductions (the reference tier);
* ``pallas``     — per-op ``pallas_call`` kernels, compiled on TPU and
  interpret-mode on CPU (the validation twin);
* ``pallas-jit`` — the same kernels as jnp programs under ``jax.jit``
  (XLA-fused, so the SWAR multi-pass runs without numpy's temporaries),
  plus the FUSED chains: ``phase_step`` runs the whole barrier-flush
  reduction set (popcount + shared-coverage sweep + sharer-invalidation
  candidate mask) for every dirty region as ONE device dispatch with
  ``lax.scan`` carrying the per-region loop, and ``take_and_cut`` fuses
  the eviction rank-select + rank-query into one dispatch.  Packed
  planes stay device-resident across the chained ops inside a dispatch
  instead of round-tripping per kernel (see DIRECTORY.md
  "Compiled-phase contract").

When jax itself is unavailable (or ``REPRO_FORCE_NUMPY=1`` is set) the
module degrades to the numpy paths; availability is probed ONCE and
cached (``available_backends``), not re-checked per call.
"""
from __future__ import annotations

import functools
import os
import warnings
from typing import Optional, Tuple

import numpy as np

from repro.utils.trace import span

try:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.platform import pallas_interpret
    HAVE_PALLAS = True
except Exception:                                  # jax absent / broken
    HAVE_PALLAS = False

ROWS_PER_BLOCK = 8
_LANE = 128
_FORCE_ENV = "REPRO_FORCE_NUMPY"

# one cached module-level availability probe (the env override and the
# jax import are both process-stable, so per-call re-checking was pure
# overhead); tests reset it via _reset_backend_probe after monkeypatching
# the environment
_AVAILABLE: Optional[Tuple[str, ...]] = None
_WARNED: set = set()


def available_backends() -> Tuple[str, ...]:
    """The backends this process can actually run, probed once and
    cached: numpy always; 'pallas'/'pallas-jit' when jax imported and
    ``REPRO_FORCE_NUMPY=1`` is not set (the debugging override that
    forces every kernel onto the numpy tier)."""
    global _AVAILABLE
    if _AVAILABLE is None:
        if not HAVE_PALLAS or os.environ.get(_FORCE_ENV) == "1":
            _AVAILABLE = ("numpy",)
        else:
            _AVAILABLE = ("numpy", "pallas", "pallas-jit")
    return _AVAILABLE


def _reset_backend_probe():
    """Drop the cached probe (tests that monkeypatch REPRO_FORCE_NUMPY)."""
    global _AVAILABLE
    _AVAILABLE = None
    _WARNED.clear()


def resolve_backend(backend: str) -> str:
    """Map a requested backend to an available one (cached probe; warns
    once per unavailable backend, not per call)."""
    from repro.core.config import BACKENDS, check_choice
    check_choice("backend", backend, BACKENDS)
    if backend not in available_backends():
        if backend not in _WARNED:
            _WARNED.add(backend)
            why = (f"{_FORCE_ENV}=1" if os.environ.get(_FORCE_ENV) == "1"
                   else "jax/pallas unavailable")
            warnings.warn(f"protocol_sweep: {why}, backend {backend!r} "
                          "falling back to numpy", RuntimeWarning,
                          stacklevel=2)
        return "numpy"
    return backend


# jit-dispatch accounting: every fused/jitted kernel call notes itself in
# the caller's stats dict (the runtime's ``jit_dispatches`` counter — CI
# fails when a bench leg silently falls back to numpy and the counter
# stays 0) and under ``jit_<kernel>``.  ``jit_cache_misses`` counts
# first-seen (kernel, shape) keys, mirroring jax's process-wide
# compilation cache.  ``jit_h2d_bytes``/``jit_d2h_bytes`` count the bytes
# of the arrays a dispatch copies to the device and back: copies, not
# their time.  Per-op 'pallas' calls count under ``pallas_<kernel>``
# only, outside the gated ``jit_dispatches``.
_JIT_SEEN: set = set()


def _note_dispatch(stats: Optional[dict], key, h2d_bytes: int,
                   d2h_bytes: int):
    if stats is None:
        return
    stats["jit_dispatches"] = stats.get("jit_dispatches", 0) + 1
    name = f"jit_{key[0]}"
    stats[name] = stats.get(name, 0) + 1
    stats["jit_h2d_bytes"] = stats.get("jit_h2d_bytes", 0) + h2d_bytes
    stats["jit_d2h_bytes"] = stats.get("jit_d2h_bytes", 0) + d2h_bytes
    if key not in _JIT_SEEN:
        _JIT_SEEN.add(key)
        stats["jit_cache_misses"] = stats.get("jit_cache_misses", 0) + 1


def _dispatch(kernel: str, fn, args: tuple, stats: Optional[dict]
              ) -> tuple:
    """One jitted dispatch of ``kernel``: the host arrays ``args`` copied
    to the device, ``fn`` run on them and every output copied back, all
    inside the span ``kernel.<kernel>``.  Notes the dispatch and the bytes
    copied each way in ``stats``; returns the outputs as numpy arrays."""
    shape = args[0].shape
    with span(f"kernel.{kernel}", shape=shape):
        dev = [jnp.asarray(a) for a in args]
        out = fn(*dev)
        host = tuple(np.asarray(o) for o in
                     (out if isinstance(out, tuple) else (out,)))
    _note_dispatch(stats, (kernel, shape), sum(a.nbytes for a in dev),
                   sum(h.nbytes for h in host))
    return host


def _note_pallas(stats: Optional[dict], kernel: str):
    if stats is not None:
        name = f"pallas_{kernel}"
        stats[name] = stats.get(name, 0) + 1


# ---------------------------------------------------------------------------
# bitmask packing (host side, numpy)
# ---------------------------------------------------------------------------


def pack_mask_rows(plane: np.ndarray) -> np.ndarray:
    """(W, C) bool -> (W, ceil(C/32)) uint32, little-endian bit order:
    bit j of word k is column 32*k + j."""
    W, C = plane.shape
    n_words = -(-C // 32) if C else 0
    pad = n_words * 32 - C
    if pad:
        plane = np.pad(plane, ((0, 0), (0, pad)))
    if n_words == 0:
        return np.zeros((W, 0), np.uint32)
    by = np.packbits(plane.reshape(W, n_words * 4, 8), axis=-1,
                     bitorder="little")            # (W, n_words*4, 1) uint8
    return np.ascontiguousarray(by.reshape(W, n_words, 4)).view(
        np.uint32).reshape(W, n_words)


def unpack_mask_rows(bits: np.ndarray, n_cols: int) -> np.ndarray:
    """Inverse of ``pack_mask_rows`` (oracle/tests)."""
    W, n_words = bits.shape
    by = np.ascontiguousarray(bits).view(np.uint8).reshape(W, n_words * 4, 1)
    cols = np.unpackbits(by, axis=-1, bitorder="little").reshape(W, -1)
    return cols[:, :n_cols].astype(bool)


# ---------------------------------------------------------------------------
# row popcount: numpy SWAR / Pallas kernel (same bit-twiddle)
# ---------------------------------------------------------------------------


def _popcount_words(v: np.ndarray) -> np.ndarray:
    """Per-word SWAR popcount, (R, n_words) uint32 -> uint32 counts."""
    v = v.astype(np.uint32, copy=True)
    v -= (v >> 1) & np.uint32(0x55555555)
    v = (v & np.uint32(0x33333333)) + ((v >> 2) & np.uint32(0x33333333))
    v = (v + (v >> 4)) & np.uint32(0x0F0F0F0F)
    return (v * np.uint32(0x01010101)) >> 24


def _popcount_rows_np(bits: np.ndarray) -> np.ndarray:
    return _popcount_words(bits).sum(axis=1, dtype=np.int64)


def _take_first_k_np(bits: np.ndarray, k: np.ndarray) -> np.ndarray:
    """Per-row rank-select: keep only the first (lowest-column) k[i] set
    bits of row i.  Word-level prefix popcounts bound how many bits each
    word still needs; within a word, bit j survives iff its rank among the
    word's set bits is below that need — 32 static shift steps over the
    packed plane (the eviction plane's segment-LRU 'take' mask)."""
    pc = _popcount_words(bits)
    excl = np.cumsum(pc, axis=1, dtype=np.int64) - pc       # bits before word
    need = np.clip(k[:, None] - excl, 0, 32).astype(np.uint32)
    out = np.zeros_like(bits, np.uint32)
    run = np.zeros_like(bits, np.uint32)                    # rank within word
    for j in range(32):
        bit = (bits >> np.uint32(j)) & np.uint32(1)
        sel = (bit != 0) & (run < need)
        out |= sel.astype(np.uint32) << np.uint32(j)
        run += bit
    return out


def _kth_set_index_np(bits: np.ndarray, k: np.ndarray) -> np.ndarray:
    """Per-row rank query: little-endian column index of the k[i]-th
    (1-based) set bit of row i, or -1 when the row has fewer than k[i]
    set bits (or k[i] <= 0).  Word-level prefix popcounts locate the
    word; 32 static shift steps locate the bit within it."""
    R, n_words = bits.shape
    pc = _popcount_words(bits).astype(np.int64)
    cum = np.cumsum(pc, axis=1)
    total = cum[:, -1]
    kk = np.asarray(k, np.int64)
    # first word whose cumulative popcount reaches k (k > total handled
    # by the final mask; argmax of an all-False row is 0, also masked)
    wi = np.argmax(cum >= kk[:, None], axis=1)
    rows = np.arange(R)
    need = (kk - (cum[rows, wi] - pc[rows, wi])).astype(np.int64)
    word = bits[rows, wi]
    run = np.zeros(R, np.int64)
    idx = np.full(R, -1, np.int64)
    for j in range(32):
        bit = ((word >> np.uint32(j)) & np.uint32(1)).astype(np.int64)
        run += bit
        hit = (idx < 0) & (bit == 1) & (run == need)
        idx = np.where(hit, 32 * wi + j, idx)
    return np.where((kk >= 1) & (total >= kk), idx, -1)


if HAVE_PALLAS:

    def _swar_pop_j(v):
        """Per-word SWAR popcount of a traced uint32 array -> int32."""
        v = v - ((v >> 1) & jnp.uint32(0x55555555))
        v = ((v & jnp.uint32(0x33333333))
             + ((v >> 2) & jnp.uint32(0x33333333)))
        v = (v + (v >> 4)) & jnp.uint32(0x0F0F0F0F)
        return ((v * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)

    def _lane_cumsum(x):
        """Exact inclusive int32 prefix sum along axis 1 inside a kernel:
        log-step shift-and-add over lane rotations (the TPU lowering has
        no ``cumsum``).  ``pltpu.roll`` follows ``jnp.roll``, so lane i
        adds lane i - s; lanes below s add nothing."""
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        s = 1
        while s < x.shape[1]:
            x = x + jnp.where(lane >= s, pltpu.roll(x, s, 1), 0)
            s *= 2
        return x

    def _popcount_kernel(bits_ref, out_ref):
        n = jnp.sum(_swar_pop_j(bits_ref[...]), axis=1, keepdims=True)
        out_ref[...] = jnp.broadcast_to(n, out_ref.shape)

    def _take_first_k_kernel(bits_ref, k_ref, out_ref):
        v = bits_ref[...]
        pc = _swar_pop_j(v)
        need = jnp.clip(k_ref[...] - (_lane_cumsum(pc) - pc), 0, 32)
        out = jnp.zeros_like(v)
        run = jnp.zeros_like(pc)
        for j in range(32):                      # static rank-select steps
            bit = ((v >> j) & jnp.uint32(1)).astype(jnp.int32)
            sel = (bit != 0) & (run < need)
            out = out | (sel.astype(jnp.uint32) << j)
            run = run + bit
        out_ref[...] = out

    def _kth_set_index_kernel(bits_ref, k_ref, out_ref):
        v = bits_ref[...]
        pc = _swar_pop_j(v)
        k = k_ref[...]                                     # (rows, 1)
        # cumulative popcounts never decrease, so the words that end
        # before the k-th set bit form a prefix: its length is the word
        # index and its popcount the bits before that word
        before = _lane_cumsum(pc) < k
        wi = jnp.sum(before.astype(jnp.int32), axis=1, keepdims=True)
        excl = jnp.sum(jnp.where(before, pc, 0), axis=1, keepdims=True)
        total = jnp.sum(pc, axis=1, keepdims=True)
        lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
        word = jnp.sum(jnp.where(lane == wi, pltpu.bitcast(v, jnp.int32), 0),
                       axis=1, keepdims=True)        # one nonzero term
        need = k - excl
        run = jnp.zeros_like(need)
        idx = jnp.full_like(need, -1)
        for j in range(32):                  # static rank steps
            bit = (word >> j) & 1
            run = run + bit
            hit = (idx < 0) & (bit == 1) & (run == need)
            idx = jnp.where(hit, 32 * wi + j, idx)
        ok = (k >= 1) & (total >= k)
        out_ref[...] = jnp.broadcast_to(jnp.where(ok, idx, -1), out_ref.shape)

    def _coverage_kernel(delta_ref, multi_ref):
        multi_ref[...] = (_lane_cumsum(delta_ref[...]) >= 2).astype(jnp.int32)

    # Each ``*_call`` builds the jitted pallas_call for padded shapes:
    # rows in blocks of ROWS_PER_BLOCK, columns padded to whole lanes,
    # per-row scalars written lane-broadcast to a (rows, _LANE) block
    # (the host keeps lane 0).  Built once per shape, so repeat calls hit
    # jax's in-memory compile cache.  ``tests/test_tpu_compile.py``
    # compiles these for a described TPU.

    @functools.lru_cache(maxsize=64)
    def _popcount_call(Rp: int, Cp: int, interpret: bool):
        return jax.jit(pl.pallas_call(
            _popcount_kernel,
            grid=(Rp // ROWS_PER_BLOCK,),
            in_specs=[pl.BlockSpec((ROWS_PER_BLOCK, Cp), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((ROWS_PER_BLOCK, _LANE), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((Rp, _LANE), jnp.int32),
            interpret=interpret))

    @functools.lru_cache(maxsize=64)
    def _take_first_k_call(Rp: int, Cp: int, interpret: bool):
        return jax.jit(pl.pallas_call(
            _take_first_k_kernel,
            grid=(Rp // ROWS_PER_BLOCK,),
            in_specs=[pl.BlockSpec((ROWS_PER_BLOCK, Cp), lambda i: (i, 0)),
                      pl.BlockSpec((ROWS_PER_BLOCK, 1), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((ROWS_PER_BLOCK, Cp), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((Rp, Cp), jnp.uint32),
            interpret=interpret))

    @functools.lru_cache(maxsize=64)
    def _kth_set_index_call(Rp: int, Cp: int, interpret: bool):
        return jax.jit(pl.pallas_call(
            _kth_set_index_kernel,
            grid=(Rp // ROWS_PER_BLOCK,),
            in_specs=[pl.BlockSpec((ROWS_PER_BLOCK, Cp), lambda i: (i, 0)),
                      pl.BlockSpec((ROWS_PER_BLOCK, 1), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((ROWS_PER_BLOCK, _LANE), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((Rp, _LANE), jnp.int32),
            interpret=interpret))

    @functools.lru_cache(maxsize=64)
    def _coverage_call(npad: int, interpret: bool):
        return jax.jit(pl.pallas_call(
            _coverage_kernel,
            in_specs=[pl.BlockSpec((1, npad), lambda: (0, 0))],
            out_specs=pl.BlockSpec((1, npad), lambda: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((1, npad), jnp.int32),
            interpret=interpret))

    def _pad_rows(bits: np.ndarray, k=None):
        """Zero-pad (R, n_words) to whole row blocks and lanes (zero words
        add no set bits); ``k`` pads to an int32 (Rp, 1) column."""
        R, n_words = bits.shape
        Rp = -(-R // ROWS_PER_BLOCK) * ROWS_PER_BLOCK
        Cp = max(-(-n_words // _LANE) * _LANE, _LANE)
        padded = np.zeros((Rp, Cp), np.uint32)
        padded[:R, :n_words] = bits
        if k is None:
            return padded
        kp = np.zeros((Rp, 1), np.int32)
        kp[:R, 0] = _k32(k)
        return padded, kp

    def _popcount_rows_pallas(bits: np.ndarray) -> np.ndarray:
        padded = _pad_rows(bits)
        out = _popcount_call(*padded.shape, pallas_interpret())(
            jnp.asarray(padded))
        return np.asarray(out[:bits.shape[0], 0]).astype(np.int64)

    def _take_first_k_pallas(bits: np.ndarray, k: np.ndarray) -> np.ndarray:
        padded, kp = _pad_rows(bits, k)
        out = _take_first_k_call(*padded.shape, pallas_interpret())(
            jnp.asarray(padded), jnp.asarray(kp))
        return np.asarray(out[:bits.shape[0], :bits.shape[1]])

    def _kth_set_index_pallas(bits: np.ndarray, k: np.ndarray) -> np.ndarray:
        padded, kp = _pad_rows(bits, k)
        out = _kth_set_index_call(*padded.shape, pallas_interpret())(
            jnp.asarray(padded), jnp.asarray(kp))
        return np.asarray(out[:bits.shape[0], 0]).astype(np.int64)

    def _coverage_multi_pallas(delta: np.ndarray) -> np.ndarray:
        n = delta.size
        npad = max(-(-n // _LANE) * _LANE, _LANE)
        padded = np.zeros((1, npad), np.int32)
        padded[0, :n] = delta
        out = _coverage_call(npad, pallas_interpret())(jnp.asarray(padded))
        return np.asarray(out[0, :n]).astype(bool)

    # -----------------------------------------------------------------
    # 'pallas-jit' tier: the same kernel algebra as jnp programs under
    # jax.jit — XLA fuses the SWAR passes into one traversal, and the
    # fused chains run several protocol ops per dispatch with the packed
    # planes staying device-resident in between.
    # -----------------------------------------------------------------

    def _rank_select_j(bits, k):
        """Packed per-row rank-select (first k[i] set bits), traced: the
        word-prefix popcount bound + 32 bit steps via fori_loop."""
        pc = _swar_pop_j(bits)
        excl = jnp.cumsum(pc, axis=1) - pc
        need = jnp.clip(k[:, None] - excl, 0, 32).astype(jnp.uint32)

        def step(j, carry):
            out, run = carry
            bit = (bits >> j) & jnp.uint32(1)
            sel = (bit != 0) & (run < need)
            out = out | (sel.astype(jnp.uint32) << j)
            return out, run + bit

        out, _ = jax.lax.fori_loop(
            0, 32, step, (jnp.zeros_like(bits), jnp.zeros_like(bits)))
        return out

    def _rank_query_j(bits, k):
        """Packed per-row rank query (column of the k[i]-th set bit, -1
        out of range), traced."""
        pc = _swar_pop_j(bits)
        cum = jnp.cumsum(pc, axis=1)
        total = cum[:, -1]
        wi = jnp.argmax(cum >= k[:, None], axis=1)
        rows = jnp.arange(bits.shape[0])
        need = k - (cum[rows, wi] - pc[rows, wi])
        word = bits[rows, wi]

        def step(j, carry):
            run, idx = carry
            bit = ((word >> j) & jnp.uint32(1)).astype(jnp.int32)
            run = run + bit
            hit = (idx < 0) & (bit == 1) & (run == need)
            return run, jnp.where(hit, 32 * wi.astype(jnp.int32) + j, idx)

        _, idx = jax.lax.fori_loop(
            0, 32, step,
            (jnp.zeros_like(need), jnp.full_like(need, -1)))
        return jnp.where((k >= 1) & (total >= k), idx, -1)

    @jax.jit
    def _popcount_rows_jit(bits):
        return jnp.sum(_swar_pop_j(bits), axis=1)

    @jax.jit
    def _take_first_k_jit(bits, k):
        return _rank_select_j(bits, k)

    @jax.jit
    def _kth_set_index_jit(bits, k):
        return _rank_query_j(bits, k)

    @jax.jit
    def _take_and_cut_jit(bits, k):
        # fused eviction rank-select + rank-query: ONE dispatch yields
        # both the take mask and the scan cut, the packed run staying
        # device-resident between the two ops
        return _rank_select_j(bits, k), _rank_query_j(bits, k)

    @jax.jit
    def _coverage_multi_jit(delta):
        return jnp.cumsum(delta) >= 2

    def _flip_points_j(sb, se):
        """Sorted pages where "covered by >= 2 windows" changes, padded
        with INT32_MAX: page p is multi-covered iff an odd number of flip
        points are <= p.  The 2W bound points are sorted and each is
        judged after every bound at that point, so a window ending where
        another starts opens no gap and no overlap; pads stab nothing.
        Gather-free: the coverage at each point counts the bounds <= it."""
        pts = jnp.sort(jnp.concatenate([sb, se]))
        cov = (jnp.sum(sb[None, :] <= pts[:, None], axis=1)
               - jnp.sum(se[None, :] <= pts[:, None], axis=1))
        multi = cov >= 2
        prev = jnp.concatenate([jnp.zeros((1,), bool), multi[:-1]])
        pad = jnp.iinfo(jnp.int32).max
        return jnp.sort(jnp.where(multi != prev, pts, pad))

    @jax.jit
    def _phase_step_jit(bits, base, rowmask, sbases, sends):
        """Fused barrier-flush chain over R stacked regions — ONE device
        dispatch per protocol phase, ``lax.scan`` carrying the per-region
        loop.  Per region: per-row dirty popcount (the writeback charge),
        the multi-coverage word mask (a page is a sharer-invalidation
        candidate iff covered by >= 2 live worker windows), and the
        shared-dirty candidate mask (dirty ∧ multi-covered ∧ active row)
        packed back to uint32.  The packed planes never leave the device
        between the chained ops.

        The multi-covered set is at most W intervals, so it is taken as
        its <= 2W sorted flip points (``_flip_points_j``) and the mask is
        built 32 pages at a time, never per page: a row starts all ones
        if an odd number of flips lie at or before its first page, and
        each flip f inside the row's range XORs ``~0 << (f - p0)`` into
        the word starting at page p0 that holds it and all ones into
        every later word.  The walk takes each active row's flips in
        order, so it runs as many passes as the most flips any active
        row's range holds (3 in a Jacobi halo geometry, 0 where no
        windows overlap), each a gather-free pass over the (W, nw) words.

        bits (R, W, nw) uint32; base (R, W) int32 row window offsets
        (-1 rows have all-zero bits); rowmask (R, W) bool flush mask;
        sbases/sends (R, W) int32 sorted live window bounds padded with
        INT32_MAX (a pad entry stabs nothing); base + 32 * nw must stay
        below INT32_MAX.  Returns (counts (R, W) int32, shared (R, W, nw)
        uint32).
        """
        W, nw = bits.shape[1:]
        off = jnp.arange(nw, dtype=jnp.int32) * 32       # word start - base
        ones = jnp.uint32(0xFFFFFFFF)

        def step(_, xs):
            b, base_r, rowm, sb, se = xs
            counts = jnp.sum(_swar_pop_j(b), axis=1)         # (W,)
            active = rowm & (counts > 0)
            flips = _flip_points_j(sb, se)                   # (2W,)
            end = base_r + 32 * nw                           # past the row
            lo = jnp.sum(flips[None, :] <= base_r[:, None], axis=1)
            hi = jnp.sum(flips[None, :] < end[:, None], axis=1)
            start = jnp.where(lo % 2 == 1, ones, jnp.uint32(0))
            mask = jnp.broadcast_to(start[:, None], b.shape)

            def flip(i, mask):
                k = lo + i                                    # (W,)
                f = jnp.where(k < hi, flips[jnp.minimum(k, 2 * W - 1)],
                              end)
                d = (f - base_r)[:, None] - off[None, :]      # (W, nw)
                part = ones << jnp.clip(d, 0, 31).astype(jnp.uint32)
                return mask ^ jnp.where(d < 32, part, jnp.uint32(0))

            passes = jnp.max(jnp.where(active, hi - lo, 0))
            mask = jax.lax.fori_loop(0, passes, flip, mask)
            shared = jnp.where(active[:, None], b & mask, jnp.uint32(0))
            return None, (counts, shared)

        _, (counts, shared) = jax.lax.scan(
            step, None, (bits, base, rowmask, sbases, sends))
        return counts, shared


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def _k32(k) -> np.ndarray:
    return np.minimum(np.asarray(k, np.int64),
                      np.iinfo(np.int32).max).astype(np.int32)


def popcount_rows(bits: np.ndarray, *, backend: str = "numpy",
                  stats: Optional[dict] = None) -> np.ndarray:
    """(W, n_words) uint32 -> (W,) int64 per-row set-bit counts."""
    if bits.shape[1] == 0:
        return np.zeros(bits.shape[0], np.int64)
    b = resolve_backend(backend)
    if b == "pallas-jit":
        (out,) = _dispatch("popcount", _popcount_rows_jit, (bits,), stats)
        return out.astype(np.int64)
    if b == "pallas":
        _note_pallas(stats, "popcount")
        return _popcount_rows_pallas(bits)
    return _popcount_rows_np(bits)


def take_first_k(bits: np.ndarray, k: np.ndarray, *,
                 backend: str = "numpy",
                 stats: Optional[dict] = None) -> np.ndarray:
    """(R, n_words) uint32 + (R,) counts -> packed mask of each row's first
    k[i] set bits in little-endian column order (the batched eviction
    engine's segment-LRU victim selection)."""
    if bits.shape[1] == 0:
        return np.zeros_like(bits, np.uint32)
    b = resolve_backend(backend)
    if b == "pallas-jit":
        (out,) = _dispatch("take_first_k", _take_first_k_jit,
                           (bits, _k32(k)), stats)
        return out
    if b == "pallas":
        _note_pallas(stats, "take_first_k")
        return _take_first_k_pallas(bits, k)
    return _take_first_k_np(bits, np.asarray(k, np.int64))


def kth_set_index(bits: np.ndarray, k: np.ndarray, *,
                  backend: str = "numpy",
                  stats: Optional[dict] = None) -> np.ndarray:
    """(R, n_words) uint32 + (R,) ranks -> (R,) little-endian column index
    of each row's k[i]-th (1-based) set bit, -1 when out of range (the
    refetch replay engine's victim-scan cut)."""
    if bits.shape[1] == 0:
        return np.full(bits.shape[0], -1, np.int64)
    b = resolve_backend(backend)
    if b == "pallas-jit":
        (out,) = _dispatch("kth_set_index", _kth_set_index_jit,
                           (bits, _k32(k)), stats)
        return out.astype(np.int64)
    if b == "pallas":
        _note_pallas(stats, "kth_set_index")
        return _kth_set_index_pallas(bits, np.asarray(k, np.int64))
    return _kth_set_index_np(bits, np.asarray(k, np.int64))


def coverage_multi(delta: np.ndarray, *, backend: str = "numpy",
                   stats: Optional[dict] = None) -> np.ndarray:
    """Sorted-bound deltas (+1 window start / -1 window end) -> boolean
    mask of sweep points where the running cover count is >= 2."""
    b = resolve_backend(backend)
    if b == "pallas-jit":
        (out,) = _dispatch("coverage", _coverage_multi_jit,
                           (delta.astype(np.int32),), stats)
        return out
    if b == "pallas":
        _note_pallas(stats, "coverage")
        return _coverage_multi_pallas(delta.astype(np.int32))
    return np.cumsum(delta) >= 2


def take_and_cut(bits: np.ndarray, k: np.ndarray, *,
                 backend: str = "numpy",
                 stats: Optional[dict] = None
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Fused eviction rank-select + rank-query: the packed first-k take
    mask AND the per-row scan cut (index of the k[i]-th set bit) in one
    call — ONE device dispatch on 'pallas-jit' (the refetch replay
    engine's victim scan); two numpy passes otherwise."""
    if bits.shape[1] == 0:
        return (np.zeros_like(bits, np.uint32),
                np.full(bits.shape[0], -1, np.int64))
    b = resolve_backend(backend)
    if b == "pallas-jit":
        take, cut = _dispatch("take_and_cut", _take_and_cut_jit,
                              (bits, _k32(k)), stats)
        return take, cut.astype(np.int64)
    kk = np.asarray(k, np.int64)
    if b == "pallas":
        _note_pallas(stats, "take_first_k")
        _note_pallas(stats, "kth_set_index")
        return (_take_first_k_pallas(bits, kk),
                _kth_set_index_pallas(bits, kk))
    return _take_first_k_np(bits, kk), _kth_set_index_np(bits, kk)


def phase_step(bits: np.ndarray, base: np.ndarray, rowmask: np.ndarray,
               sbases: np.ndarray, sends: np.ndarray, *,
               stats: Optional[dict] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
    """The fused barrier-flush chain ('pallas-jit' only): R stacked
    regions' packed dirty planes in, per-row dirty counts + packed
    shared-dirty candidate masks out, as ONE jitted device dispatch
    (``lax.scan`` over the region axis).  The device builds each
    region's multi-coverage mask a 32-page word at a time from the flip
    points of the window bounds (``_phase_step_jit``); the numpy
    fallback ``_phase_step_np`` stabs every page with ``searchsorted``
    and exists only as the oracle of the tests — the runtime routes
    non-jit backends through the unfused path."""
    if resolve_backend("pallas-jit") == "pallas-jit":
        counts, shared = _dispatch(
            "phase_step", _phase_step_jit,
            (bits, base, rowmask, sbases, sends), stats)
        return counts.astype(np.int64), shared
    return _phase_step_np(bits, base, rowmask, sbases, sends)


def _phase_step_np(bits, base, rowmask, sbases, sends):
    """Numpy oracle of the fused flush chain (tests + no-jax fallback)."""
    R, W, nw = bits.shape
    counts = np.stack([_popcount_rows_np(bits[r]) for r in range(R)])
    shared = np.zeros_like(bits)
    col = (np.arange(nw, dtype=np.int64)[:, None] * 32
           + np.arange(32, dtype=np.int64)[None, :])
    lanes = np.uint32(1) << np.arange(32, dtype=np.uint32)
    for r in range(R):
        active = rowmask[r] & (counts[r] > 0)
        page = base[r].astype(np.int64)[:, None, None] + col[None]
        cov = (np.searchsorted(sbases[r], page.ravel(), side="right")
               - np.searchsorted(sends[r], page.ravel(), side="right"))
        multi = (cov >= 2).reshape(page.shape)
        mbits = np.where(multi, lanes, np.uint32(0)).sum(
            axis=-1, dtype=np.uint32)
        shared[r] = np.where(active[:, None], bits[r] & mbits, 0)
    return counts.astype(np.int64), shared
