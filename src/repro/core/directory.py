"""Region-level sharing directory for the vectorized RegC protocol engine.

The reference ``RegCRuntime`` and the original scale engine both kept page
state per (worker, region) — a dict of per-worker ``_Window`` arrays.  Every
cross-worker protocol event (sharer invalidation on an ordinary flush, lock
notice replay, barrier sync) then became a Python loop over all workers,
which is what made 256-worker runs protocol-bound in the *simulator* rather
than in the modeled network.

``RegionDirectory`` turns the worker axis into an array axis: one object per
allocation region holds ``valid`` / ``dirty`` / ``wprot`` / ``touch`` as 2D
``(W, window)`` arrays.  Rows are workers.  Because the paper's benchmarks
block-partition each array (own block + halo), rows cover *different* page
intervals of the region; storing the union window densely would cost
W x region_pages.  Instead every row carries its own base offset: column
``j`` of row ``w`` is absolute page ``base[w] + j``, and all rows share one
column capacity (the max touched-window size).  Memory stays O(pages
actually touched), like the old per-worker windows, while cross-worker
operations become single gather/scatter numpy ops over the worker axis:

* ``invalidate_sharers`` — one boolean-mask op over all overlapping rows
  instead of a Python loop over ``range(W)``;
* ``dirty_cells``       — enumerate every (worker, page) dirty pair of the
  region at once, in worker-major order (== the sequential flush order);
* ``window_cover``      — interval-stabbing count of how many worker
  windows contain each page (lets the barrier flush skip the unshared
  majority of pages analytically);
* ``gather_valid``      — the (rows x pages) validity matrix for an
  arbitrary page set, used by both invalidation and notice replay.

``IntervalLog`` is the companion structure for lock notices: a flat,
amortized-growth ``(page, lo, hi)`` array segmented by release version, so
replaying "all notices since this worker last acquired" is an O(1) slice
plus a vectorized per-page segment-min/max coalesce instead of nested dict
loops over versions x notices.

Exactness invariant (see DIRECTORY.md): these are pure representation
changes — the protocol rules and the traffic ledger are byte-identical to
the reference runtime, which ``tests/test_regc_scale.py`` and
``tests/test_directory.py`` cross-validate.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

_I64_MAX = np.iinfo(np.int64).max
_I64_MIN = np.iinfo(np.int64).min


def use_dense(n_rows: int, l_max: int) -> bool:
    """Strategy pick for per-op batched plane updates: dense (rows x Lmax)
    gather/scatter matrices win when per-row work is too small to amortize
    a Python-level row loop (many workers, narrow intervals — the regime
    that made 256-worker runs driver-bound), or when the whole op is tiny;
    wide intervals are slice-throughput bound, where per-row contiguous
    slice ops are ~100x cheaper per cell than gather matrices.  Both
    strategies charge identically, so the cutoff is invisible to traffic
    and clocks (cross-validated in tests/test_regc_scale.py)."""
    return l_max <= 512 or n_rows * l_max <= (1 << 16)


class RegionDirectory:
    """2D per-worker page state of one allocation region.

    Cells outside a row's live window ``[0, length[w])`` always hold the
    init values (valid=False, dirty=False, wprot=True, touch=0), so window
    extension to the right is free and whole-array reductions are safe.
    """

    __slots__ = ("W", "region", "page_lo", "page_hi", "base", "length",
                 "cap", "valid", "dirty", "wprot", "touch", "incache",
                 "shift", "maybe_dirty", "_cov_stale", "_sorted_bases",
                 "_sorted_ends", "backend", "dirty_lo", "dirty_hi",
                 "span_lo", "span_hi", "race_w", "race_r",
                 "race_maxw", "race_maxr", "jit_stats", "_jit_geom")

    def __init__(self, n_workers: int, region: int, page_lo: int,
                 page_hi: int, *, track_wprot: bool = False,
                 track_touch: bool = False, backend: str = "numpy"):
        self.W = n_workers
        self.region = region
        self.page_lo = page_lo
        self.page_hi = page_hi
        self.base = np.full(n_workers, -1, np.int64)
        self.length = np.zeros(n_workers, np.int64)
        self.cap = 0
        self.valid = np.zeros((n_workers, 0), bool)
        self.dirty = np.zeros((n_workers, 0), bool)
        self.wprot = np.zeros((n_workers, 0), bool) if track_wprot else None
        # LRU bookkeeping (cache_pages runs only).  ``incache`` is cache
        # *occupancy*, distinct from ``valid``: the reference runtime keeps
        # invalidated pages in its LRU dict until they are evicted, so a
        # page can occupy a cache slot while invalid.
        self.touch = np.zeros((n_workers, 0), np.int64) if track_touch else None
        self.incache = np.zeros((n_workers, 0), bool) if track_touch else None
        # cumulative left-extension shift per row: lets LRU-queue entries
        # recorded before a window grew leftwards map to current columns
        self.shift = np.zeros(n_workers, np.int64)
        # span-touch planes (consistency regions): per-cell word-interval
        # accumulator [span_lo, span_hi) of the worker's OPEN span — the
        # vectorized replacement for the per-page ``_Span.touched`` dict.
        # Untouched cells hold (I64_MAX, I64_MIN); lazily allocated on the
        # first span write (``ensure_span``) since most regions never see
        # a consistency region.
        self.span_lo = None
        self.span_hi = None
        # race-detection vector-clock planes (detect_races runs only):
        # cell (u, p) of ``race_w`` is component u of page p's *write*
        # vector clock — the epoch (worker u's own clock value) at u's
        # last recorded write to page p; ``race_r`` is the read twin.
        # 0 means "never accessed" (epochs start at 1), so out-of-window
        # cells read as ordered and window growth stays free.  Lazily
        # allocated (``ensure_race``) since detection is an opt-in mode.
        self.race_w = None
        self.race_r = None
        # per-row running max of every epoch ever recorded in this
        # region's race planes (cells only ever grow, so this equals the
        # plane row max) — the batched detector's O(W) screen: when every
        # row's max is happens-before-ordered under the phase's minimum
        # vector-clock view, no cross-phase race check can fire anywhere
        # in the region and recording can skip the per-worker scan.
        self.race_maxw = None
        self.race_maxr = None
        # conservative per-row bounding interval of possibly-dirty pages
        # (absolute page numbers; empty when lo >= hi).  Widened on ordinary
        # writes, reset on flush; eviction clears cells without narrowing
        # them, so the interval over-approximates — which is the sound
        # direction for the phase_all window-disjointness analysis.
        self.dirty_lo = np.full(n_workers, _I64_MAX, np.int64)
        self.dirty_hi = np.full(n_workers, _I64_MIN, np.int64)
        self.maybe_dirty = False
        self._cov_stale = True
        self._sorted_bases: Optional[np.ndarray] = None
        self._sorted_ends: Optional[np.ndarray] = None
        # 'numpy' | 'pallas' | 'pallas-jit': execution backend for the
        # whole-plane reductions (barrier-flush popcount, shared-interval
        # sweep, eviction rank-select).  All tiers are integer-exact, so
        # traffic is backend-independent; 'pallas-jit' additionally fuses
        # the flush reductions into one device dispatch per phase (see
        # DIRECTORY.md "Compiled-phase contract").
        self.backend = backend
        # jit-tier state: the runtime's stats dict (jit_dispatches /
        # jit_cache_misses accounting, attached by ``alloc``) and the
        # cached int32 window-geometry operands of the fused flush chain
        # (rebuilt only when a window changes — _refresh_bounds drops it)
        self.jit_stats: Optional[dict] = None
        self._jit_geom = None

    # ------------------------------------------------------------------
    # window management
    # ------------------------------------------------------------------

    def _grow_cap(self, need: int):
        new_cap = max(need, 2 * self.cap)
        pad = new_cap - self.cap
        self.valid = np.pad(self.valid, ((0, 0), (0, pad)))
        self.dirty = np.pad(self.dirty, ((0, 0), (0, pad)))
        if self.wprot is not None:
            self.wprot = np.pad(self.wprot, ((0, 0), (0, pad)),
                                constant_values=True)
        if self.touch is not None:
            self.touch = np.pad(self.touch, ((0, 0), (0, pad)))
            self.incache = np.pad(self.incache, ((0, 0), (0, pad)))
        if self.span_lo is not None:
            self.span_lo = np.pad(self.span_lo, ((0, 0), (0, pad)),
                                  constant_values=_I64_MAX)
            self.span_hi = np.pad(self.span_hi, ((0, 0), (0, pad)),
                                  constant_values=_I64_MIN)
        if self.race_w is not None:
            self.race_w = np.pad(self.race_w, ((0, 0), (0, pad)))
            self.race_r = np.pad(self.race_r, ((0, 0), (0, pad)))
        self.cap = new_cap

    def ensure_span(self):
        """Allocate the span-touch planes on first use."""
        if self.span_lo is None:
            self.span_lo = np.full((self.W, self.cap), _I64_MAX, np.int64)
            self.span_hi = np.full((self.W, self.cap), _I64_MIN, np.int64)

    def ensure_race(self):
        """Allocate the race vector-clock planes on first use."""
        if self.race_w is None:
            self.race_w = np.zeros((self.W, self.cap), np.int64)
            self.race_r = np.zeros((self.W, self.cap), np.int64)
            self.race_maxw = np.zeros(self.W, np.int64)
            self.race_maxr = np.zeros(self.W, np.int64)

    def ensure(self, w: int, lo: int, hi: int):
        """Grow row w's window to cover absolute pages [lo, hi)."""
        b = self.base[w]
        if b < 0:
            if hi - lo > self.cap:
                self._grow_cap(hi - lo)
            self.base[w] = lo
            self.length[w] = hi - lo
            self._cov_stale = True
            return
        changed = False
        if lo < b:
            pad = int(b - lo)
            n = int(self.length[w])
            if n + pad > self.cap:
                self._grow_cap(n + pad)
            for arr, init in ((self.valid, False), (self.dirty, False),
                              (self.wprot, True), (self.touch, 0),
                              (self.incache, False),
                              (self.span_lo, _I64_MAX),
                              (self.span_hi, _I64_MIN),
                              (self.race_w, 0), (self.race_r, 0)):
                if arr is None:
                    continue
                row = arr[w]
                row[pad:pad + n] = row[:n]
                row[:pad] = init
            self.base[w] = lo
            self.length[w] = n + pad
            self.shift[w] += pad
            b = lo
            changed = True
        if hi > b + self.length[w]:
            n = int(hi - b)
            if n > self.cap:
                self._grow_cap(n)
            self.length[w] = n
            changed = True
        if changed:
            self._cov_stale = True

    def sl(self, w: int, lo: int, hi: int) -> slice:
        b = int(self.base[w])
        return slice(lo - b, hi - b)

    def ensure_rows(self, lo: np.ndarray, hi: np.ndarray,
                    rows: np.ndarray):
        """Vectorized ``ensure`` over ``rows``: grow row rows[i]'s window
        to cover [lo[i], hi[i]).  Python-loops only over rows that actually
        need to grow — zero in the steady state of phase-structured apps."""
        base = self.base[rows]
        need = (base < 0) | (lo < base) | (hi > base + self.length[rows])
        for i in np.nonzero(need)[0]:
            self.ensure(int(rows[i]), int(lo[i]), int(hi[i]))

    # ------------------------------------------------------------------
    # cross-worker vector primitives
    # ------------------------------------------------------------------

    def range_cols(self, lo: np.ndarray, hi: np.ndarray,
                   rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Per-row column-index matrix for the absolute page intervals
        [lo[i], hi[i]) of rows[i] — windows must already cover them
        (``ensure_rows``).  Returns (cols (R, Lmax), mask (R, Lmax));
        mask is False past each row's interval length."""
        L = hi - lo
        j = np.arange(int(L.max()) if L.size else 0)
        cols = (lo - self.base[rows])[:, None] + j[None, :]
        return cols, j[None, :] < L[:, None]

    def count_range(self, plane: np.ndarray, lo: np.ndarray,
                    hi: np.ndarray,
                    rows: Optional[np.ndarray] = None) -> np.ndarray:
        """Per-row counts of True cells of ``plane`` inside [lo[i], hi[i]),
        reading out-of-window cells as False (windows need NOT cover the
        intervals — used by the phase_all eviction precheck).  ``rows``
        restricts the count to a row subset (``lo``/``hi`` then align with
        ``rows``); default is all W rows."""
        rows = np.arange(self.W) if rows is None else rows
        if plane.shape[1] == 0:
            return np.zeros(rows.size, np.int64)
        L = hi - lo
        Lmax = int(L.max()) if L.size else 0
        base = self.base[rows]
        length = self.length[rows]
        if not use_dense(rows.size, Lmax):
            # wide intervals: contiguous slice sums beat building the
            # (R, Lmax) gather matrices (see use_dense).  Rows sharing a
            # clipped window span (block-partitioned phases are uniform up
            # to edge rows) reduce together as one 2D slice-view sum.
            livem = base >= 0
            c0 = np.where(livem, np.maximum(lo - base, 0), 0)
            c1 = np.maximum(np.where(livem, np.minimum(hi - base, length),
                                     0), c0)
            out = np.zeros(rows.size, np.int64)
            if rows.size > 8:
                uk, inv = np.unique(np.stack([c0, c1], axis=1), axis=0,
                                    return_inverse=True)
                for g in range(uk.shape[0]):
                    a, b = int(uk[g, 0]), int(uk[g, 1])
                    if b <= a:
                        continue
                    sel = np.nonzero(inv == g)[0]
                    rb = self.row_block(rows[sel])
                    out[sel] = plane[rb, a:b].sum(axis=1, dtype=np.int64)
                return out
            for i, w in enumerate(rows):
                a, b = int(c0[i]), int(c1[i])
                if b > a:
                    out[i] = int(plane[w, a:b].sum())
            return out
        j = np.arange(Lmax)
        cols = (lo - base)[:, None] + j[None, :]
        m = ((j[None, :] < L[:, None]) & (cols >= 0)
             & (cols < length[:, None]) & (base >= 0)[:, None])
        sub = plane[rows[:, None], np.where(m, cols, 0)] & m
        return sub.sum(axis=1)

    # ------------------------------------------------------------------
    # dirty bounding intervals (phase_all window-disjointness analysis)
    # ------------------------------------------------------------------

    def note_dirty(self, rows, lo, hi):
        """Widen the conservative dirty bounding interval of ``rows`` to
        cover absolute pages [lo, hi) (scalars or aligned arrays)."""
        self.dirty_lo[rows] = np.minimum(self.dirty_lo[rows], lo)
        self.dirty_hi[rows] = np.maximum(self.dirty_hi[rows], hi)

    def clear_dirty_bounds(self, rows=None):
        """Reset dirty bounds after a flush (``rows=None`` resets all)."""
        if rows is None:
            self.dirty_lo[:] = _I64_MAX
            self.dirty_hi[:] = _I64_MIN
        else:
            self.dirty_lo[rows] = _I64_MAX
            self.dirty_hi[rows] = _I64_MIN

    # ------------------------------------------------------------------
    # span-touch planes (consistency regions)
    # ------------------------------------------------------------------

    def span_note(self, w: int, p_lo: int, p_hi: int,
                  wlo, whi):
        """Accumulate one span write's per-page word intervals into row
        w's span planes: cell p gets (min, max)-merged with [wlo[p-p_lo],
        whi[p-p_lo]) — the vectorized replacement for the reference's
        per-page ``span.touched`` dict merge.  ``wlo``/``whi`` are scalars
        (single-page ops, the accumulator steady state) or aligned
        arrays; the window must already cover [p_lo, p_hi)."""
        self.ensure_span()
        if p_hi - p_lo == 1:
            c = int(p_lo) - int(self.base[w])
            row_lo, row_hi = self.span_lo[w], self.span_hi[w]
            lo_s = int(wlo) if np.ndim(wlo) == 0 else int(wlo[0])
            hi_s = int(whi) if np.ndim(whi) == 0 else int(whi[0])
            if lo_s < row_lo[c]:
                row_lo[c] = lo_s
            if hi_s > row_hi[c]:
                row_hi[c] = hi_s
            return
        s = self.sl(w, p_lo, p_hi)
        np.minimum(self.span_lo[w, s], wlo, out=self.span_lo[w, s])
        np.maximum(self.span_hi[w, s], whi, out=self.span_hi[w, s])

    def span_harvest(self, w: int, p_lo: int, p_hi: int):
        """Collect and reset row w's span-touched cells inside absolute
        pages [p_lo, p_hi): returns (pages, los, his) with pages ascending
        — the release-publish payload, replacing
        ``sorted(span.touched.items())``.  Touched cells are reset to the
        untouched sentinel so the planes are clean for the next span."""
        if self.span_lo is None:
            z = np.zeros(0, np.int64)
            return z, z, z
        b = int(self.base[w])
        s = self.sl(w, p_lo, p_hi)
        seg_hi = self.span_hi[w, s]
        cols = np.nonzero(seg_hi != _I64_MIN)[0] + s.start
        if cols.size == 0:
            z = np.zeros(0, np.int64)
            return z, z, z
        los = self.span_lo[w, cols].copy()
        his = self.span_hi[w, cols].copy()
        self.span_lo[w, cols] = _I64_MAX
        self.span_hi[w, cols] = _I64_MIN
        return cols + b, los, his

    # ------------------------------------------------------------------
    # race vector-clock planes (detect_races mode)
    # ------------------------------------------------------------------

    def race_note(self, w: int, p_lo: int, p_hi: int, epoch: int,
                  is_write: bool):
        """Record worker w's access to absolute pages [p_lo, p_hi) at its
        current ``epoch`` into the matching vector-clock plane.  Epochs
        are monotone per worker, so recording is a plain store (≡ max).
        The window must already cover the range (the engine ensures every
        declared access interval before/while executing it; detection
        hooks run after the event, so the windows are always grown)."""
        self.ensure_race()
        plane = self.race_w if is_write else self.race_r
        plane[w, self.sl(w, p_lo, p_hi)] = epoch
        mx = self.race_maxw if is_write else self.race_maxr
        if epoch > mx[w]:
            mx[w] = epoch

    def race_note_rows(self, rows: np.ndarray, p_lo: np.ndarray,
                       p_hi: np.ndarray, epochs: np.ndarray,
                       is_write: bool):
        """Vectorized ``race_note`` over ``rows``: record row rows[i]'s
        access to absolute pages [p_lo[i], p_hi[i]) at epochs[rows[i]]
        — the batched detector's fast path when the screen proves no
        check can fire.  Windows must already cover the ranges."""
        self.ensure_race()
        plane = self.race_w if is_write else self.race_r
        L = p_hi - p_lo
        j = np.arange(int(L.max()) if L.size else 0)
        cols = (p_lo - self.base[rows])[:, None] + j[None, :]
        m = j[None, :] < L[:, None]
        ri, ci = np.nonzero(m)
        plane[rows[ri], cols[ri, ci]] = epochs[rows[ri]]
        mx = self.race_maxw if is_write else self.race_maxr
        # fancy-indexed out= would write a copy — scatter explicitly
        np.maximum.at(mx, rows, epochs[rows])

    def race_hits(self, p_lo: int, p_hi: int, vcw: np.ndarray,
                  is_write: bool):
        """(rows, pages) of write (or read) epochs recorded over absolute
        pages [p_lo, p_hi) that are NOT ordered under the view ``vcw`` —
        the scalar detector's check.  Row-screened: a row whose window
        misses the range (out-of-window cells read 0 — "never accessed",
        ordered under any view) or whose recorded region max is already
        covered by the view (every cell of row u is <= race_max*[u])
        provably holds no firing cell and is skipped without touching
        its plane, so a check costs O(W) when nothing can fire instead
        of materializing a (W, pages) gather."""
        z = np.zeros(0, np.int64)
        if self.race_w is None:
            return z, z
        mx = self.race_maxw if is_write else self.race_maxr
        ov_lo = np.maximum(p_lo, self.base)
        ov_hi = np.minimum(p_hi, self.base + self.length)
        cand = np.nonzero((mx > vcw) & (ov_hi > ov_lo)
                          & (self.base >= 0))[0]
        if cand.size == 0:
            return z, z
        plane = self.race_w if is_write else self.race_r
        cols = (p_lo - self.base[cand])[:, None] + np.arange(p_hi - p_lo)
        inr = (cols >= 0) & (cols < self.length[cand][:, None])
        G = np.where(inr, plane[cand[:, None], np.where(inr, cols, 0)], 0)
        ui, ji = np.nonzero(G > vcw[cand][:, None])
        return cand[ui], p_lo + ji

    # ------------------------------------------------------------------
    # batched eviction primitives (segment LRU over touch-run spans)
    # ------------------------------------------------------------------

    def row_block(self, rows: np.ndarray):
        """Row indexer for (rows x column-slice) plane access: a basic
        slice (zero-copy views, in-place updates) when ``rows`` is an
        ascending contiguous run — the whole axis or a lockstep-group
        stretch, the spill steady states — else the index array itself
        (gather/scatter).  Contiguity is PROVEN (unit steps), not
        inferred from size/bounds: a permuted row set must never alias a
        slice, or per-row values misalign with the plane's row order."""
        if rows.size > 1:
            if bool((np.diff(rows) == 1).all()):
                return slice(int(rows[0]), int(rows[-1]) + 1)
        elif rows.size == 1:
            return slice(int(rows[0]), int(rows[0]) + 1)
        return rows

    def run_live(self, rows: np.ndarray, start: int, length: int,
                 run_ticks: np.ndarray) -> np.ndarray:
        """(R, length) liveness mask of one LRU touch run per row: cell j
        of row i is live (still the current LRU entry for its page, and
        the page still occupies a cache slot) iff its touch tick still
        equals the run's tick ``run_ticks[i]`` and ``incache`` is set
        (ticks are one-per-run and globally monotone, so any re-touch by
        a later run strictly exceeds it).  All rows' runs must share the
        column span [start, start+length) — the lockstep case batched
        eviction groups on."""
        s = slice(start, start + length)
        rb = self.row_block(rows)
        return ((self.touch[rb, s] == run_ticks[:, None])
                & self.incache[rb, s])

    def lru_take(self, live: np.ndarray, k: np.ndarray,
                 tot: Optional[np.ndarray] = None) -> np.ndarray:
        """Segment-LRU selection: per row, the first (oldest-tick) k[i]
        live cells of the run.  Fully-live runs (``tot`` == run length —
        the streaming steady state) reduce to a columnar cutoff; else a
        boolean prefix-count on numpy, or on 'pallas' the run packs to
        uint32 bitmasks and the ``take_first_k`` rank-select kernel
        computes the mask (integer-exact either way)."""
        k = np.asarray(k)
        if tot is not None and bool((tot == live.shape[1]).all()):
            return np.arange(live.shape[1]) < k[:, None]
        if self.backend != "numpy":
            from repro.kernels import protocol_sweep as _ps
            bits = _ps.take_first_k(_ps.pack_mask_rows(live),
                                    np.asarray(k, np.int64),
                                    backend=self.backend,
                                    stats=self.jit_stats)
            return _ps.unpack_mask_rows(bits, live.shape[1])
        return live & (np.cumsum(live, axis=1, dtype=np.int32)
                       <= k[:, None])

    def take_upto_row(self, live: np.ndarray,
                      k: int) -> Tuple[np.ndarray, int]:
        """Rank-select over ONE run's live mask (the refetch replay
        engine's victim scan): the mask of the first k live cells and the
        scan cut — the index just past the k-th live cell, up to which the
        run is consumed.  The caller guarantees the run holds MORE than k
        live cells (whole-run consumption never needs a mask).  On
        'pallas' the mask packs to uint32 bitmasks and the
        ``take_first_k`` rank-select kernel computes it (the cut falls
        out of the take mask itself); integer-exact either way.  The
        standalone ``kth_set_index`` rank-query kernel answers the cut
        without unpacking; on 'pallas-jit' the fused ``take_and_cut``
        program computes mask AND cut in ONE device dispatch."""
        if self.backend == "pallas-jit":
            from repro.kernels import protocol_sweep as _ps
            bits, cut = _ps.take_and_cut(_ps.pack_mask_rows(live[None]),
                                         np.asarray([k], np.int64),
                                         backend=self.backend,
                                         stats=self.jit_stats)
            return _ps.unpack_mask_rows(bits, live.size)[0], int(cut[0]) + 1
        if self.backend == "pallas":
            from repro.kernels import protocol_sweep as _ps
            take = _ps.unpack_mask_rows(
                _ps.take_first_k(_ps.pack_mask_rows(live[None]),
                                 np.asarray([k], np.int64),
                                 backend=self.backend,
                                 stats=self.jit_stats),
                live.size)[0]
            return take, int(np.flatnonzero(take)[-1]) + 1
        cs = np.cumsum(live, dtype=np.int64)
        take = live & (cs <= k)
        return take, int(np.argmax(cs >= k)) + 1

    def evict_rows(self, rows: np.ndarray, start: int, length: int,
                   take: Optional[np.ndarray], *,
                   set_wprot: bool) -> np.ndarray:
        """Batched eviction of the ``take`` cells (an (R, length) mask over
        columns [start, start+length) of ``rows``; None takes the whole
        span — the streaming steady state): dirty victims clear and
        re-arm write protection (when ``set_wprot``), then valid and the
        cache slot (incache) drop.  Returns per-row dirty-victim counts —
        the runtime's writeback charge; on 'pallas' the count is a packed
        bitmask popcount.  Plane updates only: traffic/clock accounting
        (and the sharer-invalidation step, which the caller must have
        proven a no-op) stay in the runtime."""
        s = slice(start, start + length)
        rb = self.row_block(rows)
        dm = self.dirty[rb, s] if take is None else self.dirty[rb, s] & take
        if self.backend != "numpy":
            from repro.kernels import protocol_sweep as _ps
            db = _ps.popcount_rows(_ps.pack_mask_rows(dm),
                                   backend=self.backend,
                                   stats=self.jit_stats)
        else:
            db = dm.sum(axis=1, dtype=np.int64)
        if take is None:
            if db.any():
                if set_wprot and self.wprot is not None:
                    self.wprot[rb, s] |= dm
                self.dirty[rb, s] = False
            self.valid[rb, s] = False
            self.incache[rb, s] = False
        else:
            keep = ~take
            if db.any():
                self.dirty[rb, s] &= ~dm
                if set_wprot and self.wprot is not None:
                    self.wprot[rb, s] |= dm
            self.valid[rb, s] &= keep
            self.incache[rb, s] &= keep
        return db

    def overlap_rows(self, lo: int, hi: int,
                     exclude: Optional[int] = None) -> np.ndarray:
        """Workers whose window intersects absolute pages [lo, hi)."""
        m = (self.base >= 0) & (self.base < hi) & (self.base + self.length > lo)
        if exclude is not None:
            m[exclude] = False
        return np.nonzero(m)[0]

    def gather_valid(self, rows: np.ndarray,
                     pages: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(len(rows), len(pages)) validity matrix plus the column-index
        matrix (for scattering back).  Out-of-window cells read False."""
        cols = pages[None, :] - self.base[rows][:, None]
        inr = (cols >= 0) & (cols < self.length[rows][:, None])
        sub = self.valid[rows[:, None], np.where(inr, cols, 0)] & inr
        return sub, cols

    def clear_valid_cells(self, rows: np.ndarray, cols: np.ndarray,
                          hit: np.ndarray) -> np.ndarray:
        """Clear valid at the True cells of ``hit`` (a (rows x pages) mask
        aligned with ``cols``); returns per-row cleared counts."""
        ri, ci = np.nonzero(hit)
        if ri.size:
            self.valid[rows[ri], cols[ri, ci]] = False
        return hit.sum(axis=1)

    def _refresh_bounds(self):
        if self._cov_stale:
            live = self.base >= 0
            self._sorted_bases = np.sort(self.base[live])
            self._sorted_ends = np.sort((self.base + self.length)[live])
            self._jit_geom = None          # window geometry changed
            self._cov_stale = False

    def jit_geometry(self):
        """(base, sorted_bases, sorted_ends) as int32 — the fused flush
        chain's window-geometry operands (``kernels.phase_step``), cached
        until a window changes (``_cov_stale`` drops it).  The packed
        dirty planes are rebuilt per flush (their contents changed) but
        geometry survives phases — the steady state re-packs one plane
        and reuses everything else."""
        self._refresh_bounds()
        if self._jit_geom is None:
            self._jit_geom = (self.base.astype(np.int32),
                              self._sorted_bases.astype(np.int32),
                              self._sorted_ends.astype(np.int32))
        return self._jit_geom

    def shared_intervals(self) -> Tuple[np.ndarray, np.ndarray]:
        """Absolute page intervals covered by >= 2 worker windows, as
        (starts, ends) arrays — a sweep over the 2W window bounds.  Pages
        outside these intervals cannot have sharers, so barrier flushes
        skip them without per-page work.  The coverage cumsum runs on the
        selected backend (``kernels.protocol_sweep`` for 'pallas')."""
        self._refresh_bounds()
        b, e = self._sorted_bases, self._sorted_ends
        if b.size < 2:
            z = np.zeros(0, np.int64)
            return z, z
        pts = np.concatenate([b, e])
        delta = np.concatenate([np.ones(b.size, np.int64),
                                np.full(e.size, -1, np.int64)])
        order = np.argsort(pts, kind="stable")
        pts = pts[order]
        if self.backend != "numpy":
            from repro.kernels import protocol_sweep as _ps
            multi = _ps.coverage_multi(delta[order], backend=self.backend,
                                       stats=self.jit_stats)
        else:
            multi = np.cumsum(delta[order]) >= 2
        edge = np.diff(np.concatenate([[False], multi]).astype(np.int8))
        starts = pts[np.nonzero(edge == 1)[0]]
        ends_i = np.nonzero(edge == -1)[0]
        ends = pts[ends_i]
        if multi[-1]:
            ends = np.concatenate([ends, pts[-1:]])
        keep = ends > starts
        return starts[keep], ends[keep]

    def dirty_counts(self) -> np.ndarray:
        """(W,) per-row dirty-page counts — the barrier-flush popcount.
        On the 'pallas' backend the boolean plane is packed into uint32
        bitmasks and popcounted by the protocol-sweep kernel; cells outside
        a row's live window are always False, so whole-plane reduction is
        exact on every backend.  (On 'pallas-jit' the barrier flush
        bypasses this per-region call for the fused ``phase_step`` chain;
        this path serves direct callers.)"""
        if self.backend != "numpy":
            from repro.kernels import protocol_sweep as _ps
            return _ps.popcount_rows(_ps.pack_mask_rows(self.dirty),
                                     backend=self.backend,
                                     stats=self.jit_stats)
        return self.dirty.sum(axis=1)

    def row_dirty_cols(self, w: int) -> np.ndarray:
        n = int(self.length[w])
        return np.nonzero(self.dirty[w, :n])[0]

    # ------------------------------------------------------------------
    # snapshot / restore (see DIRECTORY.md "Recovery contract")
    # ------------------------------------------------------------------

    def state_arrays(self, rows=None) -> Tuple[dict, dict]:
        """Full plane state as (arrays, meta) — everything needed to
        rebuild a row-for-row, cell-for-cell clone.  Planes are stored at
        their current capacity; the derived coverage caches
        (``_sorted_bases``/``_sorted_ends``) are recomputed on restore.

        Every array here is worker-major (first dim ``W``), so ``rows``
        (a slice or index array) restricts the payload to a shard's
        worker slice — the cluster checkpoint path; ``meta`` still
        records the full ``W`` (a slice is a view of the whole table,
        not a smaller directory)."""
        sl = slice(None) if rows is None else rows
        arrays = {"base": self.base[sl].copy(),
                  "length": self.length[sl].copy(),
                  "shift": self.shift[sl].copy(),
                  "valid": self.valid[sl].copy(),
                  "dirty": self.dirty[sl].copy(),
                  "dirty_lo": self.dirty_lo[sl].copy(),
                  "dirty_hi": self.dirty_hi[sl].copy()}
        for name in ("wprot", "touch", "incache", "span_lo", "span_hi",
                     "race_w", "race_r", "race_maxw", "race_maxr"):
            arr = getattr(self, name)
            if arr is not None:
                arrays[name] = arr[sl].copy()
        meta = {"W": self.W, "region": self.region,
                "page_lo": self.page_lo, "page_hi": self.page_hi,
                "cap": self.cap, "maybe_dirty": bool(self.maybe_dirty),
                "track_wprot": self.wprot is not None,
                "track_touch": self.touch is not None,
                "has_span": self.span_lo is not None,
                "has_race": self.race_w is not None,
                "backend": self.backend}
        return arrays, meta

    @classmethod
    def from_state(cls, arrays: dict, meta: dict) -> "RegionDirectory":
        d = cls(meta["W"], meta["region"], meta["page_lo"],
                meta["page_hi"], track_wprot=meta["track_wprot"],
                track_touch=meta["track_touch"], backend=meta["backend"])
        d.cap = int(meta["cap"])
        d.base = np.asarray(arrays["base"], np.int64).copy()
        d.length = np.asarray(arrays["length"], np.int64).copy()
        d.shift = np.asarray(arrays["shift"], np.int64).copy()
        d.valid = np.asarray(arrays["valid"], bool).copy()
        d.dirty = np.asarray(arrays["dirty"], bool).copy()
        d.dirty_lo = np.asarray(arrays["dirty_lo"], np.int64).copy()
        d.dirty_hi = np.asarray(arrays["dirty_hi"], np.int64).copy()
        if meta["track_wprot"]:
            d.wprot = np.asarray(arrays["wprot"], bool).copy()
        if meta["track_touch"]:
            d.touch = np.asarray(arrays["touch"], np.int64).copy()
            d.incache = np.asarray(arrays["incache"], bool).copy()
        if meta["has_span"]:
            d.span_lo = np.asarray(arrays["span_lo"], np.int64).copy()
            d.span_hi = np.asarray(arrays["span_hi"], np.int64).copy()
        if meta.get("has_race"):
            d.race_w = np.asarray(arrays["race_w"], np.int64).copy()
            d.race_r = np.asarray(arrays["race_r"], np.int64).copy()
            d.race_maxw = np.asarray(arrays["race_maxw"], np.int64).copy()
            d.race_maxr = np.asarray(arrays["race_maxr"], np.int64).copy()
        d.maybe_dirty = bool(meta["maybe_dirty"])
        d._cov_stale = True
        return d


class IntervalLog:
    """Flat, version-segmented (page, lo, hi) notice log for one lock.

    ``append_version`` records one release's notices; ``pending`` returns
    the per-page coalesced (min lo, max hi) intervals of every version in
    ``[v_from, v_to)`` — a slice of the flat arrays plus one vectorized
    segment-min/max, replacing the reference's dict-merge over versions.
    Pages come back sorted ascending, matching the reference's
    ``sorted(pending.items())`` replay order.
    """

    __slots__ = ("_p", "_lo", "_hi", "_n", "voff")

    def __init__(self):
        self._p = np.zeros(8, np.int64)
        self._lo = np.zeros(8, np.int64)
        self._hi = np.zeros(8, np.int64)
        self._n = 0
        self.voff = [0]

    def _reserve(self, k: int):
        need = self._n + k
        if need > self._p.size:
            cap = max(need, 2 * self._p.size)
            for name in ("_p", "_lo", "_hi"):
                arr = getattr(self, name)
                new = np.zeros(cap, np.int64)
                new[:self._n] = arr[:self._n]
                setattr(self, name, new)

    def append_version(self, pages, los, his):
        k = len(pages)
        self._reserve(k)
        n = self._n
        self._p[n:n + k] = pages
        self._lo[n:n + k] = los
        self._hi[n:n + k] = his
        self._n = n + k
        self.voff.append(self._n)

    def append_versions(self, pages, los, his, counts):
        """Append SEVERAL release versions in one reserve+copy: version i
        of the batch owns the next ``counts[i]`` entries of the flat
        (pages, los, his) arrays.  One numpy copy + one ``voff`` extend
        replaces per-release ``append_version`` calls — the span_all
        pipelined-release path (every worker of a uniform lock group
        publishes the same interval set, tiled by the caller)."""
        k = len(pages)
        assert int(np.sum(counts)) == k, (counts, k)
        self._reserve(k)
        n = self._n
        self._p[n:n + k] = pages
        self._lo[n:n + k] = los
        self._hi[n:n + k] = his
        self._n = n + k
        self.voff.extend((n + np.cumsum(counts, dtype=np.int64)).tolist())

    def payload_matches(self, v_from: int, v_to: int, pages, los,
                        his) -> bool:
        """True iff every version in [v_from, v_to) carries exactly this
        payload (same pages/los/his, in order) — the span_all uniform
        group's backlog check.  The caller must already know each
        version's entry count equals ``len(pages)``."""
        a, b = self.voff[v_from], self.voff[v_to]
        k = v_to - v_from
        n = len(pages)
        if b - a != k * n:
            return False
        return (bool((self._p[a:b].reshape(k, n) == pages).all())
                and bool((self._lo[a:b].reshape(k, n) == los).all())
                and bool((self._hi[a:b].reshape(k, n) == his).all()))

    def page_bounds(self, v_from: int, v_to: int):
        """Bounding (lo, hi) page interval of every notice in versions
        [v_from, v_to), or None when the slice is empty — the span_all
        flush-hoist screen's conservative pending-page footprint."""
        a, b = self.voff[v_from], self.voff[v_to]
        if a == b:
            return None
        seg = self._p[a:b]
        return int(seg.min()), int(seg.max()) + 1

    def state_arrays(self) -> dict:
        """Live log contents (entries [0, _n) plus the version offsets) —
        the snapshot payload; spare capacity is not serialized."""
        n = self._n
        return {"p": self._p[:n].copy(), "lo": self._lo[:n].copy(),
                "hi": self._hi[:n].copy(),
                "voff": np.asarray(self.voff, np.int64)}

    @classmethod
    def from_state(cls, arrays: dict) -> "IntervalLog":
        log = cls()
        p = np.asarray(arrays["p"], np.int64)
        n = int(p.size)
        log._reserve(n)
        log._p[:n] = p
        log._lo[:n] = np.asarray(arrays["lo"], np.int64)
        log._hi[:n] = np.asarray(arrays["hi"], np.int64)
        log._n = n
        log.voff = [int(v) for v in np.asarray(arrays["voff"], np.int64)]
        return log

    def pending(self, v_from: int, v_to: int):
        """Coalesced (pages, lo_min, hi_max) over versions [v_from, v_to)."""
        a, b = self.voff[v_from], self.voff[v_to]
        if a == b:
            e = np.zeros(0, np.int64)
            return e, e, e
        seg_p = self._p[a:b]
        u, inv = np.unique(seg_p, return_inverse=True)
        lo_min = np.full(u.size, np.iinfo(np.int64).max, np.int64)
        hi_max = np.full(u.size, np.iinfo(np.int64).min, np.int64)
        np.minimum.at(lo_min, inv, self._lo[a:b])
        np.maximum.at(hi_max, inv, self._hi[a:b])
        return u, lo_min, hi_max
