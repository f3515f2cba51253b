"""Directory-vectorized RegC protocol engine for paper-scale runs.

Same protocol as ``core.regc.RegCRuntime`` — same rules, same traffic
accounting — but all cross-worker paths are vectorized over the worker axis
through a per-region sharing directory (``core.directory.RegionDirectory``)
so the paper's figures (STREAM TRIAD / Jacobi / MD up to 256 cores,
millions of pages) run in seconds.  ``tests/test_regc_scale.py`` and
``tests/test_directory.py`` cross-validate the traffic counters (exactly)
and the modeled clocks (to float tolerance) against the reference runtime.

Key representation choices:

* page state is per *region*: ``valid/dirty/wprot/touch`` live in one 2D
  ``(W, window)`` directory per allocation region, rows = workers, each row
  offset to the worker's touched window, so memory is O(touched) while
  sharer invalidation, barrier flushes, and notice replay are single
  boolean-mask / gather-scatter numpy ops instead of ``range(W)`` loops;
* reads/writes are per-*interval* (vectorized over the page range);
* eviction is watermark-triggered: a per-worker resident counter makes the
  common no-eviction case O(1); past the watermark the oldest pages pop
  from a tick-ordered FIFO of touch runs (one monotone tick per run —
  victim order within a run is its column order, which is the reference's
  per-op LRU order; see DIRECTORY.md).  ``phase_all`` never abandons the
  batched path under spill: a window-disjointness analysis over the
  declared ranges proves which workers' evictions cannot interact, evicts
  them with vectorized segment-LRU plane ops, and replays only the
  residual interacting workers tick-ordered.  Ops that can evict pages of
  their own range before touching them (the mid-op refetch pattern,
  flagged by ``_danger``) resolve through an analytic segmented
  evict-then-refetch schedule (``_danger_replay``) instead of a per-page
  Python walk, in BOTH drivers;
* lock notices are flat, version-segmented numpy interval logs
  (``core.directory.IntervalLog``); acquire/barrier replay is one slice +
  segment-min/max coalesce per (lock, worker);
* consistency-region spans are plane-tracked (``span_lo``/``span_hi``
  word-interval planes; release harvests and publishes one batched log
  append), and whole span PASSES batch through ``span_all``: grants stay
  serialized — they are the lock — while each worker's release-flush and
  the next holder's acquire-replay pipeline as plane ops
  (``_span_group_vec``); only nested spans keep the per-page dict.

Beyond the reference runtime, this engine also models the paper's two
store-tracking *mechanisms* (§IV):

* ``fine``  (samhita): every store is instrumented with a runtime call
  (LLVM pass) -> ``instr_s_per_word`` per stored word, in ordinary AND
  consistency regions (the MD result: overhead visible even when almost all
  stores are ordinary);
* ``page``  (samhita_page): write detection via VM protection -> one
  ``fault_s`` per (page x write-epoch), re-armed when the page is flushed.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core.config import (DANGER_MODES, FAULT_S, INSTR_S_PER_WORD,
                               PROTOCOLS, check_choice)
from repro.core.directory import IntervalLog, RegionDirectory, use_dense
from repro.core.regc import (FINE_PROTO, IDEAL_PROTO, PAGE_PROTO, GasArray,
                             Traffic, _WORD)
from repro.dsm.costmodel import CostModel, IB_2013


class _Span:
    __slots__ = ("lock", "touched", "plane", "bounds")

    def __init__(self, lock, plane: bool = False):
        self.lock = lock
        self.plane = plane
        # A depth-1 (outermost) span tracks its touches in the directory's
        # span planes (vectorized interval merge, no per-page dict);
        # ``bounds`` records the touched page bounding interval per region
        # for the release harvest.  Nested (inner) spans keep the
        # reference's per-page dict — at most one plane-tracked span is
        # open per worker, so the planes never mix two spans' touches.
        self.touched: Optional[Dict[int, Tuple[int, int]]] = (
            None if plane else {})
        self.bounds: Optional[Dict[int, list]] = {} if plane else None


class _Lock:
    __slots__ = ("version", "log", "last_release_time", "seen", "race_vc")

    def __init__(self, n_workers):
        self.version = 0
        self.log = IntervalLog()
        self.last_release_time = 0.0
        self.seen = np.zeros(n_workers, np.int64)
        # detect_races only: the lock's vector clock — the join of every
        # releaser's clock at release time (see DIRECTORY.md
        # "Race-detection contract")
        self.race_vc = np.zeros(n_workers, np.int64)


class RegCScaleRuntime:
    """Drop-in (metadata-only) directory-vectorized version of RegCRuntime."""

    def __init__(self, n_workers: int, *, page_words: int = 1024,
                 protocol: str = FINE_PROTO, cost: CostModel = IB_2013,
                 cache_pages: Optional[int] = None, prefetch: int = 1,
                 n_mem_servers: int = 1, model_mechanism: bool = True,
                 instr_s_per_word: float = INSTR_S_PER_WORD,
                 fault_s: float = FAULT_S, fetch_batch: int = 1,
                 backend: str = "numpy", danger_mode: str = "vec",
                 detect_races: bool = False,
                 chaos=None, injector=None, straggler=None):
        check_choice("protocol", protocol, PROTOCOLS)
        # 'vec' | 'scalar': how ops flagged by the per-op ``_danger``
        # screen (mid-op refetch possible) replay.  'vec' evaluates the
        # analytic segmented evict-then-refetch schedule (_danger_replay);
        # 'scalar' forces the page-by-page reference walk — the oracle the
        # trace-fuzz suite cross-validates against.  Both are
        # traffic-exact; only wall time differs.
        check_choice("danger_mode", danger_mode, DANGER_MODES)
        self.danger_mode = danger_mode
        # 'numpy' | 'pallas' | 'pallas-jit': backend for the whole-plane
        # directory reductions (kernels.protocol_sweep).  Integer-exact
        # on every tier; 'pallas-jit' compiles the barrier-flush hot path
        # into ONE fused device dispatch per phase (see DIRECTORY.md
        # "Compiled-phase contract").  Degrades to numpy with a warning
        # when jax is unavailable (or REPRO_FORCE_NUMPY=1).
        from repro.kernels.protocol_sweep import resolve_backend
        self.backend = resolve_backend(backend)
        self.W = n_workers
        self.page_words = page_words
        self.page_bytes = page_words * _WORD
        self.protocol = protocol
        self.cost = cost
        self.cache_pages = cache_pages
        self.prefetch = prefetch
        self.n_mem_servers = max(1, n_mem_servers)
        self.model_mechanism = model_mechanism
        self.instr_s_per_word = instr_s_per_word
        self.fault_s = fault_s
        # Samhita's bulk-fetch optimization (paper §V-A): a miss run of k
        # pages costs ceil(k/fetch_batch) request/reply pairs, not k.
        # fetch_batch=1 == reference runtime accounting.
        self.fetch_batch = max(1, fetch_batch)
        self._track_wprot = (protocol == PAGE_PROTO and model_mechanism)
        self._track_touch = cache_pages is not None

        self.n_pages = 0
        self._region_starts: List[int] = []     # sorted page_lo per region
        self._region_ends: List[int] = []
        self._region_starts_np = np.zeros(0, np.int64)
        self.dirs: List[RegionDirectory] = []
        self.spans: List[List[_Span]] = [[] for _ in range(n_workers)]
        self.locks: Dict[int, _Lock] = {}
        self.clock = np.zeros(n_workers)
        self.traffic = Traffic()
        # per-worker cache occupancy (valid + invalidated-but-not-evicted
        # pages, matching the reference's LRU dict): the eviction watermark
        self.resident = np.zeros(n_workers, np.int64)
        # per-worker FIFO of touch runs
        # [t0, region, col0, n, off, shift0, pristine]: ticks are globally
        # monotone (one per run), so the queue is tick-ordered and an LRU
        # pop is a front scan that lazily skips re-touched (stale) and
        # already-evicted cells — amortized O(1) per page.  ``pristine``
        # runs were never overlapped by a later op of the same worker, so
        # their live cells are exactly the [off, n) suffix and eviction
        # needs no touch scan (see _q_append)
        self._lru_q: List[deque] = [deque() for _ in range(n_workers)]
        self._q_degraded = np.zeros(n_workers, bool)
        self._dirty_regions: List[set] = [set() for _ in range(n_workers)]
        self._reductions: Dict[str, List[Tuple[float, str]]] = {}
        self._reduction_results: Dict[str, float] = {}
        self._tick = 0
        self._rows_all = np.arange(n_workers)
        # when a dict, _danger_replay records its eviction schedule into
        # it (the shared-schedule leader run — see _danger_shared)
        self._danger_rec: Optional[dict] = None
        # phase_all path counters (which engine paths ran; the trace-fuzz
        # suite asserts the batched-eviction and residual paths are
        # actually exercised rather than silently bypassed)
        self.stats = {"batched_phases": 0, "evict_batch_rounds": 0,
                      "danger_ops": 0, "residual_replays": 0,
                      "danger_vec_ops": 0, "danger_scalar_ops": 0,
                      "danger_shared_ops": 0, "danger_subgroup_ops": 0,
                      "span_all_calls": 0, "span_serial_calls": 0,
                      "span_groups_vec": 0, "span_workers_vec": 0,
                      "span_multi_region_groups": 0,
                      "span_serial_workers": 0,
                      "span_backlog_serial": 0,
                      "race_ww": 0, "race_rw": 0,
                      # 'pallas-jit' accounting: fused/jitted device
                      # dispatches and first-seen-shape compiles.  CI's
                      # kernels smoke gates jit_dispatches > 0 on jit
                      # bench legs — a silent fallback to numpy keeps
                      # traffic identical but zeroes the counter.
                      "jit_dispatches": 0, "jit_cache_misses": 0}
        # race-detection mode (pure observer; see DIRECTORY.md
        # "Race-detection contract"): per-worker vector clocks, the
        # canonical flagged-race set, and a suspension flag the batched
        # drivers set while replaying ops internally (phase_all residual
        # replay, span_all fallbacks) so detection runs exactly once per
        # access — in the driver-level batched pass.
        self.detect_races = detect_races
        self.race_vc = (np.eye(n_workers, dtype=np.int64)
                        if detect_races else None)
        self.races: set = set()
        self._race_suspend = False
        # fault-tolerance wiring (see ft/coherence.py and DIRECTORY.md
        # "Recovery contract"): ``chaos`` is a dsm.costmodel.ChaosNet
        # message-loss model (one per-worker tick per clock-charged
        # message-group event — per-worker event order is identical
        # across drivers, so retry charges keep loop/batched bit-equal);
        # ``injector`` is a ft.runtime.FailureInjector fired at phase/
        # span/barrier boundaries (``chaos_tick``); ``straggler`` is a
        # ft.runtime.StragglerMonitor observed on per-barrier walls.
        self.chaos = chaos
        self.injector = injector
        self.straggler = straggler
        if chaos is not None:
            chaos.bind(n_workers, self.stats)
        if straggler is not None:
            assert straggler.n == n_workers, (straggler.n, n_workers)
            self.stats.setdefault("straggler_checks", 0)
            self.stats.setdefault("straggler_flags", 0)
        self._phase_idx = 0
        self._bar_clock0 = np.zeros(n_workers)

    def chaos_tick(self):
        """Advance the phase-program position and give the failure
        injector its shot.  Called internally at ``phase_all`` /
        ``span_all`` / ``barrier`` entry; loop-driver harnesses call it
        once per equivalent event so both drivers see the same
        per-event injection schedule.  A raise here interrupts BEFORE
        any of the event's state mutations — the runtime is exactly its
        post-previous-event self, which a barrier checkpoint + replayed
        event prefix reproduces bit-for-bit."""
        self._phase_idx += 1
        if self.injector is not None:
            self.injector.check(self._phase_idx)

    # ------------------------------------------------------------------
    def alloc(self, n_elems: int) -> GasArray:
        pages = -(-n_elems // self.page_words)
        ga = GasArray(self.n_pages, n_elems, self.page_words)
        self._region_starts.append(self.n_pages)
        self._region_ends.append(self.n_pages + pages)
        self._region_starts_np = np.asarray(self._region_starts, np.int64)
        d = RegionDirectory(
            self.W, len(self.dirs), self.n_pages, self.n_pages + pages,
            track_wprot=self._track_wprot, track_touch=self._track_touch,
            backend=self.backend)
        d.jit_stats = self.stats
        self.dirs.append(d)
        self.n_pages += pages
        return ga

    def _region_of(self, page: int) -> int:
        i = bisect.bisect_right(self._region_starts, page) - 1
        assert 0 <= i and page < self._region_ends[i], page
        return i

    def _net(self, w: int, n_bytes: float, msgs: int = 1):
        if self.protocol == IDEAL_PROTO:
            return
        self.clock[w] += self.cost.xfer_s(n_bytes, msgs)
        if self.chaos is not None:
            self.clock[w] += self.chaos.retry1(w)

    def compute(self, w: int, *, flops: float = 0.0, mem_bytes: float = 0.0,
                seconds: float = 0.0):
        self.clock[w] += seconds + self.cost.compute_s(
            flops, mem_bytes, self.cost.workers_on_node(self.W))

    def instr_stores(self, w: int, n_words: float):
        """Inner-loop stores to shared memory that the LLVM pass instruments
        (e.g. MD force accumulation): charged per word under the fine
        protocol; under the page protocol they hit already-faulted pages."""
        if self.model_mechanism and self.protocol == FINE_PROTO:
            self.clock[w] += n_words * self.instr_s_per_word

    # ------------------------------------------------------------------
    # interval fetch / batched eviction
    # ------------------------------------------------------------------

    _Q_SCAN_LIMIT = 64

    def _q_append(self, w: int, region: int, col0: int, n: int,
                  shift0: int) -> int:
        """Append a touch run to w's tick-ordered LRU queue and return its
        fresh (monotone) tick.  Older queued runs of the same region whose
        live span overlaps the new run lose their ``pristine`` flag —
        their overlapped cells are re-touched by this op, so the
        prefix-liveness shortcut no longer holds for them.  Queues longer
        than the scan limit (per-page danger-path runs) degrade wholesale
        to non-pristine, keeping appends O(1) amortized; eviction then
        falls back to the exact touch scan."""
        self._tick += 1
        q = self._lru_q[w]
        pristine = True
        if len(q) > self._Q_SCAN_LIMIT:
            if not self._q_degraded[w]:
                for e in q:
                    e[6] = False
                self._q_degraded[w] = True
            pristine = False
        else:
            self._q_degraded[w] = False
            hi = col0 + n
            for e in q:
                if e[1] != region or not e[6]:
                    continue
                ec0 = e[2] + (shift0 - e[5])
                if ec0 + e[4] < hi and ec0 + e[3] > col0:
                    e[6] = False
        q.append([self._tick, region, col0, n, 0, shift0, pristine])
        return self._tick

    def _fetch_range(self, w: int, region: int, p_lo: int, p_hi: int):
        """Make pages [p_lo, p_hi) valid at w, charging misses."""
        d = self.dirs[region]
        d.ensure(w, p_lo, p_hi)
        s = d.sl(w, p_lo, p_hi)
        n = p_hi - p_lo
        n_miss = n - int(d.valid[w, s].sum())
        if d.touch is not None:
            # one monotone tick per touch RUN (column order within a run
            # is the reference's per-op LRU order, so per-page tick values
            # are redundant — see DIRECTORY.md): re-touches by later runs
            # get strictly larger ticks, which is all staleness detection
            # compares
            d.touch[w, s] = self._q_append(w, region, s.start, n,
                                           int(d.shift[w]))
            n_enter = n - int(d.incache[w, s].sum())
            if n_enter:
                d.incache[w, s] = True
                self.resident[w] += n_enter
        if n_miss:
            if self.protocol != IDEAL_PROTO:
                self.traffic.page_fetches += n_miss
                self.traffic.fetch_bytes += n_miss * self.page_bytes
                n_req = -(-n_miss // self.fetch_batch)
                self._net(w, n_miss * self.page_bytes, 2 * n_req)
            d.valid[w, s] = True

    def _danger(self, w: int, n_enter: int, n: int) -> bool:
        """Batched end-of-op eviction is exact unless this op can evict a
        page of its *own* range (one already occupying a cache slot) before
        touching it — the reference would then refetch / re-enter it
        mid-op.  That needs both an in-cache page in the range
        (n_enter < n) and an eviction this op; fully-cold ranges (the spill
        benchmarks' steady state) and eviction-free ops stay on the batch
        path."""
        return (self.cache_pages is not None
                and self.protocol != IDEAL_PROTO
                and n_enter < n
                and int(self.resident[w]) + n_enter > self.cache_pages)

    def _evict_now(self, w: int, d: RegionDirectory, vc: np.ndarray):
        """Evict the cells ``vc`` (ascending tick order) of w's row in
        region d: dirty victims (valid or not) write back first — one
        message per page, matching the reference's per-page eviction flush
        — then both ``valid`` and the cache slot (``incache``) drop.
        Contiguous victim runs (the streaming-spill steady state) use
        slice ops instead of fancy indexing."""
        lo, hi = int(vc[0]), int(vc[-1]) + 1
        sl = slice(lo, hi) if hi - lo == vc.size else vc
        dmask = d.dirty[w, sl]
        if dmask.any():
            db = vc[dmask]
            d.dirty[w, sl] = False     # only the db cells were set
            if self.protocol != IDEAL_PROTO:
                self.traffic.writeback_bytes += db.size * self.page_bytes
                self.clock[w] += (self.cost.net_latency_s * db.size
                                  + db.size * self.page_bytes
                                  / self.cost.net_bw_Bps)
                if self.chaos is not None:
                    self.clock[w] += self.chaos.retry1(w)
                if d.wprot is not None:
                    d.wprot[w, db] = True
                self._invalidate_sharers(w, d.region, d.base[w] + db)
        d.valid[w, sl] = False
        d.incache[w, sl] = False
        self.resident[w] -= vc.size

    def _evict_cells(self, w: int, k: int):
        """Evict w's k least-recently-touched cache occupants by scanning
        the tick-ordered run queue from the front, lazily skipping cells
        that were re-touched (their live entry is a later run) or already
        evicted.  Each queue cell is examined O(1) times overall, so
        steady-state spill eviction is amortized O(1) per page."""
        q = self._lru_q[w]
        while k > 0:
            run = q[0]
            t0, region, col0, n, off, shift0, pristine = run
            d = self.dirs[region]
            c0 = col0 + (int(d.shift[w]) - shift0)
            if pristine:
                # never re-touched: live cells are exactly [off, n), so
                # the victims are a contiguous prefix — no touch scan
                tk = min(k, n - off)
                self._evict_now(w, d, np.arange(c0 + off, c0 + off + tk))
                k -= tk
                if off + tk == n:
                    q.popleft()
                else:
                    run[4] = off + tk
                continue
            sl = slice(c0 + off, c0 + n)      # run cells are contiguous
            live = (d.touch[w, sl] == t0) & d.incache[w, sl]
            idx = np.nonzero(live)[0]
            if idx.size == 0:
                q.popleft()
                continue
            take = idx[:k]
            self._evict_now(w, d, c0 + off + take)
            k -= take.size
            if take.size == idx.size:
                q.popleft()          # no live cells remain in this run
            else:
                run[4] = off + int(take[-1]) + 1

    def _touch_page_exact(self, w: int, d: RegionDirectory, p: int,
                          fetch: bool) -> int:
        """Per-page touch/fetch + immediate LRU eviction, mirroring the
        reference's ``_fetch``/``_touch_lru`` sequence for dangerous ops.
        Returns the number of pages fetched (0/1); the *caller* charges
        the fetch messages once per op so batching (``fetch_batch``)
        costs the same on this path as on the batch path."""
        col = p - int(d.base[w])
        n_miss = 0
        if not d.valid[w, col]:
            if fetch and self.protocol != IDEAL_PROTO:
                self.traffic.page_fetches += 1
                self.traffic.fetch_bytes += self.page_bytes
                n_miss = 1
            d.valid[w, col] = True
        if not d.incache[w, col]:
            d.incache[w, col] = True
            self.resident[w] += 1
        d.touch[w, col] = self._q_append(w, d.region, col, 1,
                                         int(d.shift[w]))
        if self.resident[w] > self.cache_pages:
            self._evict_cells(w, int(self.resident[w]) - self.cache_pages)
        return n_miss

    def _danger_replay(self, w: int, d: RegionDirectory, region: int,
                       p_lo: int, p_hi: int,
                       fetch_flag: Optional[np.ndarray], *,
                       is_write: bool) -> int:
        """Vectorized mid-op refetch replay: the exact effects of the
        reference's page-by-page touch/fetch/evict interleave for one
        danger-flagged op, computed analytically as a segmented
        evict-then-refetch schedule instead of a Python loop over pages.

        The key structure (see DIRECTORY.md §refetch schedule): within an
        op the touch front sweeps the op's columns left to right while
        the eviction front consumes the worker's LRU victim stream in
        tick order, and the two interact only at the op's *in-cache
        segments* — maximal column runs of the op range that are cache
        slots of one pre-op touch run (victim order within a run is
        column order, so both fronts traverse a segment the same way).
        When the touch front reaches a segment none of whose cells have
        been evicted yet, touching makes the whole segment stale before
        any eviction can reach it (touching is free — no enters, so the
        eviction front cannot advance).  When at least one cell has been
        evicted, the eviction front is ahead of the touch front inside
        the segment and every touch refetches an evicted cell — an enter
        that (past the watermark) evicts exactly one more victim, keeping
        the front ahead: the WHOLE segment evicts-then-refetches.  The
        schedule therefore resolves per segment, not per page: cold cells
        and refetched segments contribute enters in bulk, victims are
        consumed from the LRU queue run-by-run (rank-select over each
        run's live mask — ``directory.take_upto_row``, packed
        ``take_first_k``/``kth_set_index`` kernels on 'pallas'), and once
        the pre-op stream is exhausted the op consumes its own oldest
        touched columns (a prefix, since op ticks ascend with columns).

        ``fetch_flag`` marks which pages charge a fetch when invalid at
        touch time (None = all; writes pass the partial-page mask).
        Returns the fetch-miss count — the caller charges the op's fetch
        messages once, like the batch path.  Traffic is identical to the
        scalar walk cell for cell; clock charges group per victim run
        (allclose vs the reference, bit-equal across drivers since both
        run this same code)."""
        C = int(self.cache_pages)
        base = int(d.base[w])
        c0 = int(p_lo) - base
        n = int(p_hi) - int(p_lo)
        s = slice(c0, c0 + n)
        incache0 = d.incache[w, s].copy()
        valid0 = d.valid[w, s].copy()
        dirty0 = d.dirty[w, s].copy()
        touch0 = d.touch[w, s].copy()
        R0 = int(self.resident[w])
        slack = C - R0
        q = self._lru_q[w]
        pb = self.page_bytes

        # maximal op segments of constant (in-cache, owning run): cold
        # cells key to -1, in-cache cells to their touch tick
        key = np.where(incache0, touch0, np.int64(-1))
        cuts = np.flatnonzero(np.diff(key)) + 1
        seg_lo = np.concatenate(([0], cuts))
        seg_hi = np.concatenate((cuts, [n]))

        evicted_pre = np.zeros(n, bool)   # evicted before their touch
        touch_front = 0
        qi = 0                            # victim stream cursor: run index
        roff = int(q[0][4]) if q else 0   # ... and scan offset within it
        rec = self._danger_rec            # shared-schedule leader run

        def consume(k: int) -> int:
            """Consume k victims from the pre-op stream in tick order,
            applying eviction effects; returns the shortfall once the
            stream is exhausted (consumed from the op's own cells)."""
            nonlocal qi, roff
            while k > 0 and qi < len(q):
                run = q[qi]
                t0r, rg, col0, nr = run[0], run[1], run[2], run[3]
                if roff >= nr:
                    qi += 1
                    roff = int(q[qi][4]) if qi < len(q) else 0
                    continue
                dr = self.dirs[rg]
                cc0 = col0 + (int(dr.shift[w]) - run[5])
                a, b = cc0 + roff, cc0 + nr
                in_op = dr is d and a < c0 + n and b > c0
                if run[6] and not in_op:
                    # pristine, outside the op: a contiguous live prefix
                    take = min(k, nr - roff)
                    if rec is not None:
                        rec["events"].append((qi, np.arange(roff,
                                                            roff + take)))
                    self._evict_now(w, dr, np.arange(a, a + take))
                    k -= take
                    roff += take
                    continue
                live = (np.ones(b - a, bool) if run[6]
                        else (dr.touch[w, a:b] == t0r) & dr.incache[w, a:b])
                if in_op:
                    # cells of the op range already touched are the
                    # newest copies — never pre-op victims
                    opj = np.arange(a - c0, b - c0)
                    stale = (opj >= 0) & (opj < n) & (opj < touch_front)
                    live &= ~stale
                tot = int(live.sum())
                if tot <= k:
                    vc = np.flatnonzero(live) + a
                    if vc.size:
                        if rec is not None:
                            rec["events"].append((qi, vc - cc0))
                        self._evict_now(w, dr, vc)
                        if in_op:
                            ej = vc - c0
                            ej = ej[(ej >= 0) & (ej < n)]
                            evicted_pre[ej] = True
                    k -= tot
                    roff = nr
                    continue
                take_mask, cut = dr.take_upto_row(live, k)
                vc = np.flatnonzero(take_mask) + a
                if rec is not None:
                    rec["events"].append((qi, vc - cc0))
                self._evict_now(w, dr, vc)
                if in_op:
                    ej = vc - c0
                    ej = ej[(ej >= 0) & (ej < n)]
                    evicted_pre[ej] = True
                roff += cut
                k = 0
            return k

        enters = 0
        ev_done = 0
        own_done = 0
        for j0, j1 in zip(seg_lo, seg_hi):
            j0, j1 = int(j0), int(j1)
            if incache0[j0] and not evicted_pre[j0]:
                touch_front = j1          # stale touches: no enters
                continue
            # cold cells, or an in-cache segment whose prefix was already
            # evicted (the refetch cascade claims the whole segment)
            enters += j1 - j0
            target = enters - slack
            if target > ev_done:
                own_done += consume(target - ev_done)
                ev_done = target
            touch_front = j1

        # fetch misses: every cell invalid at its touch (never valid, or
        # evicted mid-op) whose page charges a fetch
        miss = ~valid0 | evicted_pre
        if fetch_flag is not None:
            miss &= fetch_flag
        n_miss = int(miss.sum())
        if n_miss and self.protocol != IDEAL_PROTO:
            self.traffic.page_fetches += n_miss
            self.traffic.fetch_bytes += n_miss * pb

        # final plane state of the op range, then the op's own oldest
        # columns consumed once the stream ran dry (always a prefix — op
        # ticks ascend with columns) evict through the shared `_evict_now`
        # effect sequence, reading their post-touch dirty state (write ops
        # just marked them dirty) straight off the planes
        d.valid[w, s] = True
        d.incache[w, s] = True
        if is_write:
            d.dirty[w, s] = True
            d.maybe_dirty = True
            self._dirty_regions[w].add(region)
        else:
            d.dirty[w, s] = dirty0 & ~evicted_pre
        assert own_done < n, (own_done, n)
        if rec is not None:
            rec.update(qi=qi, roff=roff, evicted_pre=evicted_pre,
                       enters=enters, own_done=own_done, n_miss=n_miss)
        if own_done:
            self._evict_now(w, d, np.arange(c0, c0 + own_done))

        # queue: drop fully-consumed front runs, advance the partial one,
        # append the op's own touch run (its consumed prefix starts dead)
        for _ in range(min(qi, len(q))):
            q.popleft()
        if q:
            if roff >= q[0][3]:       # cursor drained the run exactly
                q.popleft()
            else:
                q[0][4] = roff
        tick = self._q_append(w, region, c0, n, int(d.shift[w]))
        d.touch[w, s] = tick
        if own_done:
            q[-1][4] = own_done
        self.resident[w] += enters     # _evict_now debited every victim
        assert int(self.resident[w]) == min(R0 + enters, C), (
            self.resident[w], R0, enters, C)
        return n_miss

    _DANGER_SHARE_CELLS = 1 << 18

    def _danger_shared(self, rows: np.ndarray, d: RegionDirectory,
                       region: int, ga, lo: np.ndarray, hi: np.ndarray,
                       p_lo: np.ndarray, p_hi: np.ndarray, *,
                       is_write: bool) -> bool:
        """Dedupe lockstep-uniform danger workers into ONE shared
        evict-then-refetch schedule (the rotating-spill steady state:
        every flagged worker's cache state is the same picture shifted to
        its own window).

        Soundness is checked, not assumed: the workers must be
        *isomorphic* — same op geometry, same pre-op valid/incache/dirty
        (and wprot) patterns over the op range, same touch-run boundary
        structure, and structurally identical LRU queues (same run
        lengths/offsets/pristine flags, uniform run-to-op offsets in the
        op's region, identical live and dirty patterns over every run the
        schedule could consume — walked until the guaranteed victim
        supply covers the op's maximal demand).  When the check fails the
        caller falls back to per-worker replays; when it passes, the
        leader runs the ordinary ``_danger_replay`` once with its
        eviction schedule recorded, and every other row applies the
        recorded schedule as batched plane ops with the per-worker charge
        sequence replicated term for term — bit-equal to having replayed
        each worker.  ``stats['danger_shared_ops']`` counts the absorbed
        ops."""
        R = int(rows.size)
        w0 = int(rows[0])
        pw = self.page_words
        L = p_hi[rows] - p_lo[rows]
        n = int(L[0])
        if not (L == n).all() or n == 0:
            return False
        if is_write:
            # uniform page phase => uniform partial-page fetch mask
            if (not (lo[rows] % pw == int(lo[w0]) % pw).all()
                    or not (hi[rows] % pw == int(hi[w0]) % pw).all()
                    or not (hi[rows] - lo[rows]
                            == int(hi[w0]) - int(lo[w0])).all()):
                return False
        if not (self.resident[rows] == self.resident[w0]).all():
            return False
        qs = [self._lru_q[int(w)] for w in rows]
        qlen = len(qs[0])
        if any(len(q) != qlen for q in qs[1:]) or qlen == 0:
            return False
        if not (self._q_degraded[rows] == self._q_degraded[w0]).all():
            return False
        d.ensure_rows(p_lo[rows], p_hi[rows], rows)
        c0 = (p_lo[rows] - d.base[rows]).astype(np.int64)
        ri = rows[:, None]
        colmat = c0[:, None] + np.arange(n)[None, :]
        inc0 = d.incache[ri, colmat]
        val0 = d.valid[ri, colmat]
        dir0 = d.dirty[ri, colmat]
        if ((inc0 != inc0[0]).any() or (val0 != val0[0]).any()
                or (dir0 != dir0[0]).any()):
            return False
        if n > 1:
            t0 = d.touch[ri, colmat]
            if ((np.diff(t0, axis=1) != 0)
                    != (np.diff(t0[0]) != 0)[None, :]).any():
                return False
        wp_faults = 0
        if self._track_wprot:
            wp0 = d.wprot[ri, colmat]
            if (wp0 != wp0[0]).any():
                return False
            wp_faults = int(wp0[0].sum())

        # --- queue walk: verify every run the schedule could consume.
        # The op demands at most n victims; a run's GUARANTEED supply is
        # its live cells outside the op range (in-op cells may go stale
        # first), so once the cumulative guaranteed supply reaches n the
        # schedule provably never looks further.
        need = n
        cum = 0
        cells = n * R
        run_info = []               # per run: (region, members' cc0)
        for j in range(qlen):
            metas = [q[j] for q in qs]
            m0 = metas[0]
            rg, nr, off, pris = m0[1], m0[3], m0[4], m0[6]
            for mm in metas[1:]:
                if (mm[1] != rg or mm[3] != nr or mm[4] != off
                        or mm[6] != pris):
                    return False
            dr = self.dirs[rg]
            cc0 = np.array(
                [metas[i][2] + (int(dr.shift[rows[i]]) - metas[i][5])
                 for i in range(R)], np.int64)
            if rg == region and not ((cc0 - c0) == (cc0[0] - c0[0])).all():
                return False
            run_info.append((rg, cc0))
            ln = nr - off
            if ln <= 0:
                continue
            cells += ln * R
            if cells > self._DANGER_SHARE_CELLS:
                return False
            cm = cc0[:, None] + np.arange(off, nr)[None, :]
            dm = dr.dirty[ri, cm]
            if (dm != dm[0]).any():
                return False
            if rg == region:
                cols0 = cc0[0] + np.arange(off, nr)
                outside = (cols0 < c0[0]) | (cols0 >= c0[0] + n)
            else:
                outside = None
            if pris:
                cum += int(outside.sum()) if outside is not None else ln
            else:
                tks = np.array([metas[i][0] for i in range(R)], np.int64)
                lv = (dr.touch[ri, cm] == tks[:, None]) & dr.incache[ri, cm]
                if (lv != lv[0]).any():
                    return False
                cum += int((lv[0] & outside).sum() if outside is not None
                           else lv[0].sum())
            if cum >= need:
                break

        # --- leader runs the ordinary replay, recording the schedule
        self._danger_rec = rec = {"events": []}
        try:
            if is_write:
                self.write(w0, ga, int(lo[w0]), int(hi[w0]))
            else:
                self.read(w0, ga, int(lo[w0]), int(hi[w0]))
        finally:
            self._danger_rec = None
        self._danger_apply(rows, d, region, lo, hi, p_lo, p_hi, rec,
                           run_info, c0, colmat, dir0[0],
                           wp_faults, is_write=is_write)
        # members resolve vectorized too (the leader's read/write call
        # counted itself): danger_vec semantics — and the committed
        # per-row bench counters — are unchanged by sharing
        self.stats["danger_vec_ops"] += R - 1
        self.stats["danger_shared_ops"] += R
        return True

    def _danger_apply(self, rows: np.ndarray, d: RegionDirectory,
                      region: int, lo, hi, p_lo, p_hi, rec: dict,
                      run_info, c0: np.ndarray, colmat: np.ndarray,
                      dirty0: np.ndarray, wp_faults: int, *,
                      is_write: bool):
        """Apply the leader's recorded schedule to the other isomorphic
        rows as batched plane ops, replicating the per-worker charge
        sequence term for term (see _danger_shared)."""
        m = rows[1:]
        R = int(m.size)
        mi = m[:, None]
        cm_op = colmat[1:]
        n = int(p_hi[rows[0]] - p_lo[rows[0]])
        pb = self.page_bytes
        lat = self.cost.net_latency_s
        bwd = self.cost.net_bw_Bps

        if is_write:
            # write()'s pre-danger charges: instrumented stores, then
            # write faults (wprot cleared over the range)
            if self.model_mechanism and self.protocol == FINE_PROTO:
                self.clock[m] += ((int(hi[rows[0]]) - int(lo[rows[0]]))
                                  * self.instr_s_per_word)
            if self._track_wprot:
                self.clock[m] += wp_faults * self.fault_s
                d.wprot[mi, cm_op] = False
            d.note_dirty(m, p_lo[m], p_hi[m])

        def evict_cols(dr, cols):
            dm = dr.dirty[mi, cols]
            db = int(dm[0].sum())
            assert (dm.sum(axis=1) == db).all(), "isomorphism violated"
            if db:
                r_i, c_i = np.nonzero(dm)
                dr.dirty[m[r_i], cols[r_i, c_i]] = False
                self.traffic.writeback_bytes += db * pb * R
                self.clock[m] += (lat * db + db * pb / bwd)
                if self.chaos is not None:
                    self.clock[m] += self.chaos.retry_rows(m)
                if dr.wprot is not None:
                    dr.wprot[m[r_i], cols[r_i, c_i]] = True
                # sharer invalidation is a proven no-op here: shared
                # danger rows come from the independent set, whose dirty
                # victims no other worker's reach intersects
            dr.valid[mi, cols] = False
            dr.incache[mi, cols] = False
            self.resident[m] -= cols.shape[1]

        for qi_ev, rel in rec["events"]:
            rg, cc0 = run_info[qi_ev]
            evict_cols(self.dirs[rg], cc0[1:][:, None] + rel[None, :])

        # fetch-miss traffic + the op's final plane state
        n_miss = rec["n_miss"]
        if n_miss:
            self.traffic.page_fetches += n_miss * R
            self.traffic.fetch_bytes += n_miss * pb * R
        d.valid[mi, cm_op] = True
        d.incache[mi, cm_op] = True
        if is_write:
            d.dirty[mi, cm_op] = True
            d.maybe_dirty = True
            for w in m:
                self._dirty_regions[w].add(region)
        else:
            d.dirty[mi, cm_op] = (dirty0 & ~rec["evicted_pre"])[None, :]
        own_done = rec["own_done"]
        if own_done:
            evict_cols(d, cm_op[:, :own_done])

        # queue cleanup + the op's own touch run, per row (deques are
        # per-row Python state; O(consumed runs) each)
        qi, roff = rec["qi"], rec["roff"]
        ticks = np.empty(R, np.int64)
        for i, w in enumerate(m):
            q = self._lru_q[w]
            for _ in range(min(qi, len(q))):
                q.popleft()
            if q:
                if roff >= q[0][3]:
                    q.popleft()
                else:
                    q[0][4] = roff
            ticks[i] = self._q_append(int(w), region, int(c0[1 + i]), n,
                                      int(d.shift[w]))
            if own_done:
                q[-1][4] = own_done
        d.touch[mi, cm_op] = ticks[:, None]
        enters = rec["enters"]
        self.resident[m] += enters
        C = int(self.cache_pages)
        assert (self.resident[m] == min(int(self.resident[rows[0]]), C)
                ).all(), "isomorphism violated (resident)"

        # the op's fetch messages, once per worker (read/write charge
        # these after _danger_replay returns)
        if n_miss:
            self.clock[m] += self.cost.xfer_s(
                n_miss * pb, 2 * -(-n_miss // self.fetch_batch))
            if self.chaos is not None:
                self.clock[m] += self.chaos.retry_rows(m)

    def _danger_sig(self, w: int, d: RegionDirectory, lo, hi,
                    p_lo, p_hi, *, is_write: bool) -> tuple:
        """Cheap per-row isomorphism-class key for ``_danger_subgroups``:
        op geometry, occupancy, op-range plane patterns, and the LRU
        queue's run structure (op-region runs keyed by their column
        offset relative to the op — the shift-invariant part of the
        ``_danger_shared`` contract).  Rows with equal keys are only
        *candidates*: ``_danger_shared`` still re-verifies every
        cross-row condition (run dirty/live patterns, the cell budget)
        before any schedule is shared."""
        pw = self.page_words
        p0, p1 = int(p_lo[w]), int(p_hi[w])
        n = p1 - p0
        s = d.sl(w, p0, p1)
        sig: list = [n, int(self.resident[w]), bool(self._q_degraded[w])]
        if is_write:
            sig += [int(lo[w]) % pw, int(hi[w]) % pw,
                    int(hi[w]) - int(lo[w])]
        sig.append(d.incache[w, s].tobytes())
        sig.append(d.valid[w, s].tobytes())
        sig.append(d.dirty[w, s].tobytes())
        if n > 1:
            sig.append((np.diff(d.touch[w, s]) != 0).tobytes())
        if self._track_wprot:
            sig.append(d.wprot[w, s].tobytes())
        c0 = p0 - int(d.base[w])
        for _t0, rg, col0, nr, off, _shift0, pris in self._lru_q[w]:
            cc = col0 + (int(self.dirs[rg].shift[w]) - _shift0)
            sig.append((rg, nr, off, bool(pris),
                        cc - c0 if rg == d.region else -(1 << 30)))
        return tuple(sig)

    def _danger_subgroups(self, drows: np.ndarray, d: RegionDirectory,
                          ga, lo, hi, p_lo, p_hi, *,
                          is_write: bool) -> np.ndarray:
        """The packed multi-row victim scan for danger groups that are
        almost-but-not-quite isomorphic: when the whole-group
        ``_danger_shared`` check fails (typically one clamped or
        phase-skewed row breaking an otherwise-lockstep group),
        partition the rows into candidate classes by ``_danger_sig``
        and let every class of >= 2 rows attempt the shared schedule on
        its own.  Only rows whose class is a singleton — or fails the
        full cross-row re-verification — drop to per-worker replay.
        Returns those residual rows, ascending.  Exact for the same
        reason the split itself is: the rows are proven independent, so
        subgroup replay order is interchangeable, and each subgroup's
        shared schedule is bit-equal to its per-worker replays."""
        groups: Dict[tuple, List[int]] = {}
        d.ensure_rows(p_lo[drows], p_hi[drows], drows)
        for w in drows.tolist():
            groups.setdefault(self._danger_sig(w, d, lo, hi, p_lo, p_hi,
                                               is_write=is_write),
                              []).append(w)
        resid: List[int] = []
        for ws in groups.values():
            grp = np.asarray(ws, np.int64)
            # a class spanning the whole group IS the attempt that just
            # failed — re-running it cannot succeed
            if (2 <= grp.size < drows.size
                    and self._danger_shared(grp, d, d.region, ga, lo, hi,
                                            p_lo, p_hi,
                                            is_write=is_write)):
                self.stats["danger_subgroup_ops"] += int(grp.size)
                continue
            resid.extend(ws)
        resid.sort()
        return np.asarray(resid, np.int64)

    def _maybe_evict(self, w: int):
        """Watermark-triggered batched eviction: no per-op work unless the
        occupancy counter crossed ``cache_pages``; then the oldest pages
        (exact LRU via monotone ticks) are evicted in one queue pass."""
        if self.cache_pages is None or self.resident[w] <= self.cache_pages:
            return
        self._evict_cells(w, int(self.resident[w]) - self.cache_pages)

    # ------------------------------------------------------------------
    # reads / writes (interval API)
    # ------------------------------------------------------------------

    def read(self, w: int, ga: GasArray, lo: int, hi: int):
        region = self._region_of(ga.page_lo)
        p_lo = ga.page_lo + lo // self.page_words
        p_hi = ga.page_lo + (max(hi - 1, lo)) // self.page_words + 1
        if self.detect_races and not self._race_suspend:
            # the DECLARED range only — prefetch is a cache artifact, not
            # an access, so it must not create happens-before obligations
            self._race_access(w, region, p_lo, p_hi, False)
        arr_end = ga.page_lo + -(-ga.n_elems // self.page_words)
        p_hi_pf = min(p_hi + self.prefetch, arr_end)   # sequential prefetch
        p_hi = max(p_hi_pf, p_hi)
        if self.cache_pages is not None:
            d = self.dirs[region]
            d.ensure(w, p_lo, p_hi)
            s = d.sl(w, p_lo, p_hi)
            n = p_hi - p_lo
            n_enter = n - int(d.incache[w, s].sum())
            if self._danger(w, n_enter, n):
                if self.danger_mode == "vec" and self.cache_pages >= 1:
                    self.stats["danger_vec_ops"] += 1
                    n_miss = self._danger_replay(w, d, region, p_lo, p_hi,
                                                 None, is_write=False)
                else:
                    self.stats["danger_scalar_ops"] += 1
                    n_miss = 0
                    for p in range(p_lo, p_hi):
                        n_miss += self._touch_page_exact(w, d, p, fetch=True)
                if n_miss:
                    self._net(w, n_miss * self.page_bytes,
                              2 * -(-n_miss // self.fetch_batch))
                return None
        self._fetch_range(w, region, p_lo, p_hi)
        self._maybe_evict(w)
        return None

    def write(self, w: int, ga: GasArray, lo: int, hi: int, values=None):
        region = self._region_of(ga.page_lo)
        p_lo = ga.page_lo + lo // self.page_words
        p_hi = ga.page_lo + (max(hi - 1, lo)) // self.page_words + 1
        if self.detect_races and not self._race_suspend:
            self._race_access(w, region, p_lo, p_hi, True)
        d = self.dirs[region]
        d.ensure(w, p_lo, p_hi)
        in_span = bool(self.spans[w])
        if not in_span:
            d.note_dirty(w, p_lo, p_hi)
        n_words = hi - lo

        # mechanism cost: instrumented stores (fine) / write faults (page)
        if self.model_mechanism and self.protocol == FINE_PROTO:
            self.clock[w] += n_words * self.instr_s_per_word
        if self._track_wprot:
            s = d.sl(w, p_lo, p_hi)
            n_faults = int(d.wprot[w, s].sum())
            self.clock[w] += n_faults * self.fault_s
            d.wprot[w, s] = False

        if self.cache_pages is not None and self.protocol != IDEAL_PROTO:
            s = d.sl(w, p_lo, p_hi)
            n = p_hi - p_lo
            n_enter0 = n - int(d.incache[w, s].sum())
            if self._danger(w, n_enter0, n):
                if (self.danger_mode == "vec" and self.cache_pages >= 1
                        and not in_span):
                    # danger-flagged in-span writes keep the exact
                    # per-page LRU walk (critical sections touch few
                    # pages; their intervals still land in the span
                    # planes in one note after the walk)
                    self.stats["danger_vec_ops"] += 1
                    pages = np.arange(p_lo, p_hi)
                    bw_ = (pages - ga.page_lo) * self.page_words
                    wlo_v = np.maximum(lo - bw_, 0)
                    whi_v = np.minimum(hi - bw_, self.page_words)
                    n_miss = self._danger_replay(
                        w, d, region, p_lo, p_hi,
                        (whi_v - wlo_v) < self.page_words, is_write=True)
                    if n_miss:
                        self._net(w, n_miss * self.page_bytes,
                                  2 * -(-n_miss // self.fetch_batch))
                    return
                # exact per-page replica of the reference's write-allocate +
                # LRU sequence (see _danger)
                self.stats["danger_scalar_ops"] += 1
                span = self.spans[w][-1] if in_span else None
                base = int(d.base[w])
                n_miss = 0
                for p in range(p_lo, p_hi):
                    wlo, whi = ga.word_range_in_page(p, lo, hi)
                    n_miss += self._touch_page_exact(
                        w, d, p, fetch=(whi - wlo) < self.page_words)
                    if in_span:
                        if not span.plane:
                            old = span.touched.get(p)
                            span.touched[p] = ((min(wlo, old[0]),
                                                max(whi, old[1]))
                                               if old else (wlo, whi))
                    else:
                        d.dirty[w, p - base] = True
                        d.maybe_dirty = True
                        self._dirty_regions[w].add(region)
                if in_span and span.plane:
                    # interval merge is order-insensitive and eviction
                    # never reads the span planes, so one note after the
                    # exact per-page walk is equivalent
                    self._span_note(w, span, d, region, ga, lo, hi,
                                    p_lo, p_hi)
                if n_miss:
                    self._net(w, n_miss * self.page_bytes,
                              2 * -(-n_miss // self.fetch_batch))
                return

        # write-allocate: partial edge pages must be fetched; interior
        # full-page writes just become valid
        if self.protocol != IDEAL_PROTO:
            if p_hi - p_lo == 1:
                if n_words < self.page_words:
                    self._fetch_range(w, region, p_lo, p_lo + 1)
            else:
                if lo % self.page_words != 0:
                    self._fetch_range(w, region, p_lo, p_lo + 1)
                if hi % self.page_words != 0:
                    self._fetch_range(w, region, p_hi - 1, p_hi)
        s = d.sl(w, p_lo, p_hi)
        n = p_hi - p_lo
        n_new = n - int(d.valid[w, s].sum())
        if d.touch is not None:
            d.touch[w, s] = self._q_append(w, region, s.start, n,
                                           int(d.shift[w]))
            n_enter = n - int(d.incache[w, s].sum())
            if n_enter:
                d.incache[w, s] = True
                self.resident[w] += n_enter
        if n_new:
            d.valid[w, s] = True

        if in_span:
            span = self.spans[w][-1]
            if span.plane:
                self._span_note(w, span, d, region, ga, lo, hi, p_lo, p_hi)
            else:
                for p in range(p_lo, p_hi):
                    wlo, whi = ga.word_range_in_page(p, lo, hi)
                    old = span.touched.get(p)
                    span.touched[p] = ((min(wlo, old[0]), max(whi, old[1]))
                                       if old else (wlo, whi))
        else:
            d.dirty[w, s] = True
            d.maybe_dirty = True
            self._dirty_regions[w].add(region)
        self._maybe_evict(w)

    # ------------------------------------------------------------------
    # ordinary flush (page granularity in both protocols)
    # ------------------------------------------------------------------

    def _invalidate_sharers(self, w: int, region: int, pages: np.ndarray):
        """Invalidate every other worker's valid copy of ``pages``.

        Small page sets (accumulator pages, many overlapping rows) use one
        dense boolean-mask gather over the worker axis; wide page sets
        (block flushes — few overlapping neighbours, thousands of pages)
        intersect each row's window with the sorted page list instead, so
        work tracks actual coverage rather than rows x pages."""
        d = self.dirs[region]
        rows = d.overlap_rows(int(pages[0]), int(pages[-1]) + 1, exclude=w)
        if rows.size == 0:
            return
        if pages.size <= 64:
            hit, cols = d.gather_valid(rows, pages)
            n_inv = int(hit.sum())
            if n_inv:
                # valid drops but the pages keep their cache slots
                # (``incache``) until evicted, like the reference's LRU dict
                d.clear_valid_cells(rows, cols, hit)
                self.traffic.invalidations += n_inv
                self.traffic.control_msgs += n_inv
                if self.chaos is not None:
                    self.chaos.inval_msgs(n_inv)
            return
        n_inv = 0
        for v in rows:
            b = int(d.base[v])
            i0 = int(np.searchsorted(pages, b))
            i1 = int(np.searchsorted(pages, b + int(d.length[v])))
            if i0 >= i1:
                continue
            cols = pages[i0:i1] - b
            vcells = d.valid[v, cols]
            k = int(vcells.sum())
            if k:
                d.valid[v, cols[vcells]] = False
                n_inv += k
        if n_inv:
            self.traffic.invalidations += n_inv
            self.traffic.control_msgs += n_inv
            if self.chaos is not None:
                self.chaos.inval_msgs(n_inv)

    def _flush_worker(self, w: int):
        """Write back + invalidate sharers for all of w's ordinary-dirty
        pages (the single-flusher path used by acquire)."""
        regions = self._dirty_regions[w]
        if not regions:
            return
        for region in sorted(regions):
            d = self.dirs[region]
            cols = d.row_dirty_cols(w)
            d.clear_dirty_bounds(w)
            if cols.size == 0:
                continue
            d.dirty[w, cols] = False
            if self.protocol == IDEAL_PROTO:
                continue
            n_dirty = cols.size
            self.traffic.writeback_bytes += n_dirty * self.page_bytes
            self._net(w, n_dirty * self.page_bytes,
                      -(-n_dirty // self.fetch_batch))   # batched writeback
            if d.wprot is not None:
                d.wprot[w, cols] = True     # re-arm write protection
            self._invalidate_sharers(w, region, d.base[w] + cols)
        regions.clear()

    def _flush_all_workers(self, mask: Optional[np.ndarray] = None):
        """Batched flush of every (masked) worker's ordinary-dirty pages,
        in one pass per region that reproduces the sequential flush-order
        semantics analytically (see DIRECTORY.md):

        for a page with dirty-worker set D (flushed in worker order) and
        initial valid set V, the sequential per-worker flushes produce
        ``|V \\ {d0}| + [|D|>1]*[d0 in V]`` invalidations and leave the page
        valid only at d0 when ``|D|==1``.  Pages covered by a single worker
        window contribute nothing (their only possible sharer is their own
        writer), so the gather runs only over multiply-covered pages.

        ``mask`` restricts the flush to a (W,) bool subset of workers —
        span_all's hoisted flush phase; unmasked workers' dirty state and
        bounds are left untouched.  ``None`` flushes everyone (barrier).
        Charge expressions equal the single-worker ``_flush_worker`` term
        for term, so hoisting a worker's flush out of its acquire keeps
        clocks bit-equal to the per-worker span loop.
        """
        mrows = None if mask is None else np.nonzero(mask)[0]
        # 'pallas-jit': run the whole flush chain — per-row popcount,
        # shared-interval coverage stab, sharer-candidate mask — for ALL
        # dirty regions as ONE fused device dispatch, then consume its
        # outputs region by region below.  Charging, wprot re-arm and the
        # analytic invalidation stay host-side (they are cheap and carry
        # the exactness contract), so traffic/clocks are bit-equal to the
        # unfused path by construction.  IDEAL skips sharer work entirely
        # and keeps the short-circuit path.
        jit_counts = jit_shared = None
        ji = 0
        if self.backend == "pallas-jit" and self.protocol != IDEAL_PROTO:
            cand = [d for d in self.dirs if d.maybe_dirty and d.cap > 0]
            if cand:
                jit_counts, jit_shared = self._jit_flush_chain(cand, mask)
        for d in self.dirs:
            if not d.maybe_dirty:
                continue
            if jit_counts is not None and d.cap > 0:
                nD_w = jit_counts[ji]      # fused chain output
                sub_bits = jit_shared[ji]
                ji += 1
            else:
                nD_w = d.dirty_counts()    # bitmask popcount on 'pallas'
                sub_bits = None
            if mask is not None:
                rest = int(nD_w[~mask].sum())
                nD_w = np.where(mask, nD_w, 0)
            total = int(nD_w.sum())
            d.maybe_dirty = False if mask is None else rest > 0
            d.clear_dirty_bounds(mrows)
            if total == 0:
                continue
            if self.protocol == IDEAL_PROTO:
                if mask is None:
                    d.dirty[:] = False
                else:
                    d.dirty[mrows] = False
                continue
            active = np.nonzero(nD_w)[0]
            # per-(worker, region) writeback charge, as in the sequential
            # flush: one batched message group per worker window
            self.traffic.writeback_bytes += total * self.page_bytes
            msgs = -(-nD_w[active] // self.fetch_batch)
            self.clock[active] += (self.cost.net_latency_s * msgs
                                   + (nD_w[active] * self.page_bytes)
                                   / self.cost.net_bw_Bps)
            if self.chaos is not None:
                self.clock[active] += self.chaos.retry_rows(active)
            if d.wprot is not None:
                if mask is None:
                    np.logical_or(d.wprot, d.dirty, out=d.wprot)  # re-arm own
                else:
                    d.wprot[active] |= d.dirty[active]
            # sharer invalidation: only pages under >= 2 worker windows can
            # have sharers, so per-cell work is confined to the (small)
            # halo/global intervals instead of every dirty page
            if sub_bits is not None:
                # fused chain already intersected dirty & multi-coverage &
                # active-row on device; row-major nonzero over the active
                # rows reproduces the sequential worker-major /
                # column-ascending flush order exactly
                from repro.kernels.protocol_sweep import unpack_mask_rows
                sub = unpack_mask_rows(sub_bits[active], int(d.cap))
                ai, cols = np.nonzero(sub)
                if ai.size:
                    self._invalidate_shared_dirty(
                        d, active[ai].astype(np.int64),
                        cols.astype(np.int64))
            else:
                starts, ends = d.shared_intervals()
                if starts.size:
                    w_list, col_list = [], []
                    for w in active:
                        b = int(d.base[w])
                        e = b + int(d.length[w])
                        i0 = int(np.searchsorted(ends, b, "right"))
                        i1 = int(np.searchsorted(starts, e, "left"))
                        for i in range(i0, i1):
                            lo = max(int(starts[i]), b)
                            hi = min(int(ends[i]), e)
                            if lo >= hi:
                                continue
                            c = np.nonzero(d.dirty[w, lo - b:hi - b])[0]
                            if c.size:
                                col_list.append(c + (lo - b))
                                w_list.append(np.full(c.size, w, np.int64))
                    if col_list:
                        w_idx = np.concatenate(w_list)  # ascending worker
                        cols = np.concatenate(col_list)  # == seq. order
                        self._invalidate_shared_dirty(d, w_idx, cols)
            if mask is None:
                d.dirty[:] = False
            else:
                d.dirty[active] = False
        if mask is None:
            for regions in self._dirty_regions:
                regions.clear()
        else:
            for w in mrows:
                self._dirty_regions[w].clear()

    def _jit_flush_chain(self, cand, mask: Optional[np.ndarray]):
        """Stack every dirty region's packed dirty plane + cached int32
        window geometry into one (R, W, nw) batch and run the fused
        barrier-flush chain (``kernels.phase_step``) as a single jitted
        device dispatch.  Returns ``(counts, shared)`` — per-region
        per-row UNMASKED dirty counts (the caller applies ``mask`` for
        the ``rest`` bookkeeping, exactly as the unfused path) and packed
        shared-dirty candidate masks (dirty & >=2-coverage & active row).
        Returns ``(None, None)`` when page ids could overflow the int32
        device arithmetic — the caller falls back to the unfused sweep,
        and ``stats['jit_flush_fallbacks']`` counts it."""
        from repro.kernels import protocol_sweep as _ps
        R, W = len(cand), self.W
        nw_max = max(-(-int(d.cap) // 32) for d in cand)
        # page = base + col with col < nw_max*32; bound it in int32 (pads
        # are INT32_MAX and must stay strictly above every probed page)
        if max(int(d.page_hi) for d in cand) + nw_max * 32 >= (1 << 31) - 1:
            self.stats["jit_flush_fallbacks"] = (
                self.stats.get("jit_flush_fallbacks", 0) + 1)
            return None, None
        i32max = np.iinfo(np.int32).max
        bits = np.zeros((R, W, nw_max), np.uint32)
        base32 = np.empty((R, W), np.int32)
        sbs = np.full((R, W), i32max, np.int32)
        ses = np.full((R, W), i32max, np.int32)
        for i, d in enumerate(cand):
            pk = _ps.pack_mask_rows(d.dirty)
            bits[i, :, :pk.shape[1]] = pk
            b32, sb, se = d.jit_geometry()
            base32[i] = b32
            sbs[i, :sb.size] = sb
            ses[i, :se.size] = se
        rowmask = (np.ones((R, W), bool) if mask is None
                   else np.broadcast_to(mask, (R, W)))
        counts, shared = _ps.phase_step(bits, base32, rowmask, sbs, ses,
                                        stats=self.stats)
        return counts, shared

    def _invalidate_shared_dirty(self, d: RegionDirectory,
                                 w_idx: np.ndarray, cols: np.ndarray):
        """Apply the analytic sequential-flush invalidation to the dirty
        cells (worker-major order) of multiply-covered pages.

        The gather is sparse: worker windows are intervals, so each row
        sees only a contiguous slice of the page list ``u`` — total
        (row, page) pairs ~ the actual window coverage, not rows x pages
        (a dense gather over block-partitioned arrays touches W x |u|
        cells to find ~2 live ones per page)."""
        pages = d.base[w_idx] + cols
        u, first, counts = np.unique(pages, return_index=True,
                                     return_counts=True)
        d0_rows = w_idx[first]                # min dirty worker per page
        d0_valid = d.valid[d0_rows, cols[first]]
        rows = d.overlap_rows(int(u[0]), int(u[-1]) + 1)
        pr_l, pu_l, pc_l = [], [], []
        for w in rows:
            b = int(d.base[w])
            i0 = int(np.searchsorted(u, b))
            i1 = int(np.searchsorted(u, b + int(d.length[w])))
            if i0 < i1:
                pr_l.append(np.full(i1 - i0, w, np.int64))
                pu_l.append(np.arange(i0, i1))
                pc_l.append(u[i0:i1] - b)
        pr = np.concatenate(pr_l)             # pair: worker row
        pu = np.concatenate(pu_l)             # pair: index into u
        pc = np.concatenate(pc_l)             # pair: column in row
        val = d.valid[pr, pc]
        nV0 = np.bincount(pu[val], minlength=u.size)
        d0v = d0_valid.astype(np.int64)
        n_inv = int((nV0 - d0v + np.where(counts > 1, d0v, 0)).sum())
        if n_inv:
            self.traffic.invalidations += n_inv
            self.traffic.control_msgs += n_inv
            if self.chaos is not None:
                self.chaos.inval_msgs(n_inv)
        # final valid state: keep only a sole dirty writer's copy
        keep = (counts == 1)[pu] & (pr == d0_rows[pu])
        hot = val & ~keep
        if hot.any():
            d.valid[pr[hot], pc[hot]] = False

    # ------------------------------------------------------------------
    # spans + notice replay
    # ------------------------------------------------------------------

    def _span_note(self, w: int, span: _Span, d: RegionDirectory,
                   region: int, ga, lo: int, hi: int, p_lo: int, p_hi: int):
        """Record one in-span write's per-page word intervals in the span
        planes (plane-tracked spans only): the vectorized replacement for
        the per-page ``span.touched`` dict merge."""
        b = span.bounds.get(region)
        if b is None:
            span.bounds[region] = [p_lo, p_hi]
        else:
            if p_lo < b[0]:
                b[0] = p_lo
            if p_hi > b[1]:
                b[1] = p_hi
        d.ensure_span()
        if p_hi - p_lo == 1:
            wlo, whi = ga.word_range_in_page(p_lo, lo, hi)
            d.span_note(w, p_lo, p_hi, wlo, whi)
            return
        bw_ = (np.arange(p_lo, p_hi) - ga.page_lo) * self.page_words
        d.span_note(w, p_lo, p_hi, np.maximum(lo - bw_, 0),
                    np.minimum(hi - bw_, self.page_words))

    def _replay_invalidate(self, w: int, pages: np.ndarray, rearm: bool):
        """Page-protocol notice replay: invalidate w's valid copies of
        ``pages`` (grouped per region), returning the number invalidated."""
        total = 0
        regions = np.searchsorted(self._region_starts_np, pages, "right") - 1
        for r in np.unique(regions):
            d = self.dirs[int(r)]
            if d.base[w] < 0:
                continue
            pr = pages[regions == r]
            cols = pr - d.base[w]
            inr = (cols >= 0) & (cols < d.length[w])
            vcells = d.valid[w, np.where(inr, cols, 0)] & inr
            n = int(vcells.sum())
            if n:
                hot = cols[vcells]
                d.valid[w, hot] = False
                if rearm and d.wprot is not None:
                    d.wprot[w, hot] = True
                total += n
        return total

    def acquire(self, w: int, lock_id: int):
        lk = self.locks.setdefault(lock_id, _Lock(self.W))
        self._flush_worker(w)                       # RegC rule 1
        self._net(w, 64, 2)
        self.traffic.control_msgs += 2
        self.clock[w] = max(self.clock[w], lk.last_release_time)
        # RegC rule 2, notices coalesced per page (matches reference)
        u, lo_u, hi_u = lk.log.pending(int(lk.seen[w]), lk.version)
        if u.size:
            if self.protocol == FINE_PROTO:
                nbytes = (hi_u - lo_u) * _WORD + self.page_words // 8
                tot = int(nbytes.sum())
                self.traffic.diff_bytes += tot
                self.clock[w] += (self.cost.net_latency_s * u.size
                                  + tot / self.cost.net_bw_Bps)
                if self.chaos is not None:
                    self.clock[w] += self.chaos.retry1(w)
            else:
                n_inv = self._replay_invalidate(
                    w, u, rearm=self.model_mechanism)
                self.traffic.invalidations += n_inv
                self.traffic.control_msgs += int(u.size)
                if self.chaos is not None:
                    self.chaos.inval_msgs(n_inv)
        lk.seen[w] = lk.version
        if self.detect_races and not self._race_suspend:
            # acquire happens-after every release of this lock: join the
            # lock's vector clock into the acquirer's view
            np.maximum(self.race_vc[w], lk.race_vc, out=self.race_vc[w])
        self.spans[w].append(_Span(lock_id, plane=not self.spans[w]))

    def _span_harvest(self, w: int, span: _Span):
        """The release-publish payload of ``span`` — (pages, los, his)
        ascending by page — from the span planes (plane-tracked spans;
        cells reset for the next span) or the per-page dict (nested
        spans).  Region order is page order, so multi-region harvests
        concatenate already sorted."""
        if span.plane:
            parts = [self.dirs[region].span_harvest(w, lo_b, hi_b)
                     for region, (lo_b, hi_b) in sorted(span.bounds.items())]
            if not parts:
                z = np.zeros(0, np.int64)
                return z, z, z
            if len(parts) == 1:
                return parts[0]
            return tuple(np.concatenate([p[i] for p in parts])
                         for i in range(3))
        items = sorted(span.touched.items())
        return (np.array([p for p, _ in items], np.int64),
                np.array([iv[0] for _, iv in items], np.int64),
                np.array([iv[1] for _, iv in items], np.int64))

    def _span_publish(self, w: int, lk: _Lock, pages: np.ndarray,
                      los: np.ndarray, his: np.ndarray):
        """Release-time publish: traffic + ONE batched clock charge for
        the span's coalesced page intervals (the reference charges one
        message per page; the batch groups them — allclose, and bit-equal
        across drivers since every release runs this same code), then one
        log append for the whole version."""
        n = int(pages.size)
        if n:
            if self.protocol == FINE_PROTO:
                tot = (int((his - los).sum()) * _WORD
                       + n * (self.page_words // 8))
                self.traffic.diff_bytes += tot
            else:
                tot = n * self.page_bytes
                self.traffic.writeback_bytes += tot
            self.clock[w] += (self.cost.net_latency_s * n
                              + tot / self.cost.net_bw_Bps)
            if self.chaos is not None:
                self.clock[w] += self.chaos.retry1(w)
        lk.log.append_version(pages, los, his)
        lk.version += 1
        lk.seen[w] = lk.version

    def release(self, w: int, lock_id: int):
        span = self.spans[w].pop()
        assert span.lock == lock_id, "unbalanced lock release"
        lk = self.locks[lock_id]
        if self.protocol != IDEAL_PROTO:
            self._span_publish(w, lk, *self._span_harvest(w, span))
        elif span.plane:
            # IDEAL publishes nothing, but the planes must reset
            for region, (lo_b, hi_b) in span.bounds.items():
                self.dirs[region].span_harvest(w, lo_b, hi_b)
        self._net(w, 64, 1)
        self.traffic.control_msgs += 1
        lk.last_release_time = self.clock[w]
        if self.detect_races and not self._race_suspend:
            # publish the releaser's view into the lock, then open a new
            # epoch so later accesses are not ordered under this release
            np.maximum(lk.race_vc, self.race_vc[w], out=lk.race_vc)
            self.race_vc[w, w] += 1

    class _SpanCtx:
        def __init__(self, rt, w, lock_id):
            self.rt, self.w, self.lock_id = rt, w, lock_id

        def __enter__(self):
            self.rt.acquire(self.w, self.lock_id)

        def __exit__(self, *exc):
            self.rt.release(self.w, self.lock_id)
            return False

    def span(self, w: int, lock_id: int):
        return self._SpanCtx(self, w, lock_id)

    # ------------------------------------------------------------------
    # race detection (detect_races mode; pure observer — touches only
    # race_vc / lock race_vc / the directory race planes / self.races,
    # never traffic, clocks, windows beyond what the op itself ensures,
    # or any protocol plane.  See DIRECTORY.md "Race-detection contract".
    # ------------------------------------------------------------------

    def _race_record(self, p: int, w: int, u: int, kind: str):
        a, b = (w, u) if w < u else (u, w)
        t = (p, a, b, kind)
        if t not in self.races:
            self.races.add(t)
            self.stats["race_" + kind] += 1

    def _race_access(self, w: int, region: int, p_lo: int, p_hi: int,
                     is_write: bool):
        """Check-then-record one worker's declared page range: flag every
        (page, other-worker) recorded epoch not ordered before w's view,
        then stamp w's current epoch into the matching plane.  The check
        is ``RegionDirectory.race_hits`` — row-screened on window overlap
        and recorded maxima, so a quiet check is O(W), not a (W, pages)
        gather."""
        d = self.dirs[region]
        d.ensure_race()
        d.ensure(w, p_lo, p_hi)
        vcw = self.race_vc[w]
        ui, pi = d.race_hits(p_lo, p_hi, vcw, True)
        for u, p in zip(ui.tolist(), pi.tolist()):
            self._race_record(p, w, u, "ww" if is_write else "rw")
        if is_write:
            ui, pi = d.race_hits(p_lo, p_hi, vcw, False)
            for u, p in zip(ui.tolist(), pi.tolist()):
                self._race_record(p, w, u, "rw")
        d.race_note(w, p_lo, p_hi, int(vcw[w]), is_write)

    def _race_op_all(self, ga, lo: np.ndarray, hi: np.ndarray,
                     is_write: bool):
        """Batched detection of one phase op across all workers.  Fast
        path: when the region's recorded-epoch maxima are all ordered
        under the phase's minimum vector-clock view (no cross-phase
        check can fire) and write ranges are pairwise disjoint (no
        same-phase pair), recording collapses to one plane scatter.
        Otherwise fall to the per-worker check — whose result is
        processing-order independent (a peer's current epoch is never
        visible in another row's clock until its next release), so
        op-major here matches the loop driver's worker-major order."""
        pw = self.page_words
        region = self._region_of(ga.page_lo)
        d = self.dirs[region]
        p_lo = ga.page_lo + lo // pw
        p_hi = ga.page_lo + np.maximum(hi - 1, lo) // pw + 1
        vc = self.race_vc
        cross = False
        if d.race_w is not None:
            vcmin = vc.min(axis=0)
            cross = bool((d.race_maxw > vcmin).any())
            if is_write and not cross:
                cross = bool((d.race_maxr > vcmin).any())
        overlap = False
        if is_write and not cross:
            order = np.argsort(p_lo, kind="stable")
            run_hi = np.maximum.accumulate(p_hi[order])[:-1]
            overlap = bool((run_hi > p_lo[order][1:]).any())
        if cross or overlap:
            for w in range(self.W):
                self._race_access(w, region, int(p_lo[w]), int(p_hi[w]),
                                  is_write)
        else:
            d.ensure_race()
            d.ensure_rows(p_lo, p_hi, self._rows_all)
            d.race_note_rows(self._rows_all, p_lo, p_hi,
                             vc.diagonal(), is_write)

    def _race_phase_all(self, reads, writes):
        """End-of-phase batched detection over the declared op ranges —
        vector clocks are static inside a phase and page-granular
        flagging is order independent, so one uniform pass here covers
        every engine path (batched rows, danger rows, shared-schedule
        members, residual replays) exactly once."""
        for ga, lo, hi in reads:
            self._race_op_all(ga, lo, hi, False)
        for ga, lo, hi in writes:
            self._race_op_all(ga, lo, hi, True)

    def _race_span_all(self, rows: np.ndarray, locks: np.ndarray,
                       reads, writes):
        """End-of-span_all detection: replay each lock group's grant
        chain (workers ascending — the engine's grant order in both the
        analytic and serial paths) through the scalar acquire/access/
        release detector.  Group processing order is immaterial: rows
        and lock clocks are disjoint across groups, and cross-group
        same-call accesses can never be happens-before ordered."""
        pw = self.page_words
        vc = self.race_vc
        for lk_id in np.unique(locks[rows]):
            lk = self.locks[int(lk_id)]
            for w in rows[locks[rows] == lk_id].tolist():
                np.maximum(vc[w], lk.race_vc, out=vc[w])
                for ops, is_write in ((reads, False), (writes, True)):
                    for ga, lo, hi in ops:
                        region = self._region_of(ga.page_lo)
                        lo_w, hi_w = int(lo[w]), int(hi[w])
                        p_lo = ga.page_lo + lo_w // pw
                        p_hi = ga.page_lo + max(hi_w - 1, lo_w) // pw + 1
                        self._race_access(w, region, p_lo, p_hi, is_write)
                np.maximum(lk.race_vc, vc[w], out=lk.race_vc)
                vc[w, w] += 1

    @property
    def race_counts(self) -> Dict[str, int]:
        return {"race_ww": self.stats["race_ww"],
                "race_rw": self.stats["race_rw"]}

    # ------------------------------------------------------------------
    # batched SPMD driver fast path
    # ------------------------------------------------------------------

    def phase(self, w: int, reads=(), writes=(), *, flops: float = 0.0,
              mem_bytes: float = 0.0, seconds: float = 0.0,
              instr_words: float = 0.0):
        """One worker-phase in a single runtime call: interval reads, then
        interval writes, then the modeled compute + instrumented stores.
        ``reads``/``writes`` are sequences of ``(ga, lo, hi)``.  This is
        the per-worker reference path that ``phase_all`` batches over the
        worker axis (and through which it replays the residual
        interacting workers of eviction-capable phases)."""
        for ga, lo, hi in reads:
            self.read(w, ga, lo, hi)
        for ga, lo, hi in writes:
            self.write(w, ga, lo, hi)
        if flops or mem_bytes or seconds:
            self.compute(w, flops=flops, mem_bytes=mem_bytes, seconds=seconds)
        if instr_words:
            self.instr_stores(w, instr_words)

    # ------------------------------------------------------------------
    # worker-axis batched driver (phase_all)
    # ------------------------------------------------------------------

    def _w_arr(self, v) -> np.ndarray:
        return np.broadcast_to(np.asarray(v, np.int64), (self.W,))

    def _page_range_all(self, ga, lo: np.ndarray, hi: np.ndarray, *,
                        prefetch: bool):
        pw = self.page_words
        p_lo = ga.page_lo + lo // pw
        p_hi = ga.page_lo + np.maximum(hi - 1, lo) // pw + 1
        if prefetch:
            arr_end = ga.page_lo + -(-ga.n_elems // pw)
            p_hi = np.maximum(np.minimum(p_hi + self.prefetch, arr_end), p_hi)
        return self._region_of(int(ga.page_lo)), p_lo, p_hi

    def _may_evict_mask(self, ranges) -> Optional[np.ndarray]:
        """Per-worker eviction-possibility upper bound for one phase (the
        per-worker refinement of the old all-or-nothing ``_phase_fits``
        precheck): every page that can newly occupy a cache slot this
        phase is not-incache at phase start and lies in some declared
        range, so ``resident + sum over ops of (range length - in-cache
        count)`` bounds each worker's peak occupancy (overlapping ranges
        only loosen the bound).  Returns None when no worker can cross
        the watermark — the phase then runs fully batched with no
        eviction work at all."""
        if self.cache_pages is None:
            return None
        quick = self.resident.copy()
        for region, p_lo, p_hi in ranges:
            quick += p_hi - p_lo
        if (quick <= self.cache_pages).all():
            return None            # even all-cold ranges fit: no gathers
        ub = self.resident.copy()
        for region, p_lo, p_hi in ranges:
            d = self.dirs[region]
            ub += (p_hi - p_lo) - d.count_range(d.incache, p_lo, p_hi)
        may = ub > self.cache_pages
        return may if may.any() else None

    def _residual_workers(self, rranges, wranges,
                          may: np.ndarray) -> np.ndarray:
        """Window-disjointness analysis: which workers' phase executions
        can interact through eviction.

        Within a phase (no barriers, no spans) the ONLY cross-worker
        effect is an eviction writeback invalidating another worker's
        valid copy of the victim page — and only ``may``-workers can
        evict.  An evictor's dirty victims lie inside its conservative
        dirty bounds (the directory's per-row dirty bounding interval,
        widened by this phase's declared write ranges); another worker can
        observe the writeback only if those pages intersect its *reach*
        (current window + declared ranges: valid copies exist only inside
        the window, and this phase fetches only inside the ranges).
        Workers touched by no such intersection are pairwise independent
        — their per-worker op sequences commute, so they run batched.
        The returned mask marks the rest, which replay tick-ordered."""
        resid = np.zeros(self.W, bool)
        reach: Dict[int, list] = {}
        for region, p_lo, p_hi in rranges + wranges:
            r = reach.get(region)
            if r is None:
                reach[region] = [p_lo.copy(), p_hi.copy()]
            else:
                np.minimum(r[0], p_lo, out=r[0])
                np.maximum(r[1], p_hi, out=r[1])
        wr: Dict[int, list] = {}
        for region, p_lo, p_hi in wranges:
            r = wr.get(region)
            if r is None:
                wr[region] = [p_lo.copy(), p_hi.copy()]
            else:
                np.minimum(r[0], p_lo, out=r[0])
                np.maximum(r[1], p_hi, out=r[1])
        imax = np.iinfo(np.int64).max
        imin = np.iinfo(np.int64).min
        for ri, d in enumerate(self.dirs):
            dlo, dhi = d.dirty_lo, d.dirty_hi
            if ri in wr:
                dlo = np.minimum(dlo, wr[ri][0])
                dhi = np.maximum(dhi, wr[ri][1])
            e = may & (dlo < dhi)
            if not e.any():
                continue
            live = d.base >= 0
            rlo = np.where(live, d.base, imax)
            rhi = np.where(live, d.base + d.length, imin)
            if ri in reach:
                rlo = np.minimum(rlo, reach[ri][0])
                rhi = np.maximum(rhi, reach[ri][1])
                live = np.ones(self.W, bool)
            E = np.nonzero(e)[0]
            M = ((rlo[None, :] < dhi[E][:, None])
                 & (rhi[None, :] > dlo[E][:, None]) & live[None, :])
            M[np.arange(E.size), E] = False
            if M.any():
                ei, vi = np.nonzero(M)
                resid[E[ei]] = True
                resid[vi] = True
        return resid

    def _op_danger_split(self, d, ga, lo, hi, p_lo, p_hi, rows,
                         may: np.ndarray, *, is_write: bool) -> np.ndarray:
        """Per-op ``_danger`` screening for the batched path: workers
        whose op could evict a still-in-cache page of its own range
        before touching it (the mid-op refetch pattern) replay THIS op
        per worker — ``read``/``write`` resolve it through the analytic
        refetch schedule (``_danger_replay``) — and the rest stay
        batched.  Exact because the split only runs over workers already
        proven independent, so any interleaving of their op executions
        is equivalent."""
        if self.protocol == IDEAL_PROTO:
            return rows
        L = p_hi - p_lo
        cand = may[rows] & (self.resident[rows] + L[rows] > self.cache_pages)
        if not cand.any():
            return rows
        crows = rows[cand]
        n_in = d.count_range(d.incache, p_lo[crows], p_hi[crows], rows=crows)
        n_enter = L[crows] - n_in
        danger = (n_enter < L[crows]) & (
            self.resident[crows] + n_enter > self.cache_pages)
        if not danger.any():
            return rows
        drows = crows[danger]
        self.stats["danger_ops"] += int(drows.size)
        # lockstep-uniform danger workers (the rotating steady state)
        # share one schedule: the leader replays once, recording, and the
        # rest apply the recorded schedule as batched plane ops
        shareable = (drows.size >= 2 and self.danger_mode == "vec"
                     and self.cache_pages >= 1)
        if not (shareable
                and self._danger_shared(drows, d, d.region, ga, lo, hi,
                                        p_lo, p_hi, is_write=is_write)):
            # near-isomorphic residue: a size->=3 group that failed the
            # whole-group check may still contain a lockstep subgroup
            # (one clamped row breaking an otherwise-uniform phase) —
            # the packed multi-row victim scan shares what it can
            resid = (self._danger_subgroups(drows, d, ga, lo, hi,
                                            p_lo, p_hi, is_write=is_write)
                     if shareable and drows.size >= 3 else drows)
            for w in resid:
                if is_write:
                    self.write(int(w), ga, int(lo[w]), int(hi[w]))
                else:
                    self.read(int(w), ga, int(lo[w]), int(hi[w]))
        keep = np.ones(rows.size, bool)
        keep[np.nonzero(cand)[0][danger]] = False
        return rows[keep]

    def _evict_rows_batch(self, rows: np.ndarray):
        """Watermark eviction for ``rows`` after a batched op: each worker
        over the watermark evicts its least-recently-touched pages
        run-by-run from its tick-ordered queue — same victims, same
        per-run charges as ``_evict_cells`` — but rows whose front runs
        cover the same column span (the lockstep steady state of uniform
        spill phases) apply their liveness test, segment-LRU selection
        and plane updates as single 2D ops (``directory.run_live`` /
        ``lru_take`` / ``evict_rows``).  Only called for workers whose
        evictions provably cannot invalidate any other worker (window
        disjointness), so ``_evict_now``'s sharer-invalidation step is
        skipped as a proven no-op."""
        if rows.size == 0 or self.cache_pages is None:
            return
        k = self.resident[rows] - self.cache_pages
        over = k > 0
        if not over.any():
            return
        rows = rows[over]
        k = k[over].astype(np.int64)
        charge = self.protocol != IDEAL_PROTO
        while rows.size:
            if rows.size < 4:
                for w, kw in zip(rows, k):
                    self._evict_cells(int(w), int(kw))
                return
            self.stats["evict_batch_rounds"] += 1
            # one front run per needy worker, grouped by column span;
            # pristine runs (never re-touched) are fully live on [off, n),
            # so their groups skip the touch scan entirely
            groups: Dict[Tuple[int, int, int, bool], list] = {}
            bts = np.empty(rows.size, np.int64)
            for i, w in enumerate(rows):
                t0, region, col0, n, off, shift0, pris = self._lru_q[w][0]
                d = self.dirs[region]
                c0 = col0 + (int(d.shift[w]) - shift0)
                bts[i] = t0
                groups.setdefault((region, c0 + off, n - off, pris),
                                  []).append(i)
            keep_rows, keep_k = [], []
            for (region, start, length, pris), idxs in groups.items():
                idxs = np.asarray(idxs, np.int64)
                R, kk = rows[idxs], k[idxs]
                d = self.dirs[region]
                if R.size < 4:
                    for w, kw in zip(R, kk):
                        self._evict_cells(int(w), int(kw))
                    continue
                if pris:
                    live = None
                    tot = np.full(R.size, length, np.int64)
                else:
                    live = d.run_live(R, start, length, bts[idxs])
                    tot = live.sum(axis=1, dtype=np.int64)
                part = kk < tot
                for si in (np.nonzero(~part)[0], np.nonzero(part)[0]):
                    if si.size == 0:
                        continue
                    is_part = bool(part[si[0]])
                    whole = si.size == R.size
                    Rs, ks = R[si], kk[si]
                    tots = tot[si]
                    fully = pris or bool((tots == length).all())
                    # segment-LRU selection only where the run outlives
                    # the demand; whole-run and prefix takes of fully-live
                    # runs (the streaming steady state) skip masks
                    span = length
                    if not is_part:
                        take = None if fully else live[si]
                    elif pris and int(ks.min()) == int(ks.max()):
                        span = int(ks[0])      # uniform prefix: short span
                        take = None
                    elif pris:
                        take = np.arange(length) < ks[:, None]
                    else:
                        lv = live if whole else live[si]
                        take = d.lru_take(lv, ks, tots)
                    db = d.evict_rows(Rs, start, span, take,
                                      set_wprot=charge)
                    if charge and db.any():
                        self.traffic.writeback_bytes += (int(db.sum())
                                                         * self.page_bytes)
                        hit = db > 0
                        self.clock[Rs[hit]] += (
                            self.cost.net_latency_s * db[hit]
                            + db[hit] * self.page_bytes
                            / self.cost.net_bw_Bps)
                        if self.chaos is not None:
                            self.clock[Rs[hit]] += (
                                self.chaos.retry_rows(Rs[hit]))
                    if is_part:
                        # advance each run past its last taken cell
                        self.resident[Rs] -= ks
                        if fully:          # columnar take: cutoff is k
                            last = ks - 1
                        else:
                            last = take.shape[1] - 1 - np.argmax(
                                take[:, ::-1], axis=1)
                        for i, w in enumerate(Rs):
                            self._lru_q[w][0][4] += int(last[i]) + 1
                    else:
                        self.resident[Rs] -= tots
                        for w in Rs:
                            self._lru_q[w].popleft()
                        rem = ks - tots
                        m = rem > 0
                        if m.any():
                            keep_rows.append(Rs[m])
                            keep_k.append(rem[m])
            if not keep_rows:
                return
            rows = np.concatenate(keep_rows)
            k = np.concatenate(keep_k)
            # group leftovers concatenate in group order — restore the
            # ascending row order every plane primitive assumes
            order = np.argsort(rows)
            rows = rows[order]
            k = k[order]

    def _fetch_range_all(self, region: int, p_lo: np.ndarray,
                         p_hi: np.ndarray, rows: np.ndarray):
        """Vectorized ``_fetch_range`` over ``rows`` of the worker axis:
        identical per-worker traffic and clock charges.  Strategy is
        per-op: dense (R, Lmax) gather/scatter matrices in the
        many-rows/narrow-intervals regime; otherwise rows group by their
        shared (window-relative start, length) — block-partitioned phases
        are uniform — and each group runs single 2D slice-plane ops."""
        d = self.dirs[region]
        d.ensure_rows(p_lo, p_hi, rows)
        L = p_hi - p_lo
        if use_dense(rows.size, int(L.max())):
            self._fetch_dense(d, region, p_lo, p_hi, rows)
            return
        c0 = p_lo - d.base[rows]
        uk, inv = np.unique(np.stack([c0, L], axis=1), axis=0,
                            return_inverse=True)
        for g in range(uk.shape[0]):
            self._fetch_uniform(d, region, rows[inv == g],
                                int(uk[g, 0]), int(uk[g, 1]))

    def _fetch_uniform(self, d: RegionDirectory, region: int,
                       rows: np.ndarray, c0: int, n: int):
        """One uniform-span fetch group: all ``rows`` fetch columns
        [c0, c0+n) of their windows, so every plane pass is a contiguous
        2D slice op — no gather matrices, no per-row Python loop.  Charge
        expressions match ``_fetch_range`` term for term."""
        s = slice(c0, c0 + n)
        rb = d.row_block(rows)              # slice views for lockstep rows
        n_miss = n - d.valid[rb, s].sum(axis=1)
        if d.touch is not None:
            shifts = d.shift[rows]
            t0 = np.array([self._q_append(int(w), region, c0, n,
                                          int(shifts[i]))
                           for i, w in enumerate(rows)], np.int64)
            d.touch[rb, s] = t0[:, None]
            n_enter = n - d.incache[rb, s].sum(axis=1)
            d.incache[rb, s] = True
            self.resident[rows] += n_enter
        tot_miss = int(n_miss.sum())
        if tot_miss:
            if self.protocol != IDEAL_PROTO:
                self.traffic.page_fetches += tot_miss
                self.traffic.fetch_bytes += tot_miss * self.page_bytes
                n_req = -(-n_miss // self.fetch_batch)
                t = (self.cost.net_latency_s * (2 * n_req)
                     + (n_miss * self.page_bytes) / self.cost.net_bw_Bps)
                hit = n_miss > 0
                self.clock[rows[hit]] += t[hit]
                if self.chaos is not None:
                    self.clock[rows[hit]] += self.chaos.retry_rows(
                        rows[hit])
            d.valid[rb, s] = True

    def _fetch_dense(self, d: RegionDirectory, region: int,
                     p_lo: np.ndarray, p_hi: np.ndarray, rows: np.ndarray):
        cols, mask = d.range_cols(p_lo, p_hi, rows)
        safe = np.where(mask, cols, 0)
        r2 = rows[:, None]
        vsub = d.valid[r2, safe] & mask
        L = p_hi - p_lo
        n_miss = L - vsub.sum(axis=1)
        if d.touch is not None:
            # one monotone tick per (worker, op) run: relative order within
            # each worker matches the per-worker path, which is all the
            # LRU victim selection compares (ticks never cross workers)
            t0 = np.array([self._q_append(int(w), region, int(cols[i, 0]),
                                          int(L[i]), int(d.shift[w]))
                           for i, w in enumerate(rows)], np.int64)
            ri, ci = np.nonzero(mask)
            d.touch[rows[ri], cols[ri, ci]] = t0[ri]
            isub = d.incache[r2, safe] & mask
            ri, ci = np.nonzero(mask & ~isub)
            if ri.size:
                d.incache[rows[ri], cols[ri, ci]] = True
            self.resident[rows] += L - isub.sum(axis=1)
        tot_miss = int(n_miss.sum())
        if tot_miss:
            if self.protocol != IDEAL_PROTO:
                self.traffic.page_fetches += tot_miss
                self.traffic.fetch_bytes += tot_miss * self.page_bytes
                n_req = -(-n_miss // self.fetch_batch)
                t = (self.cost.net_latency_s * (2 * n_req)
                     + (n_miss * self.page_bytes) / self.cost.net_bw_Bps)
                hit = n_miss > 0
                self.clock[rows[hit]] += t[hit]
                if self.chaos is not None:
                    self.clock[rows[hit]] += self.chaos.retry_rows(
                        rows[hit])
            ri, ci = np.nonzero(mask & ~vsub)
            d.valid[rows[ri], cols[ri, ci]] = True

    def _read_all(self, ga, lo: np.ndarray, hi: np.ndarray, rows=None,
                  may=None):
        region, p_lo, p_hi = self._page_range_all(ga, lo, hi, prefetch=True)
        rows = self._rows_all if rows is None else rows
        if may is not None:
            rows = self._op_danger_split(self.dirs[region], ga, lo, hi,
                                         p_lo, p_hi, rows, may,
                                         is_write=False)
        if rows.size:
            self._fetch_range_all(region, p_lo[rows], p_hi[rows], rows)
        if may is not None:
            self._evict_rows_batch(rows)

    def _write_all(self, ga, lo: np.ndarray, hi: np.ndarray, rows=None,
                   may=None):
        region, p_lo, p_hi = self._page_range_all(ga, lo, hi, prefetch=False)
        d = self.dirs[region]
        rows = self._rows_all if rows is None else rows
        if may is not None:
            rows = self._op_danger_split(d, ga, lo, hi, p_lo, p_hi, rows,
                                         may, is_write=True)
        if rows.size:
            d.ensure_rows(p_lo[rows], p_hi[rows], rows)
            d.note_dirty(rows, p_lo[rows], p_hi[rows])
            L = (p_hi - p_lo)[rows]
            if use_dense(rows.size, int(L.max())):
                self._write_dense(d, region, ga, lo, hi, p_lo, p_hi, rows)
            else:
                c0 = p_lo[rows] - d.base[rows]
                uk, inv = np.unique(np.stack([c0, L], axis=1), axis=0,
                                    return_inverse=True)
                for g in range(uk.shape[0]):
                    self._write_uniform(d, region, lo, hi, p_lo, p_hi,
                                        rows[inv == g],
                                        int(uk[g, 0]), int(uk[g, 1]))
            d.maybe_dirty = True
            for w in rows:
                self._dirty_regions[w].add(region)
        if may is not None:
            self._evict_rows_batch(rows)

    def _write_dense(self, d: RegionDirectory, region: int, ga,
                     lo: np.ndarray, hi: np.ndarray, p_lo: np.ndarray,
                     p_hi: np.ndarray, rows: np.ndarray):
        pw = self.page_words
        n_words = (hi - lo)[rows]

        # mechanism cost, in the per-worker path's charge order
        if self.model_mechanism and self.protocol == FINE_PROTO:
            self.clock[rows] += n_words * self.instr_s_per_word
        if self._track_wprot:
            cols, mask = d.range_cols(p_lo[rows], p_hi[rows], rows)
            wsub = d.wprot[rows[:, None], np.where(mask, cols, 0)] & mask
            self.clock[rows] += wsub.sum(axis=1) * self.fault_s
            ri, ci = np.nonzero(mask)
            d.wprot[rows[ri], cols[ri, ci]] = False

        # write-allocate edge fetches (first page, then last page — the
        # per-worker path's order), only for the workers that need them
        n_pg = (p_hi - p_lo)[rows]
        if self.protocol != IDEAL_PROTO:
            single = n_pg == 1
            first = np.where(single, n_words < pw, lo[rows] % pw != 0)
            last = (~single) & (hi[rows] % pw != 0)
            if first.any():
                r = rows[np.nonzero(first)[0]]
                self._fetch_range_all(region, p_lo[r], p_lo[r] + 1, r)
            if last.any():
                r = rows[np.nonzero(last)[0]]
                self._fetch_range_all(region, p_hi[r] - 1, p_hi[r], r)

        cols, mask = d.range_cols(p_lo[rows], p_hi[rows], rows)
        safe = np.where(mask, cols, 0)
        vsub = d.valid[rows[:, None], safe] & mask
        if d.touch is not None:
            shifts = d.shift[rows]
            t0 = np.array([self._q_append(int(w), region, int(cols[i, 0]),
                                          int(n_pg[i]), int(shifts[i]))
                           for i, w in enumerate(rows)], np.int64)
            ri, ci = np.nonzero(mask)
            d.touch[rows[ri], cols[ri, ci]] = t0[ri]
            isub = d.incache[rows[:, None], safe] & mask
            ri, ci = np.nonzero(mask & ~isub)
            if ri.size:
                d.incache[rows[ri], cols[ri, ci]] = True
            self.resident[rows] += n_pg - isub.sum(axis=1)
        ri, ci = np.nonzero(mask & ~vsub)
        if ri.size:
            d.valid[rows[ri], cols[ri, ci]] = True
        ri, ci = np.nonzero(mask)
        d.dirty[rows[ri], cols[ri, ci]] = True

    def _write_uniform(self, d: RegionDirectory, region: int,
                       lo: np.ndarray, hi: np.ndarray, p_lo: np.ndarray,
                       p_hi: np.ndarray, rows: np.ndarray, c0: int, n: int):
        """One uniform-span write group: all ``rows`` write columns
        [c0, c0+n) of their windows — single 2D slice-plane ops, charge
        expressions term-for-term those of the per-worker ``write``."""
        pw = self.page_words
        s = slice(c0, c0 + n)
        rb = d.row_block(rows)              # slice views for lockstep rows
        n_words = (hi - lo)[rows]
        if self.model_mechanism and self.protocol == FINE_PROTO:
            self.clock[rows] += n_words * self.instr_s_per_word
        if self._track_wprot:
            n_faults = d.wprot[rb, s].sum(axis=1)
            self.clock[rows] += n_faults * self.fault_s
            d.wprot[rb, s] = False
        if self.protocol != IDEAL_PROTO:
            if n == 1:
                first = n_words < pw
                last = np.zeros(rows.size, bool)
            else:
                first = lo[rows] % pw != 0
                last = hi[rows] % pw != 0
            if first.any():
                r = rows[np.nonzero(first)[0]]
                self._fetch_range_all(region, p_lo[r], p_lo[r] + 1, r)
            if last.any():
                r = rows[np.nonzero(last)[0]]
                self._fetch_range_all(region, p_hi[r] - 1, p_hi[r], r)
        if d.touch is not None:
            shifts = d.shift[rows]
            t0 = np.array([self._q_append(int(w), region, c0, n,
                                          int(shifts[i]))
                           for i, w in enumerate(rows)], np.int64)
            d.touch[rb, s] = t0[:, None]
            n_enter = n - d.incache[rb, s].sum(axis=1)
            d.incache[rb, s] = True
            self.resident[rows] += n_enter
        d.valid[rb, s] = True
        d.dirty[rb, s] = True

    def phase_all(self, reads=(), writes=(), *, flops=0.0, mem_bytes=0.0,
                  seconds=0.0, instr_words=0.0):
        """One SPMD phase for ALL workers in a single runtime call.

        ``reads``/``writes`` are sequences of ``(ga, lo, hi)`` with
        ``lo``/``hi`` as (W,) int arrays (scalars broadcast); ``flops``/
        ``mem_bytes``/``seconds``/``instr_words`` may be scalars or (W,)
        arrays.  Bit-exactly equivalent to
        ``for w in range(W): phase(w, ...)``: within a phase (no barriers,
        no spans) workers interact only through eviction writebacks.  The
        engine therefore never leaves the batched path wholesale:

        * when no worker can cross the eviction watermark (per-worker
          upper bound, ``_may_evict_mask``) ops run op-major as single
          vectorized passes over the (W, window) directory planes;
        * otherwise a window-disjointness analysis over the declared
          ranges (``_residual_workers``) proves which workers' evictions
          cannot observe each other's directory updates — those run
          batched too, with watermark eviction applied per op as
          vectorized segment-LRU plane ops (``_evict_rows_batch``) and
          the per-op ``_danger`` refetch pattern screened per worker;
        * only the residual *interacting* workers replay tick-ordered
          through the per-worker ``phase`` path, in worker order.

        Must be called outside spans — consistency regions serialize
        through their locks and stay per-worker
        (``span``/``acquire``/``release``)."""
        assert not any(self.spans), "phase_all must run outside spans"
        self.chaos_tick()
        W = self.W
        reads = [(ga, self._w_arr(lo), self._w_arr(hi))
                 for ga, lo, hi in reads]
        writes = [(ga, self._w_arr(lo), self._w_arr(hi))
                  for ga, lo, hi in writes]
        rranges = [self._page_range_all(ga, lo, hi, prefetch=True)
                   for ga, lo, hi in reads]
        wranges = [self._page_range_all(ga, lo, hi, prefetch=False)
                   for ga, lo, hi in writes]
        may = self._may_evict_mask(rranges + wranges)
        resid = None
        if may is not None and self.protocol != IDEAL_PROTO:
            r = self._residual_workers(rranges, wranges, may)
            if r.any():
                resid = r
        rows = None if resid is None else np.nonzero(~resid)[0]
        self.stats["batched_phases"] += 1
        self._race_suspend = True
        if rows is None or rows.size:
            for ga, lo, hi in reads:
                self._read_all(ga, lo, hi, rows=rows, may=may)
            for ga, lo, hi in writes:
                self._write_all(ga, lo, hi, rows=rows, may=may)
        fl = np.asarray(flops, np.float64)
        mb = np.asarray(mem_bytes, np.float64)
        sec = np.asarray(seconds, np.float64)
        iw = np.asarray(instr_words, np.float64)
        crows = self._rows_all if rows is None else rows
        if crows.size:
            if fl.any() or mb.any() or sec.any():
                sharing = self.cost.workers_on_node(W)
                bw = self.cost.node_bw(sharing) / max(1, sharing)
                t = np.broadcast_to(
                    sec + np.maximum(fl / self.cost.flops_per_worker,
                                     mb / bw), (W,))
                self.clock[crows] += t[crows]
            if (self.model_mechanism and self.protocol == FINE_PROTO
                    and iw.any()):
                self.clock[crows] += np.broadcast_to(
                    iw * self.instr_s_per_word, (W,))[crows]
        if resid is not None:
            # tick-ordered replay of the interacting workers, in worker
            # order (the loop driver's order within each dependence class)
            self.stats["residual_replays"] += int(resid.sum())
            flb = np.broadcast_to(fl, (W,))
            mbb = np.broadcast_to(mb, (W,))
            secb = np.broadcast_to(sec, (W,))
            iwb = np.broadcast_to(iw, (W,))
            for w in np.nonzero(resid)[0]:
                self.phase(
                    int(w),
                    reads=[(ga, int(lo[w]), int(hi[w]))
                           for ga, lo, hi in reads],
                    writes=[(ga, int(lo[w]), int(hi[w]))
                            for ga, lo, hi in writes],
                    flops=float(flb[w]), mem_bytes=float(mbb[w]),
                    seconds=float(secb[w]), instr_words=float(iwb[w]))
        self._race_suspend = False
        if self.detect_races:
            self._race_phase_all(reads, writes)

    # ------------------------------------------------------------------
    # worker-axis batched span driver (span_all)
    # ------------------------------------------------------------------

    def _span_one(self, w: int, lock_id: int, reads, writes):
        """One worker's whole consistency region through the per-worker
        path — the serialized reference body every batched span_all path
        is proven bit-equal against (and the fallback when spill or
        flush/span page interactions make batching unsound)."""
        self.acquire(w, lock_id)
        for ga, lo, hi in reads:
            self.read(w, ga, int(lo[w]), int(hi[w]))
        for ga, lo, hi in writes:
            self.write(w, ga, int(lo[w]), int(hi[w]))
        self.release(w, lock_id)

    def _span_flush_safe(self, rows: np.ndarray, locks: np.ndarray,
                         ranges) -> bool:
        """May every masked worker's acquire-time ordinary flush hoist to
        one batched pass BEFORE any span body runs?  Sound iff no flushed
        dirty page (or its sharer invalidation) can be observed by any
        span body or notice replay of this pass: the masked workers'
        conservative dirty bounds must be disjoint from every *span
        interaction interval* — the declared (prefetch-extended)
        read/write page ranges plus the pending-notice page bounds of
        every involved lock.  All intervals are absolute page numbers, so
        region resolution is unnecessary."""
        spans_iv = []
        for region, p_lo, p_hi in ranges:
            spans_iv.append((int(p_lo[rows].min()), int(p_hi[rows].max())))
        for lk_id in np.unique(locks[rows]):
            lk = self.locks.get(int(lk_id))
            if lk is None:
                continue
            grp = rows[locks[rows] == lk_id]
            v_min = int(lk.seen[grp].min())
            if v_min >= lk.version:
                continue
            pb_iv = lk.log.page_bounds(v_min, lk.version)
            if pb_iv is not None:
                spans_iv.append(pb_iv)
        if not spans_iv:
            return True
        for d in self.dirs:
            dlo, dhi = d.dirty_lo[rows], d.dirty_hi[rows]
            m = dlo < dhi
            if not m.any():
                continue
            lo, hi = int(dlo[m].min()), int(dhi[m].max())
            for rlo, rhi in spans_iv:
                if rlo < hi and rhi > lo:
                    return False
        return True

    def _span_group_vec(self, grp: np.ndarray, lock_id: int, reads, writes,
                        rranges, wranges) -> bool:
        """Analytic batched pass for one uniform same-lock span group —
        the pipelined fast path of ``span_all``.

        Grants stay serialized (the release-time chain below is the only
        true serialization point), but everything *around* the grant
        pipelines across the group as plane ops: the pending-notice set of
        the i-th holder is exactly the earlier holders' releases of THIS
        pass (precondition: every member has replayed the lock's log —
        ``seen == version`` — the post-barrier steady state), and every
        release publishes the same declared write intervals, so replay
        invalidations, fetch misses, write faults and release payloads
        resolve as (G, pages) matrix ops, one batched log append
        (``IntervalLog.append_versions``), and a G-step scalar clock chain
        whose per-worker charge sequence replicates the per-worker path
        term for term (bit-equal clocks).

        Unsynced members are allowed in ONE analytically tractable shape
        — the repeated uniform pass (e.g. the second sweep of the same
        accumulation before any barrier): when every log version a member
        has not replayed carries exactly THIS pass's payload, its
        coalesced pending is that payload no matter how far behind it is.
        Any other backlog, differing per-worker intervals, or an empty
        interval returns False (caller falls back to the per-worker
        serial body).  Ops across several regions resolve region-by-
        region: plane matrices, pending masks and replay hits are
        per-region separable (a page belongs to exactly one region), and
        the release payload is the per-region payloads concatenated in
        region order — which IS page order, matching ``_span_harvest``'s
        sorted multi-region concatenation.  Eviction inside spans never
        reaches here — span_all screens it into the full-serial
        fallback."""
        lk = self.locks.setdefault(lock_id, _Lock(self.W))
        w0 = int(grp[0])
        ops = []      # (ga, lo, hi, p_lo, p_hi, is_write, region) — uniform
        regions = []  # ascending (rranges/wranges come region-resolved)
        for (ga, lo, hi), (region, p_lo, p_hi), is_w in (
                [(o, r, False) for o, r in zip(reads, rranges)]
                + [(o, r, True) for o, r in zip(writes, wranges)]):
            if (not (lo[grp] == lo[w0]).all()
                    or not (hi[grp] == hi[w0]).all()):
                return False
            if int(hi[w0]) <= int(lo[w0]):
                return False
            if region not in regions:
                regions.append(region)
            ops.append((ga, int(lo[w0]), int(hi[w0]),
                        int(p_lo[w0]), int(p_hi[w0]), is_w, region))
        regions.sort()

        G = int(grp.size)
        IDEAL = self.protocol == IDEAL_PROTO
        FINE = self.protocol == FINE_PROTO
        pw = self.page_words
        pb = self.page_bytes
        track = self.cache_pages is not None
        imax = np.iinfo(np.int64).max
        imin = np.iinfo(np.int64).min
        gi = grp[:, None]

        # per-region context: union window, gathered plane matrices, and
        # the uniform release payload accumulator (per declared-write
        # page, the (min, max)-coalesced word interval — what each member
        # publishes and what each later holder replays)
        ctx = {}
        for r in regions:
            d_r = self.dirs[r]
            u_lo = min(op[3] for op in ops if op[6] == r)
            u_hi = max(op[4] for op in ops if op[6] == r)
            P = u_hi - u_lo
            d_r.ensure_rows(np.full(G, u_lo, np.int64),
                            np.full(G, u_hi, np.int64), grp)
            colm = (u_lo - d_r.base[grp])[:, None] + np.arange(P)[None, :]
            ctx[r] = {
                "d": d_r, "u_lo": u_lo, "colm": colm,
                "V": (d_r.valid[gi, colm]).copy(),
                "IC": (d_r.incache[gi, colm]).copy() if track else None,
                "WP": ((d_r.wprot[gi, colm]).copy()
                       if self._track_wprot else None),
                "pend": np.zeros(P, bool),
                "wlo": np.full(P, imax, np.int64),
                "whi": np.full(P, imin, np.int64),
            }
        for ga, lo, hi, p_lo, p_hi, is_w, r in ops:
            if not is_w:
                continue
            c = ctx[r]
            sl = slice(p_lo - c["u_lo"], p_hi - c["u_lo"])
            bw_ = (np.arange(p_lo, p_hi) - ga.page_lo) * pw
            c["pend"][sl] = True
            np.minimum(c["wlo"][sl], np.maximum(lo - bw_, 0),
                       out=c["wlo"][sl])
            np.maximum(c["whi"][sl], np.minimum(hi - bw_, pw),
                       out=c["whi"][sl])
        if regions:
            parts = []
            for r in regions:
                c = ctx[r]
                rel_idx = np.nonzero(c["pend"])[0]
                parts.append((rel_idx + c["u_lo"], c["wlo"][rel_idx],
                              c["whi"][rel_idx]))
            rel_pages = np.concatenate([p[0] for p in parts])
            rel_los = np.concatenate([p[1] for p in parts])
            rel_his = np.concatenate([p[2] for p in parts])
        else:
            rel_pages = rel_los = rel_his = np.zeros(0, np.int64)
        npend = int(rel_pages.size)
        pub_bytes = 0
        if npend:
            if FINE:
                pub_bytes = (int((rel_his - rel_los).sum()) * _WORD
                             + npend * (pw // 8))
            else:
                pub_bytes = npend * pb

        # ---- pending sets: member i replays the earlier i releases of
        # THIS pass, plus any backlog — tolerated only when the backlog
        # repeats this very payload (then the coalesced pending IS the
        # payload, however far behind a member is)
        v0 = lk.version
        seen = lk.seen[grp]
        has_pend = np.ones(G, bool)
        has_pend[0] = int(seen[0]) < v0
        v_min = int(seen.min())
        if v_min < v0:
            voff = lk.log.voff
            sizes = np.diff(np.asarray(voff[v_min:v0 + 1], np.int64))
            if npend == 0 or not (sizes == npend).all():
                # mixed-shape backlog: some member must replay versions
                # whose interval counts differ from this pass's — per-
                # member pending sets diverge (see DIRECTORY.md "Why the
                # mixed-payload backlog stays serial")
                self.stats["span_backlog_serial"] += 1
                return False
            if not lk.log.payload_matches(v_min, v0, rel_pages, rel_los,
                                          rel_his):
                # mixed-payload backlog: right shape, different pages —
                # coalesced pendings are not THIS payload, so the uniform
                # (G, P) replay algebra below does not apply
                self.stats["span_backlog_serial"] += 1
                return False

        # ---- replay effects --------------------------------------------
        if npend and not IDEAL and not FINE:
            n_inv = 0
            for r in regions:
                c = ctx[r]
                if not c["pend"].any():
                    continue
                hits = c["V"] & c["pend"][None, :] & has_pend[:, None]
                nh = int(hits.sum())
                if nh:
                    if c["WP"] is not None and self.model_mechanism:
                        c["WP"] |= hits
                    c["V"] &= ~(has_pend[:, None] & c["pend"][None, :])
                n_inv += nh
            self.traffic.invalidations += n_inv
            self.traffic.control_msgs += npend * int(has_pend.sum())
            if self.chaos is not None:
                self.chaos.inval_msgs(n_inv)

        # ---- op effects, op-major (rows are mutually independent) ------
        op_miss = []       # per read op: (G,) fetch-miss counts
        op_faults = []     # per write op: (G,) wprot fault counts
        op_edges = []      # per write op: (first(G,)|None, last(G,)|None)
        for ga, lo, hi, p_lo, p_hi, is_w, r in ops:
            cx = ctx[r]
            V, IC, WP = cx["V"], cx["IC"], cx["WP"]
            d, u_lo, colm = cx["d"], cx["u_lo"], cx["colm"]
            sl = slice(p_lo - u_lo, p_hi - u_lo)
            n = p_hi - p_lo
            if not is_w:
                miss = ((~V[:, sl]).sum(axis=1) if not IDEAL
                        else np.zeros(G, np.int64))
                op_miss.append(miss)
                V[:, sl] = True
                if track:
                    self._span_track_touch(d, grp, gi, colm, IC, r,
                                           p_lo, n, sl)
                tot = int(miss.sum())
                if tot:
                    self.traffic.page_fetches += tot
                    self.traffic.fetch_bytes += tot * pb
                continue
            if self._track_wprot:
                op_faults.append(WP[:, sl].sum(axis=1))
                WP[:, sl] = False
            else:
                op_faults.append(None)
            first = last = None
            if not IDEAL:
                n_words = hi - lo
                if n == 1:
                    f_part, l_part = n_words < pw, False
                else:
                    f_part = lo % pw != 0
                    l_part = hi % pw != 0
                if f_part:
                    c = p_lo - u_lo
                    first = (~V[:, c]).astype(np.int64)
                    V[:, c] = True
                    if track:
                        self._span_track_touch(d, grp, gi, colm, IC,
                                               r, p_lo, 1,
                                               slice(c, c + 1))
                    tot = int(first.sum())
                    if tot:
                        self.traffic.page_fetches += tot
                        self.traffic.fetch_bytes += tot * pb
                if l_part:
                    c = p_hi - 1 - u_lo
                    last = (~V[:, c]).astype(np.int64)
                    V[:, c] = True
                    if track:
                        self._span_track_touch(d, grp, gi, colm, IC,
                                               r, p_hi - 1, 1,
                                               slice(c, c + 1))
                    tot = int(last.sum())
                    if tot:
                        self.traffic.page_fetches += tot
                        self.traffic.fetch_bytes += tot * pb
            op_edges.append((first, last))
            if track:
                self._span_track_touch(d, grp, gi, colm, IC, r,
                                       p_lo, n, sl)
            V[:, sl] = True

        # ---- commit planes --------------------------------------------
        for r in regions:
            cx = ctx[r]
            d, colm = cx["d"], cx["colm"]
            d.valid[gi, colm] = cx["V"]
            if cx["IC"] is not None:
                d.incache[gi, colm] = cx["IC"]
            if cx["WP"] is not None:
                d.wprot[gi, colm] = cx["WP"]

        # ---- publish: one batched log append, G versions --------------
        if not IDEAL:
            if FINE and npend:
                self.traffic.diff_bytes += (pub_bytes                # replays
                                            * int(has_pend.sum()))
            if npend:
                if FINE:
                    self.traffic.diff_bytes += pub_bytes * G    # releases
                else:
                    self.traffic.writeback_bytes += pub_bytes * G
            lk.log.append_versions(
                np.tile(rel_pages, G), np.tile(rel_los, G),
                np.tile(rel_his, G), np.full(G, npend, np.int64))
            lk.version = v0 + G
            lk.seen[grp] = v0 + np.arange(1, G + 1)
        self.traffic.control_msgs += 3 * G          # acquire 2 + release 1

        # ---- the grant chain: the only serialized part ----------------
        # per-worker charge sequence replicates the per-worker path term
        # for term (same scalar expressions, same order), so clocks stay
        # bit-equal to the span loop
        xfer = self.cost.xfer_s
        lat = self.cost.net_latency_s
        bw = self.cost.net_bw_Bps
        fb = self.fetch_batch
        ctrl2 = xfer(64, 2)
        ctrl1 = xfer(64, 1)
        t_rel = lk.last_release_time
        for i in range(G):
            w = int(grp[i])
            c = float(self.clock[w])
            if not IDEAL:
                c += ctrl2
                if self.chaos is not None:
                    c += self.chaos.retry1(w)
            c = max(c, t_rel)
            if has_pend[i] and npend and not IDEAL and FINE:
                c += lat * npend + pub_bytes / bw
                if self.chaos is not None:
                    c += self.chaos.retry1(w)
            ri = wi = 0
            for ga, lo, hi, p_lo, p_hi, is_w, _r in ops:
                if not is_w:
                    m = int(op_miss[ri][i])
                    ri += 1
                    if m and not IDEAL:
                        c += xfer(m * pb, 2 * -(-m // fb))
                        if self.chaos is not None:
                            c += self.chaos.retry1(w)
                    continue
                if self.model_mechanism and FINE:
                    c += (hi - lo) * self.instr_s_per_word
                if op_faults[wi] is not None:
                    c += int(op_faults[wi][i]) * self.fault_s
                first, last = op_edges[wi]
                wi += 1
                if first is not None and first[i]:
                    c += xfer(pb, 2)
                    if self.chaos is not None:
                        c += self.chaos.retry1(w)
                if last is not None and last[i]:
                    c += xfer(pb, 2)
                    if self.chaos is not None:
                        c += self.chaos.retry1(w)
            if not IDEAL and npend:
                c += lat * npend + pub_bytes / bw
                if self.chaos is not None:
                    c += self.chaos.retry1(w)
            if not IDEAL:
                c += ctrl1
                if self.chaos is not None:
                    c += self.chaos.retry1(w)
            self.clock[w] = c
            t_rel = c
        lk.last_release_time = t_rel
        self.stats["span_groups_vec"] += 1
        self.stats["span_workers_vec"] += G
        if len(regions) > 1:
            self.stats["span_multi_region_groups"] += 1
        return True

    def _span_track_touch(self, d: RegionDirectory, grp, gi, colm, IC,
                          region: int, p_lo: int, n: int, sl: slice):
        """LRU/touch bookkeeping of one uniform group op (cache runs
        only): one touch run per worker in the per-worker path's order,
        cache-slot entries counted off the gathered occupancy matrix.
        ``sl`` addresses [p_lo, p_lo+n) in the group's U-window columns.
        Eviction is impossible here (span_all screens it out), so the
        watermark never trips."""
        ticks = np.empty(grp.size, np.int64)
        for i, w in enumerate(grp):
            ticks[i] = self._q_append(int(w), region,
                                      int(p_lo - d.base[w]), n,
                                      int(d.shift[w]))
        d.touch[gi, colm[:, sl]] = ticks[:, None]
        enters = (~IC[:, sl]).sum(axis=1)
        IC[:, sl] = True
        self.resident[grp] += enters

    def span_all(self, w_mask=None, lock_ids=0, reads=(), writes=()):
        """One consistency-region pass for many workers in a single call.

        Equivalent — traffic field-for-field, clocks bit-equal — to the
        per-worker span loop::

            for w in <masked workers, ascending>:
                with rt.span(w, lock_ids[w]):
                    for ga, lo, hi in reads:  rt.read(w, ga, lo[w], hi[w])
                    for ga, lo, hi in writes: rt.write(w, ga, lo[w], hi[w])

        ``w_mask`` is a (W,) bool mask (None = all workers); ``lock_ids``
        scalar or (W,); ``reads``/``writes`` as in ``phase_all``.

        Lock grants are the only true serialization point, and they stay
        serialized (the release-time chain).  Everything around them
        pipelines:

        * every masked worker's acquire-time ordinary flush hoists into
          ONE batched sequential-flush pass (``_flush_all_workers`` over
          the mask) when the flushed dirty bounds provably cannot touch
          any span page or pending notice (``_span_flush_safe``);
        * workers sharing a lock form a *grant group*; uniform groups
          (same declared intervals, members synced to the lock's log)
          resolve analytically as plane ops (``_span_group_vec``) — the
          i-th holder's replay set is exactly the earlier holders'
          releases of this pass;
        * distinct locks' groups are mutually independent (span bodies
          touch only their own directory rows once eviction is excluded),
          so groups run one after another without interleaving cost.

        Falls back — exactly, never approximately — to the per-worker
        body for non-uniform groups, and to the fully serial worker-order
        loop when a span could evict (capacity pressure inside spans) or
        when flushed pages and span/notice pages may interact."""
        assert not any(self.spans), "span_all must run outside spans"
        self.chaos_tick()
        W = self.W
        if w_mask is None:
            rows = self._rows_all
        else:
            w_mask = np.asarray(w_mask)
            rows = (np.nonzero(w_mask)[0] if w_mask.dtype == bool
                    else np.unique(np.asarray(w_mask, np.int64)))
        locks = self._w_arr(lock_ids)
        reads = [(ga, self._w_arr(lo), self._w_arr(hi))
                 for ga, lo, hi in reads]
        writes = [(ga, self._w_arr(lo), self._w_arr(hi))
                  for ga, lo, hi in writes]
        self.stats["span_all_calls"] += 1
        if rows.size == 0:
            return
        rranges = [self._page_range_all(ga, lo, hi, prefetch=True)
                   for ga, lo, hi in reads]
        wranges = [self._page_range_all(ga, lo, hi, prefetch=False)
                   for ga, lo, hi in writes]
        serial = False
        if self.cache_pages is not None:
            # any possible in-span eviction (even the bookkeeping-only
            # IDEAL kind) serializes the whole pass: an eviction can
            # write back into another worker's reach and the LRU queue
            # walk is inherently tick-ordered
            ub = self.resident.copy()
            for region, p_lo, p_hi in rranges + wranges:
                ub += p_hi - p_lo
            serial = bool((ub[rows] > self.cache_pages).any())
        if not serial and self.protocol != IDEAL_PROTO:
            serial = not self._span_flush_safe(rows, locks,
                                               rranges + wranges)
        self._race_suspend = True
        if serial:
            self.stats["span_serial_calls"] += 1
            self.stats["span_serial_workers"] += int(rows.size)
            for w in rows:
                self._span_one(int(w), int(locks[w]), reads, writes)
        else:
            mask = np.zeros(W, bool)
            mask[rows] = True
            self._flush_all_workers(mask)
            for lk_id in np.unique(locks[rows]):
                grp = rows[locks[rows] == int(lk_id)]
                if not self._span_group_vec(grp, int(lk_id), reads, writes,
                                            rranges, wranges):
                    self.stats["span_serial_workers"] += int(grp.size)
                    for w in grp:
                        self._span_one(int(w), int(lk_id), reads, writes)
        self._race_suspend = False
        if self.detect_races:
            self._race_span_all(rows, locks, reads, writes)

    # ------------------------------------------------------------------
    def reduce(self, w: int, name: str, value: float, op: str = "sum"):
        self._reductions.setdefault(name, []).append((float(value), op))

    def reduce_all(self, name: str, values, op: str = "sum"):
        """Batched ``reduce``: one contribution per worker in a single
        call (``values`` scalar or (W,)); combines identically at the
        barrier (same values, same op, same reduction_msgs)."""
        vals = np.broadcast_to(np.asarray(values, np.float64), (self.W,))
        self._reductions.setdefault(name, []).extend(
            (float(v), op) for v in vals)

    def reduction_result(self, name: str) -> float:
        return self._reduction_results[name]

    def barrier(self):
        self.chaos_tick()
        self._flush_all_workers()
        if self.protocol != IDEAL_PROTO:
            for lk in self.locks.values():
                if (lk.seen == lk.version).all():
                    continue       # everyone current (usual post-span state)
                for w in range(self.W):
                    if lk.seen[w] == lk.version:
                        continue
                    u, lo_u, hi_u = lk.log.pending(int(lk.seen[w]),
                                                   lk.version)
                    lk.seen[w] = lk.version
                    if not u.size:
                        continue
                    if self.protocol == FINE_PROTO:
                        # fine-grain update of valid stale copies only
                        regions = np.searchsorted(
                            self._region_starts_np, u, "right") - 1
                        for r in np.unique(regions):
                            d = self.dirs[int(r)]
                            if d.base[w] < 0:
                                continue
                            m = regions == r
                            cols = u[m] - d.base[w]
                            inr = (cols >= 0) & (cols < d.length[w])
                            vcells = d.valid[w, np.where(inr, cols, 0)] & inr
                            self.traffic.diff_bytes += int(
                                ((hi_u[m] - lo_u[m]) * _WORD)[vcells].sum())
                    else:
                        n_inv = self._replay_invalidate(w, u, rearm=False)
                        self.traffic.invalidations += n_inv
                        if self.chaos is not None:
                            self.chaos.inval_msgs(n_inv)
        if self.straggler is not None:
            flagged = self.straggler.observe(self.clock - self._bar_clock0)
            self.stats["straggler_checks"] += 1
            self.stats["straggler_flags"] += len(flagged)
        log_w = max(1, int(np.ceil(np.log2(max(self.W, 2)))))
        for name, contribs in self._reductions.items():
            vals = [v for v, _ in contribs]
            op = contribs[0][1]
            fn = {"sum": np.sum, "max": np.max, "min": np.min}[op]
            self._reduction_results[name] = float(fn(vals))
            self.traffic.reduction_msgs += self.W - 1
        self._reductions.clear()
        if self.detect_races:
            # barrier orders everyone against everyone: join all views,
            # then every worker opens a fresh epoch
            j = self.race_vc.max(axis=0)
            self.race_vc[:] = j[None, :]
            self.race_vc[self._rows_all, self._rows_all] += 1
        t = float(self.clock.max()) + self.cost.net_latency_s * log_w * (
            0 if self.protocol == IDEAL_PROTO else 1) + 1e-7 * log_w
        self.clock[:] = t
        self._bar_clock0 = self.clock.copy()

    @property
    def time(self) -> float:
        return float(self.clock.max())

    # ------------------------------------------------------------------
    # barrier-consistent checkpoints (ft/coherence.py; DIRECTORY.md
    # "Recovery contract")
    # ------------------------------------------------------------------

    def snapshot(self, rows: "Optional[Tuple[int, int]]" = None
                 ) -> Tuple[dict, dict]:
        """Serialize the COMPLETE runtime state as (arrays, meta).

        Only legal at a consistent cut — no open spans, no unresolved
        reductions, no in-flight danger recording — i.e. right after a
        ``barrier()`` (or before any work).  At such a cut the directory
        planes, lock logs, LRU queues, clocks, traffic, stats, and the
        chaos/straggler counters are the *entire* protocol state:
        :meth:`from_snapshot` rebuilds a runtime whose every subsequent
        event is bit-identical to the original's.  ``arrays`` holds only
        numpy arrays (npz-shardable, no jax); ``meta`` is
        JSON-serializable.

        ``rows=(w_lo, w_hi)`` restricts the worker-major payload to one
        shard's contiguous worker slice (directory plane rows, clocks,
        LRU queues, lock ``seen`` vectors, per-worker chaos/straggler
        counters); worker-independent state (lock logs, reduction
        results, global counters) is carried in full by every slice —
        :meth:`compose_snapshots` reassembles the slices into a full
        snapshot and *asserts* the replicated globals agree bit-for-bit
        (the cluster's divergence check).  A slice records
        ``meta["slice"]`` and cannot be restored directly."""
        assert not any(self.spans), "snapshot inside an open span"
        assert not self._reductions, "snapshot with unresolved reductions"
        assert self._danger_rec is None, "snapshot during danger recording"
        arrays: Dict[str, np.ndarray] = {
            "clock": self.clock.copy(),
            "bar_clock0": self._bar_clock0.copy(),
            "resident": self.resident.copy(),
            "q_degraded": self._q_degraded.copy(),
        }
        # LRU touch-run queues: flat (N, 7) entry rows + per-worker counts
        lru_counts = np.array([len(q) for q in self._lru_q], np.int64)
        if int(lru_counts.sum()):
            lru_entries = np.array(
                [list(e) for q in self._lru_q for e in q], np.int64)
        else:
            lru_entries = np.zeros((0, 7), np.int64)
        arrays["lru_counts"] = lru_counts
        arrays["lru_entries"] = lru_entries
        dr_counts = np.array([len(s) for s in self._dirty_regions],
                             np.int64)
        arrays["dirty_region_counts"] = dr_counts
        arrays["dirty_region_flat"] = np.array(
            [r for s in self._dirty_regions for r in sorted(s)], np.int64)
        red_names = sorted(self._reduction_results)
        arrays["red_vals"] = np.array(
            [self._reduction_results[k] for k in red_names], np.float64)
        dir_metas = []
        for r, d in enumerate(self.dirs):
            darr, dmeta = d.state_arrays()
            for k, v in darr.items():
                arrays[f"d{r:05d}_{k}"] = v
            dir_metas.append(dmeta)
        lock_metas = []
        for j, (lid, lk) in enumerate(sorted(self.locks.items())):
            pre = f"lk{j:05d}_"
            arrays[pre + "seen"] = lk.seen.copy()
            arrays[pre + "lrt"] = np.array([lk.last_release_time],
                                           np.float64)
            if self.detect_races:
                arrays[pre + "vc"] = lk.race_vc.copy()
            for k, v in lk.log.state_arrays().items():
                arrays[pre + k] = v
            lock_metas.append({"id": int(lid), "version": int(lk.version)})
        if self.detect_races:
            # worker vector clocks slice per shard; the flagged set is
            # replicated (global) — compose_snapshots asserts it agrees
            # across shards, another divergence check for free
            arrays["race_vc"] = self.race_vc.copy()
            arrays["race_set"] = (np.array(
                sorted((p, a, b, 0 if kind == "ww" else 1)
                       for p, a, b, kind in self.races), np.int64)
                if self.races else np.zeros((0, 4), np.int64))
        if self.chaos is not None:
            arrays.update(self.chaos.state_arrays())
        if self.straggler is not None:
            for k, v in self.straggler.state_arrays().items():
                arrays["strag_" + k] = v
        meta = {
            "config": {"n_workers": self.W, "page_words": self.page_words,
                       "protocol": self.protocol,
                       "cache_pages": self.cache_pages,
                       "prefetch": self.prefetch,
                       "n_mem_servers": self.n_mem_servers,
                       "model_mechanism": self.model_mechanism,
                       "instr_s_per_word": self.instr_s_per_word,
                       "fault_s": self.fault_s,
                       "fetch_batch": self.fetch_batch,
                       "backend": self.backend,
                       "danger_mode": self.danger_mode,
                       "detect_races": self.detect_races},
            "cost": dataclasses.asdict(self.cost),
            "traffic": dataclasses.asdict(self.traffic),
            "stats": dict(self.stats),
            "tick": self._tick,
            "phase_idx": self._phase_idx,
            "n_pages": self.n_pages,
            "region_starts": [int(x) for x in self._region_starts],
            "region_ends": [int(x) for x in self._region_ends],
            "dirs": dir_metas,
            "locks": lock_metas,
            "red_names": red_names,
            "chaos": (None if self.chaos is None
                      else self.chaos.config()),
            "straggler": (None if self.straggler is None
                          else self.straggler.config()),
        }
        if rows is not None:
            w_lo, w_hi = int(rows[0]), int(rows[1])
            assert 0 <= w_lo < w_hi <= self.W, rows
            arrays = _slice_snapshot_arrays(arrays, w_lo, w_hi)
            meta["slice"] = [w_lo, w_hi]
        return arrays, meta

    @classmethod
    def from_snapshot(cls, arrays: dict, meta: dict, *,
                      injector=None) -> "RegCScaleRuntime":
        """Rebuild a runtime from :meth:`snapshot` output.  The clone is
        bit-identical going forward: same clocks, traffic, stats,
        directory planes, lock logs, LRU order, chaos counters.  Pass a
        (possibly already partially fired) ``injector`` to rearm failure
        injection on the replayed suffix."""
        assert meta.get("slice") is None, (
            "partial (shard-slice) snapshot: compose_snapshots first")
        cfg = meta["config"]
        chaos = None
        if meta.get("chaos") is not None:
            from repro.dsm.costmodel import ChaosNet
            chaos = ChaosNet(**meta["chaos"])
        straggler = None
        if meta.get("straggler") is not None:
            from repro.ft.runtime import StragglerMonitor
            sarr = {k[len("strag_"):]: v for k, v in arrays.items()
                    if k.startswith("strag_")}
            straggler = StragglerMonitor.from_state(sarr,
                                                    meta["straggler"])
        cache_pages = cfg["cache_pages"]
        rt = cls(int(cfg["n_workers"]),
                 page_words=int(cfg["page_words"]),
                 protocol=cfg["protocol"],
                 cost=CostModel(**meta["cost"]),
                 cache_pages=(None if cache_pages is None
                              else int(cache_pages)),
                 prefetch=int(cfg["prefetch"]),
                 n_mem_servers=int(cfg["n_mem_servers"]),
                 model_mechanism=bool(cfg["model_mechanism"]),
                 instr_s_per_word=float(cfg["instr_s_per_word"]),
                 fault_s=float(cfg["fault_s"]),
                 fetch_batch=int(cfg["fetch_batch"]),
                 backend=cfg["backend"],
                 danger_mode=cfg["danger_mode"],
                 detect_races=bool(cfg.get("detect_races", False)),
                 chaos=chaos, injector=injector, straggler=straggler)
        rt.n_pages = int(meta["n_pages"])
        rt._region_starts = [int(x) for x in meta["region_starts"]]
        rt._region_ends = [int(x) for x in meta["region_ends"]]
        rt._region_starts_np = np.asarray(rt._region_starts, np.int64)
        rt.dirs = []
        for r, dmeta in enumerate(meta["dirs"]):
            pre = f"d{r:05d}_"
            darr = {k[len(pre):]: v for k, v in arrays.items()
                    if k.startswith(pre)}
            d = RegionDirectory.from_state(darr, dmeta)
            d.jit_stats = rt.stats
            rt.dirs.append(d)
        rt.locks = {}
        for j, lm in enumerate(meta["locks"]):
            pre = f"lk{j:05d}_"
            lk = _Lock(rt.W)
            lk.version = int(lm["version"])
            lk.seen = np.asarray(arrays[pre + "seen"], np.int64).copy()
            lk.last_release_time = float(
                np.asarray(arrays[pre + "lrt"])[0])
            lk.log = IntervalLog.from_state(
                {k: arrays[pre + k] for k in ("p", "lo", "hi", "voff")})
            if pre + "vc" in arrays:
                lk.race_vc = np.asarray(arrays[pre + "vc"],
                                        np.int64).copy()
            rt.locks[int(lm["id"])] = lk
        if rt.detect_races:
            rt.race_vc = np.asarray(arrays["race_vc"], np.int64).copy()
            rs = np.asarray(arrays["race_set"], np.int64).reshape(-1, 4)
            rt.races = {(int(p), int(a), int(b), "ww" if k == 0 else "rw")
                        for p, a, b, k in rs}
        rt.clock = np.asarray(arrays["clock"], np.float64).copy()
        rt._bar_clock0 = np.asarray(arrays["bar_clock0"],
                                    np.float64).copy()
        rt.resident = np.asarray(arrays["resident"], np.int64).copy()
        rt._q_degraded = np.asarray(arrays["q_degraded"], bool).copy()
        lru_counts = np.asarray(arrays["lru_counts"], np.int64)
        ents = np.asarray(arrays["lru_entries"],
                          np.int64).reshape(-1, 7)
        rt._lru_q = []
        off = 0
        for w in range(rt.W):
            n = int(lru_counts[w])
            rt._lru_q.append(deque(
                [int(x) for x in e] for e in ents[off:off + n]))
            off += n
        dr_counts = np.asarray(arrays["dirty_region_counts"], np.int64)
        dr_flat = np.asarray(arrays["dirty_region_flat"], np.int64)
        rt._dirty_regions = []
        off = 0
        for w in range(rt.W):
            n = int(dr_counts[w])
            rt._dirty_regions.append(
                set(int(x) for x in dr_flat[off:off + n]))
            off += n
        rt.traffic = Traffic(**meta["traffic"])
        # IN PLACE: a bound ChaosNet holds a reference to rt.stats
        rt.stats.clear()
        rt.stats.update(meta["stats"])
        if chaos is not None:
            chaos.load_state(arrays)
        rt._tick = int(meta["tick"])
        rt._phase_idx = int(meta["phase_idx"])
        rt._reduction_results = {
            k: float(v) for k, v in zip(
                meta["red_names"],
                np.asarray(arrays["red_vals"], np.float64))}
        return rt

    @classmethod
    def compose_snapshots(cls, parts) -> Tuple[dict, dict]:
        """Reassemble shard-slice snapshots (``snapshot(rows=...)``
        output, any order) into one full (arrays, meta) restorable by
        :meth:`from_snapshot`.

        The slices must tile ``[0, W)`` exactly.  Worker-major arrays are
        concatenated in rank order; the replicated globals (lock logs,
        reduction results, global chaos/straggler counters, traffic,
        stats, configs) must agree bit-for-bit across every slice — a
        mismatch means the shard replicas diverged, which the cluster
        treats as a hard protocol error, not something to paper over."""
        parts = sorted(parts, key=lambda p: p[1]["slice"][0])
        assert parts, "compose_snapshots of nothing"
        metas = [m for _a, m in parts]
        W = int(metas[0]["config"]["n_workers"])
        bounds = [tuple(m["slice"]) for m in metas]
        want = 0
        for lo, hi in bounds:
            assert lo == want, f"slices do not tile: gap before {lo}"
            want = hi
        assert want == W, f"slices cover [0, {want}) of {W} workers"
        ref_meta = {k: v for k, v in metas[0].items() if k != "slice"}
        for m in metas[1:]:
            other = {k: v for k, v in m.items() if k != "slice"}
            assert other == ref_meta, "shard snapshot metas diverged"
        keys = set(parts[0][0])
        for a, _m in parts[1:]:
            assert set(a) == keys, "shard snapshot keys diverged"
        out: Dict[str, np.ndarray] = {}
        for k in keys:
            vals = [a[k] for a, _m in parts]
            if _snapshot_key_kind(k) == "global":
                for v in vals[1:]:
                    assert (v.dtype == vals[0].dtype
                            and np.array_equal(v, vals[0])), (
                        f"replicated snapshot key {k!r} diverged "
                        "across shards")
                out[k] = vals[0].copy()
            else:
                out[k] = np.concatenate(vals, axis=0)
        return out, ref_meta

    def gas_for_region(self, region: int, n_elems: int) -> GasArray:
        """Handle for an allocation that already exists in the directory
        (the restore-side replacement for ``alloc``: snapshots persist
        regions, not the caller's GasArray handles)."""
        return GasArray(self._region_starts[region], n_elems,
                        self.page_words)


# ---------------------------------------------------------------------------
# shard-slice snapshot plumbing (repro.cluster; DIRECTORY.md "Cluster
# contract").  Snapshot keys fall into three kinds:
#   rows   — worker-major, first dim W: sliced per shard, concatenated
#            back in rank order by compose_snapshots
#   flat   — variable-length per-worker payloads stored as (flat, counts)
#            pairs: sliced by the counts' prefix sums, concatenated back
#   global — worker-independent replicated state (lock logs/version
#            clocks, reduction results, global chaos/straggler totals):
#            carried whole by every slice, asserted bit-equal on compose
# ---------------------------------------------------------------------------

_SNAP_ROW_KEYS = frozenset({
    "clock", "bar_clock0", "resident", "q_degraded",
    "lru_counts", "dirty_region_counts", "race_vc",
    "chaos_msg_seq", "strag_hist_counts", "strag_streak"})
_SNAP_FLAT_COUNTS = {"lru_entries": "lru_counts",
                     "dirty_region_flat": "dirty_region_counts",
                     "strag_hist": "strag_hist_counts"}
_SNAP_DIR_RE = re.compile(r"^d\d{5}_")       # directory planes: all (W, ...)
# per-worker lock state: version seen + (detect_races) lock vector clock
_SNAP_SEEN_RE = re.compile(r"^lk\d{5}_(seen|vc)$")


def _snapshot_key_kind(key: str) -> str:
    if key in _SNAP_ROW_KEYS or _SNAP_DIR_RE.match(key) \
            or _SNAP_SEEN_RE.match(key):
        return "rows"
    if key in _SNAP_FLAT_COUNTS:
        return "flat"
    return "global"


def _slice_snapshot_arrays(arrays: Dict[str, np.ndarray], w_lo: int,
                           w_hi: int) -> Dict[str, np.ndarray]:
    out: Dict[str, np.ndarray] = {}
    for k, v in arrays.items():
        kind = _snapshot_key_kind(k)
        if kind == "rows":
            out[k] = v[w_lo:w_hi].copy()
        elif kind == "flat":
            counts = np.asarray(arrays[_SNAP_FLAT_COUNTS[k]], np.int64)
            off = np.concatenate([[0], np.cumsum(counts)])
            out[k] = v[int(off[w_lo]):int(off[w_hi])].copy()
        else:
            out[k] = v.copy()
    return out
