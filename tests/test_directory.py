"""Unit + cross-validation tests for the region-level sharing directory
(``repro.core.directory``) and the directory-vectorized protocol engine.

Unlike the hypothesis suite in test_regc_scale.py, these are deterministic
(seeded numpy RNG) so they run in environments without hypothesis — they
are the tier-1 oracle for the directory engine:

* random-trace cross-validation against the reference runtime, including
  cache-spill configurations (traffic exact, clocks to float tolerance);
* LRU equivalence: epoch-batched watermark eviction vs the reference's
  per-op LRU on cache-spill traces;
* STREAM / Jacobi / MD at small W through the interval fast path;
* directory primitive semantics (windows, shared intervals, notice logs).
"""
import dataclasses

import numpy as np
import pytest

from repro.core import FINE_PROTO, IDEAL_PROTO, PAGE_PROTO, RegCRuntime
from repro.core.directory import IntervalLog, RegionDirectory
from repro.core.regc import Traffic
from repro.core.regc_scale import RegCScaleRuntime
from repro.dsm.apps import jacobi, molecular_dynamics, stream_triad

PROTOS = [FINE_PROTO, PAGE_PROTO, IDEAL_PROTO]


# ---------------------------------------------------------------------------
# directory primitives
# ---------------------------------------------------------------------------


def test_window_ensure_grow_and_shift():
    d = RegionDirectory(3, 0, 0, 100, track_touch=True)
    d.ensure(1, 10, 14)
    d.valid[1, d.sl(1, 10, 14)] = True
    d.touch[1, d.sl(1, 10, 14)] = [1, 2, 3, 4]
    # left extension shifts existing cells and records the shift
    d.ensure(1, 6, 20)
    assert int(d.base[1]) == 6 and int(d.length[1]) == 14
    assert int(d.shift[1]) == 4
    assert d.valid[1, d.sl(1, 10, 14)].all()
    assert not d.valid[1, d.sl(1, 6, 10)].any()
    np.testing.assert_array_equal(d.touch[1, d.sl(1, 10, 14)], [1, 2, 3, 4])
    # wprot-free, dirty stays clear
    assert not d.dirty[1, : d.length[1]].any()


def test_overlap_rows_and_gather():
    d = RegionDirectory(4, 0, 0, 100)
    d.ensure(0, 0, 10)
    d.ensure(2, 8, 20)
    d.ensure(3, 50, 60)
    assert d.overlap_rows(5, 9).tolist() == [0, 2]
    assert d.overlap_rows(5, 9, exclude=0).tolist() == [2]
    d.valid[0, d.sl(0, 4, 9)] = True
    d.valid[2, d.sl(2, 8, 12)] = True
    rows = d.overlap_rows(0, 100)
    sub, cols = d.gather_valid(rows, np.array([4, 8, 55]))
    # row 0 valid at {4..8}, row 2 valid at {8..11}, row 3 nothing
    np.testing.assert_array_equal(
        sub, [[True, True, False], [False, True, False],
              [False, False, False]])


def test_shared_intervals_sweep():
    d = RegionDirectory(4, 0, 0, 1000)
    d.ensure(0, 0, 100)
    d.ensure(1, 90, 200)       # overlaps 0 on [90, 100)
    d.ensure(2, 300, 400)      # alone
    d.ensure(3, 150, 160)      # inside 1
    starts, ends = d.shared_intervals()
    assert list(zip(starts.tolist(), ends.tolist())) == [(90, 100),
                                                         (150, 160)]


def test_interval_log_segment_minmax():
    log = IntervalLog()
    log.append_version([5, 9], [10, 0], [20, 4])
    log.append_version([], [], [])
    log.append_version([5, 7], [2, 1], [8, 3])
    u, lo, hi = log.pending(0, 3)
    assert u.tolist() == [5, 7, 9]
    assert lo.tolist() == [2, 1, 0]          # per-page segment min
    assert hi.tolist() == [20, 3, 4]         # per-page segment max
    u2, lo2, hi2 = log.pending(2, 3)         # only the last version
    assert u2.tolist() == [5, 7]
    assert lo2.tolist() == [2, 1] and hi2.tolist() == [8, 3]
    assert log.pending(3, 3)[0].size == 0


def test_span_planes_note_harvest_roundtrip():
    d = RegionDirectory(3, 0, 0, 100)
    d.ensure(1, 10, 20)
    # scalar single-page merges + a vector note, like in-span writes
    d.span_note(1, 12, 13, 5, 9)
    d.span_note(1, 12, 13, 2, 7)             # (min, max)-merge: (2, 9)
    d.span_note(1, 14, 17, np.array([0, 3, 1]), np.array([8, 6, 4]))
    pages, los, his = d.span_harvest(1, 10, 20)
    assert pages.tolist() == [12, 14, 15, 16]
    assert los.tolist() == [2, 0, 3, 1]
    assert his.tolist() == [9, 8, 6, 4]
    # harvest resets: a second harvest over the same bounds is empty
    assert d.span_harvest(1, 10, 20)[0].size == 0
    # other rows untouched
    assert d.span_harvest(0, 10, 20)[0].size == 0


def test_span_planes_survive_window_growth():
    d = RegionDirectory(2, 0, 0, 100)
    d.ensure(0, 10, 14)
    d.span_note(0, 11, 12, 1, 3)
    d.ensure(0, 4, 30)               # left extension + cap growth
    d.span_note(0, 25, 26, 0, 2)
    pages, los, his = d.span_harvest(0, 4, 30)
    assert pages.tolist() == [11, 25]
    assert los.tolist() == [1, 0] and his.tolist() == [3, 2]


def test_interval_log_append_versions_batched():
    a, b = IntervalLog(), IntervalLog()
    payload = (np.array([3, 7], np.int64), np.array([1, 0], np.int64),
               np.array([4, 8], np.int64))
    for _ in range(3):
        a.append_version(*payload)
    a.append_version([], [], [])
    b.append_versions(np.tile(payload[0], 3), np.tile(payload[1], 3),
                      np.tile(payload[2], 3), np.array([2, 2, 2], np.int64))
    b.append_versions(np.zeros(0, np.int64), np.zeros(0, np.int64),
                      np.zeros(0, np.int64), np.array([0], np.int64))
    assert a.voff == b.voff
    for v0 in range(4):
        for v1 in range(v0, 5):
            ua, la, ha = a.pending(v0, v1)
            ub, lb, hb = b.pending(v0, v1)
            np.testing.assert_array_equal(ua, ub)
            np.testing.assert_array_equal(la, lb)
            np.testing.assert_array_equal(ha, hb)
    assert a.page_bounds(0, 3) == (3, 8) == b.page_bounds(0, 3)
    assert a.page_bounds(3, 4) is None


# ---------------------------------------------------------------------------
# bitmask protocol-sweep kernels: packed uint32 planes vs boolean oracle
# ---------------------------------------------------------------------------


def test_bitmask_pack_popcount_matches_boolean_plane():
    from repro.kernels import protocol_sweep as ps
    rng = np.random.default_rng(7)
    for W, C in ((1, 1), (3, 31), (8, 32), (37, 1000), (256, 513)):
        plane = rng.random((W, C)) < 0.3
        bits = ps.pack_mask_rows(plane)
        assert bits.shape == (W, -(-C // 32)) and bits.dtype == np.uint32
        np.testing.assert_array_equal(ps.unpack_mask_rows(bits, C), plane)
        np.testing.assert_array_equal(ps.popcount_rows(bits),
                                      plane.sum(axis=1))


def test_bitmask_popcount_pallas_matches_numpy():
    pytest.importorskip("jax")
    from repro.kernels import protocol_sweep as ps
    rng = np.random.default_rng(11)
    plane = rng.random((41, 700)) < 0.5
    bits = ps.pack_mask_rows(plane)
    np.testing.assert_array_equal(ps.popcount_rows(bits, backend="pallas"),
                                  plane.sum(axis=1))


def test_coverage_sweep_pallas_matches_numpy():
    pytest.importorskip("jax")
    from repro.kernels import protocol_sweep as ps
    rng = np.random.default_rng(13)
    for n in (2, 9, 128, 515):
        delta = rng.choice(np.array([1, -1], np.int64), n)
        np.testing.assert_array_equal(
            ps.coverage_multi(delta, backend="pallas"),
            np.cumsum(delta) >= 2)


def test_take_first_k_matches_boolean_oracle():
    """Packed rank-select (the eviction engine's segment-LRU selection)
    vs the boolean-plane oracle: first k[i] set bits per row, little-
    endian column order."""
    from repro.kernels import protocol_sweep as ps
    rng = np.random.default_rng(17)
    for R, C in ((1, 1), (4, 31), (8, 64), (33, 517), (128, 90)):
        live = rng.random((R, C)) < 0.4
        k = rng.integers(0, C + 3, R).astype(np.int64)
        bits = ps.pack_mask_rows(live)
        got = ps.unpack_mask_rows(ps.take_first_k(bits, k), C)
        want = live & (np.cumsum(live, axis=1) <= k[:, None])
        np.testing.assert_array_equal(got, want, err_msg=f"{R}x{C}")


def test_take_first_k_pallas_matches_numpy():
    pytest.importorskip("jax")
    from repro.kernels import protocol_sweep as ps
    rng = np.random.default_rng(19)
    live = rng.random((23, 333)) < 0.5
    k = rng.integers(0, 200, 23).astype(np.int64)
    bits = ps.pack_mask_rows(live)
    np.testing.assert_array_equal(
        ps.take_first_k(bits, k, backend="pallas"),
        ps.take_first_k(bits, k, backend="numpy"))


def test_kth_set_index_matches_boolean_oracle():
    """Packed rank query (the refetch replay engine's victim-scan cut)
    vs the boolean oracle: column of each row's k-th set bit, -1 when
    the row holds fewer than k (or k <= 0)."""
    from repro.kernels import protocol_sweep as ps
    rng = np.random.default_rng(23)
    for R, C in ((1, 1), (4, 31), (8, 64), (33, 517), (128, 90)):
        live = rng.random((R, C)) < 0.4
        k = rng.integers(-1, C + 3, R).astype(np.int64)
        got = ps.kth_set_index(ps.pack_mask_rows(live), k)
        for r in range(R):
            idx = np.flatnonzero(live[r])
            want = idx[k[r] - 1] if 1 <= k[r] <= idx.size else -1
            assert got[r] == want, (R, C, r, k[r])


def test_kth_set_index_pallas_matches_numpy():
    pytest.importorskip("jax")
    from repro.kernels import protocol_sweep as ps
    rng = np.random.default_rng(29)
    live = rng.random((23, 333)) < 0.5
    k = rng.integers(0, 200, 23).astype(np.int64)
    bits = ps.pack_mask_rows(live)
    np.testing.assert_array_equal(
        ps.kth_set_index(bits, k, backend="pallas"),
        ps.kth_set_index(bits, k, backend="numpy"))


def test_jit_kernels_match_numpy_oracles():
    """Every jitted kernel tier vs its numpy oracle, with the dispatch
    accounting live: popcount, rank-select, rank-query, coverage, and
    the fused ``take_and_cut`` (one dispatch for what the unfused path
    does in two)."""
    pytest.importorskip("jax")
    from repro.kernels import protocol_sweep as ps
    rng = np.random.default_rng(37)
    live = rng.random((29, 451)) < 0.45
    k = rng.integers(0, 300, 29).astype(np.int64)
    bits = ps.pack_mask_rows(live)
    st = {}
    np.testing.assert_array_equal(
        ps.popcount_rows(bits, backend="pallas-jit", stats=st),
        ps.popcount_rows(bits))
    np.testing.assert_array_equal(
        ps.take_first_k(bits, k, backend="pallas-jit", stats=st),
        ps.take_first_k(bits, k))
    np.testing.assert_array_equal(
        ps.kth_set_index(bits, k, backend="pallas-jit", stats=st),
        ps.kth_set_index(bits, k))
    delta = rng.choice(np.array([1, -1], np.int64), 513)
    np.testing.assert_array_equal(
        ps.coverage_multi(delta, backend="pallas-jit", stats=st),
        np.cumsum(delta) >= 2)
    take_j, cut_j = ps.take_and_cut(bits, k, backend="pallas-jit",
                                    stats=st)
    np.testing.assert_array_equal(take_j, ps.take_first_k(bits, k))
    np.testing.assert_array_equal(cut_j, ps.kth_set_index(bits, k))
    # five jit entries above -> five device dispatches, no silent
    # numpy fallback
    assert st["jit_dispatches"] == 5, st


I32MAX = np.iinfo(np.int32).max


def _flush_stack(regions, nw, rng):
    """An (R, W, nw) fused-flush batch from per-region row windows: each
    region is a list of (base, length) rows, base -1 for a dead row.  Live
    rows get random dirty bits over their whole packed row, dead rows none;
    the sorted live bounds are padded with INT32_MAX."""
    R, W = len(regions), len(regions[0])
    bits = rng.integers(0, 1 << 32, (R, W, nw), dtype=np.uint64)
    bits = (bits & rng.integers(0, 1 << 32, (R, W, nw),
                                dtype=np.uint64)).astype(np.uint32)
    base = np.full((R, W), -1, np.int32)
    sbs = np.full((R, W), I32MAX, np.int32)
    ses = np.full((R, W), I32MAX, np.int32)
    for r, rows in enumerate(regions):
        live = [(w, b, n) for w, (b, n) in enumerate(rows) if b >= 0]
        for w, b, _ in live:
            base[r, w] = b
        bits[r, base[r] < 0] = 0
        sbs[r, :len(live)] = np.sort([b for _, b, _ in live])
        ses[r, :len(live)] = np.sort([b + n for _, b, n in live])
    return bits, base, sbs, ses


def _random_regions(rng):
    """Three regions of seven rows, some dead, windows of 1-200 pages over
    a 5000-page span (the multi-region stacks the runtime batches)."""
    regions = []
    for _ in range(3):
        rows = [(-1, 0)] * 7
        for w in rng.choice(7, int(rng.integers(2, 8)), replace=False):
            rows[w] = (int(rng.integers(0, 5000)), int(rng.integers(1, 201)))
        regions.append(rows)
    return regions, 7


def _jacobi_halo(nw):
    """Jacobi's flush geometry at W=256, each worker's window its
    16384-page block: nw 1024 is the stencil read plus a 64-page halo on
    each side, nw 513 the copy phase's windows that reach one page into
    the next block; a seeded permutation places the blocks."""
    W, B = 256, 16384
    halo = 64 if nw == 1024 else 0
    rows = []
    for blk in np.random.default_rng(5).permutation(W):
        lo = max(int(blk) * B - halo, 0)
        hi = min((int(blk) + 1) * B + (halo or 1), W * B)
        rows.append((lo, hi - lo))
    return [rows], nw


FLUSH_GEOMETRIES = {
    # windows shorter than one 32-page word, several flips in one word
    "short_windows": lambda rng: ([[(3, 2), (4, 5), (6, 1), (9, 20),
                                    (10, 3), (40, 7), (44, 1), (45, 30)]], 3),
    # bases off the 32-page grid, overlaps straddling word edges
    "unaligned_bases": lambda rng: ([[(13, 70), (31, 33), (63, 2), (65, 90),
                                      (97, 31), (150, 17)]], 5),
    # nested and identical windows: coverage 3 and 4 stays one interval
    "nested_identical": lambda rng: ([[(0, 300), (50, 100), (50, 100),
                                       (60, 20), (200, 1), (200, 1)]], 10),
    # one window ends where the next starts: no gap, no overlap
    "touching": lambda rng: ([[(0, 64), (64, 64), (128, 5), (133, 40),
                               (100, 33), (140, 1)]], 6),
    # dead rows (base -1) among live ones, INT32_MAX pads in the bounds
    "dead_rows_and_pads": lambda rng: ([[(0, 50), (-1, 0), (30, 60),
                                         (-1, 0), (45, 3)],
                                        [(-1, 0)] * 5,
                                        [(-1, 0), (7, 9), (-1, 0), (-1, 0),
                                         (-1, 0)]], 4),
    # bounds just under the int32 guard of RegCScaleRuntime._jit_flush_chain
    # (base + 32 * nw must stay below INT32_MAX)
    "int32_guard": lambda rng: ([[(I32MAX - 1 - 32 * 8 - d, n)
                                  for d, n in ((0, 256), (40, 100), (100, 90),
                                               (200, 77), (3, 1))]], 8),
    "random_stacks": _random_regions,
    "jacobi_w256_nw1024": lambda rng: _jacobi_halo(1024),
    "jacobi_w256_nw513": lambda rng: _jacobi_halo(513),
}


@pytest.mark.parametrize("geometry", list(FLUSH_GEOMETRIES))
def test_phase_step_jit_matches_numpy_oracle(geometry):
    """The fused barrier-flush chain (flip points, then word masks) vs its
    per-page searchsorted numpy oracle: per-row dirty counts AND the packed
    shared-dirty candidate masks (dirty & >=2-coverage & active row), bit
    for bit, in one device dispatch, on adversarial window geometries and
    on the two benchmark cells' Jacobi shapes."""
    pytest.importorskip("jax")
    from repro.kernels import protocol_sweep as ps
    rng = np.random.default_rng(41)
    candidates = 0
    for trial in range(4 if geometry == "random_stacks" else 1):
        regions, nw = FLUSH_GEOMETRIES[geometry](rng)
        bits, base, sbs, ses = _flush_stack(regions, nw, rng)
        rowmask = rng.random(base.shape) < 0.8
        rowmask[:, ::2] = True           # even rows flushed, odd ones drawn
        st = {}
        counts, shared = ps.phase_step(bits, base, rowmask, sbs, ses,
                                       stats=st)
        counts_np, shared_np = ps._phase_step_np(bits, base, rowmask,
                                                 sbs, ses)
        np.testing.assert_array_equal(counts, counts_np, err_msg=str(trial))
        np.testing.assert_array_equal(shared, shared_np, err_msg=str(trial))
        assert st["jit_dispatches"] == 1, st
        candidates += int(np.count_nonzero(shared_np))
    assert candidates > 0            # every geometry has shared dirty pages


def test_force_numpy_env_override_wins():
    """``REPRO_FORCE_NUMPY=1`` pins every backend request to the numpy
    tier through the cached one-shot probe: ``available_backends``
    collapses, ``resolve_backend`` degrades both accelerated tiers, and
    a 'pallas-jit' runtime runs the whole trace without a single device
    dispatch — while staying traffic/clock exact."""
    from repro.kernels import protocol_sweep as ps
    import os
    old = os.environ.get(ps._FORCE_ENV)
    os.environ[ps._FORCE_ENV] = "1"
    ps._reset_backend_probe()
    try:
        assert ps.available_backends() == ("numpy",)
        assert ps.resolve_backend("pallas-jit") == "numpy"
        assert ps.resolve_backend("pallas") == "numpy"
        rts = {}
        for backend in ("numpy", "pallas-jit"):
            rt = RegCScaleRuntime(4, page_words=32, protocol=PAGE_PROTO,
                                  prefetch=1, cache_pages=6,
                                  backend=backend)
            ga = rt.alloc(32 * 40)
            ids = np.arange(4, dtype=np.int64)
            for _ in range(3):
                rt.phase_all(writes=[(ga, ids * 320, ids * 320 + 340)])
                rt.barrier()
            rts[backend] = rt
        for f in dataclasses.fields(Traffic):
            assert (getattr(rts["numpy"].traffic, f.name)
                    == getattr(rts["pallas-jit"].traffic, f.name)), f.name
        np.testing.assert_array_equal(rts["numpy"].clock,
                                      rts["pallas-jit"].clock)
        assert rts["pallas-jit"].stats["jit_dispatches"] == 0
    finally:
        if old is None:
            os.environ.pop(ps._FORCE_ENV, None)
        else:
            os.environ[ps._FORCE_ENV] = old
        ps._reset_backend_probe()


@pytest.mark.parametrize("backend", ["numpy", "pallas", "pallas-jit"])
def test_take_upto_row_rank_select(backend):
    """The replay engine's one-run victim scan: first k live cells plus
    the scan cut, packed kernels on 'pallas' (and the fused one-dispatch
    ``take_and_cut`` on 'pallas-jit') vs the cumsum path — all must
    agree with the boolean oracle (caller guarantees count > k)."""
    if backend != "numpy":
        pytest.importorskip("jax")
    from repro.core.directory import RegionDirectory
    d = RegionDirectory(1, 0, 0, 64, backend=backend)
    rng = np.random.default_rng(31)
    for C in (5, 33, 64, 257):
        live = rng.random(C) < 0.5
        tot = int(live.sum())
        if tot < 2:
            live[:2] = True
            tot = int(live.sum())
        k = int(rng.integers(1, tot))          # strictly fewer than live
        take, cut = d.take_upto_row(live, k)
        idx = np.flatnonzero(live)
        want = np.zeros(C, bool)
        want[idx[:k]] = True
        np.testing.assert_array_equal(take, want, err_msg=f"{backend} {C}")
        assert cut == idx[k - 1] + 1, (backend, C, k)


@pytest.mark.parametrize("backend", ["numpy", "pallas", "pallas-jit"])
def test_evict_rows_matches_per_cell_oracle(backend):
    """The batched eviction primitive (dirty counts, wprot re-arm,
    valid/incache clears at the take cells — and only there) against a
    straight per-cell simulation, packed-vs-boolean parity on every
    backend, including the take=None whole-span fast path."""
    if backend != "numpy":
        pytest.importorskip("jax")
    rng = np.random.default_rng(23)
    for trial in range(4):
        d = RegionDirectory(8, 0, 0, 500, track_wprot=True,
                            track_touch=True, backend=backend)
        for w in range(8):
            d.ensure(w, 0, 80)
        n = 80
        d.valid[:, :n] = rng.random((8, n)) < 0.6
        d.dirty[:, :n] = rng.random((8, n)) < 0.3
        d.incache[:, :n] = d.valid[:, :n] | (rng.random((8, n)) < 0.2)
        rows = np.arange(1, 7)
        start, length = 10, 50
        take = (None if trial % 2 else
                rng.random((rows.size, length)) < 0.5)
        ref = {p: d.__getattribute__(p)[:, :n].copy()
               for p in ("valid", "dirty", "wprot", "incache")}
        tk = (np.ones((rows.size, length), bool) if take is None else take)
        exp_db = np.zeros(rows.size, np.int64)
        for i, w in enumerate(rows):
            for j in range(length):
                if not tk[i, j]:
                    continue
                c = start + j
                if ref["dirty"][w, c]:
                    exp_db[i] += 1
                    ref["dirty"][w, c] = False
                    ref["wprot"][w, c] = True
                ref["valid"][w, c] = False
                ref["incache"][w, c] = False
        db = d.evict_rows(rows, start, length, take, set_wprot=True)
        np.testing.assert_array_equal(db, exp_db)
        for p in ("valid", "dirty", "wprot", "incache"):
            np.testing.assert_array_equal(
                d.__getattribute__(p)[:, :n], ref[p], err_msg=p)


def test_run_live_and_lru_take_segment_semantics():
    """run_live: a cell is live iff its touch tick still equals the run's
    tick AND it still occupies a cache slot; lru_take picks the first k
    live cells (columnar fast path when fully live)."""
    d = RegionDirectory(3, 0, 0, 100, track_touch=True)
    for w in range(3):
        d.ensure(w, 0, 20)
    d.touch[:, :10] = 7
    d.incache[:, :10] = True
    d.touch[1, 3] = 9              # re-touched by a later run -> stale
    d.incache[2, 5] = False        # evicted -> not live
    rows = np.arange(3)
    live = d.run_live(rows, 0, 10, np.full(3, 7, np.int64))
    assert live[0].all()
    assert not live[1, 3] and live[1, :3].all() and live[1, 4:].all()
    assert not live[2, 5]
    take = d.lru_take(live, np.array([4, 4, 4]))
    np.testing.assert_array_equal(take.sum(axis=1), [4, 4, 4])
    # row 1 skips the stale cell: takes cols 0,1,2,4
    assert not take[1, 3] and take[1, 4]
    # fully-live fast path: columnar cutoff
    full = d.lru_take(live[:1], np.array([3]), np.array([10]))
    np.testing.assert_array_equal(full[0, :4], [True] * 3 + [False])


def test_directory_backends_agree():
    """dirty_counts + shared_intervals identical on every backend (the
    packed-bitmask kernels are integer-exact reformulations)."""
    pytest.importorskip("jax")
    dirs = {}
    for backend in ("numpy", "pallas", "pallas-jit"):
        d = RegionDirectory(6, 0, 0, 4000, backend=backend)
        rng2 = np.random.default_rng(3)
        for w in range(6):
            lo = int(rng2.integers(0, 3000))
            d.ensure(w, lo, lo + int(rng2.integers(1, 900)))
            n = int(d.length[w])
            d.dirty[w, :n] = rng2.random(n) < 0.2
        dirs[backend] = d
    for backend in ("pallas", "pallas-jit"):
        np.testing.assert_array_equal(dirs["numpy"].dirty_counts(),
                                      dirs[backend].dirty_counts(),
                                      err_msg=backend)
        s_np, e_np = dirs["numpy"].shared_intervals()
        s_pl, e_pl = dirs[backend].shared_intervals()
        np.testing.assert_array_equal(s_np, s_pl, err_msg=backend)
        np.testing.assert_array_equal(e_np, e_pl, err_msg=backend)


def test_runtime_backend_pallas_matches_numpy_trace():
    pytest.importorskip("jax")
    from repro.dsm.apps import jacobi
    rts = {}
    for backend in ("numpy", "pallas"):
        rt = RegCScaleRuntime(6, protocol=PAGE_PROTO, prefetch=1,
                              backend=backend)
        jacobi(rt, 128, 2, mode="lock")
        rts[backend] = rt
    for f in dataclasses.fields(Traffic):
        assert (getattr(rts["numpy"].traffic, f.name)
                == getattr(rts["pallas"].traffic, f.name)), f.name
    np.testing.assert_array_equal(rts["numpy"].clock, rts["pallas"].clock)


# ---------------------------------------------------------------------------
# random-trace cross-validation vs the reference runtime (deterministic)
# ---------------------------------------------------------------------------


def gen_trace(rng, n_ops=40):
    ops = []
    depth = {w: [] for w in range(3)}
    for _ in range(n_ops):
        w = int(rng.integers(0, 3))
        kind = rng.choice(["read", "write", "acquire", "release", "barrier"])
        if kind == "release":
            if not depth[w]:
                continue
            ops.append(("release", w, depth[w].pop()))
        elif kind == "acquire":
            if len(depth[w]) >= 2:
                continue
            lock = int(rng.integers(0, 2))
            depth[w].append(lock)
            ops.append(("acquire", w, lock))
        elif kind == "barrier":
            if any(depth.values()):
                continue
            ops.append(("barrier",))
        else:
            arr = int(rng.integers(0, 2))
            lo = int(rng.integers(0, 250))
            hi = int(rng.integers(lo + 1, min(lo + 120, 256) + 1))
            ops.append((kind, w, arr, lo, hi))
    for w in range(3):
        while depth[w]:
            ops.append(("release", w, depth[w].pop()))
    ops.append(("barrier",))
    return ops


def run_trace(rt, ops, arrays):
    for op in ops:
        if op[0] == "read":
            rt.read(op[1], arrays[op[2]], op[3], op[4])
        elif op[0] == "write":
            rt.write(op[1], arrays[op[2]], op[3], op[4])
        elif op[0] == "acquire":
            rt.acquire(op[1], op[2])
        elif op[0] == "release":
            rt.release(op[1], op[2])
        else:
            rt.barrier()
    return rt


def assert_same(ref, fast, ctx=""):
    for f in dataclasses.fields(Traffic):
        assert getattr(ref.traffic, f.name) == getattr(fast.traffic, f.name), (
            ctx, f.name, ref.traffic, fast.traffic)
    np.testing.assert_allclose(fast.clock, ref.clock, rtol=1e-9, atol=1e-12)


@pytest.mark.parametrize("cache_pages", [None, 4, 2, 7])
def test_random_traces_match_reference(cache_pages):
    for seed in range(60):
        rng = np.random.default_rng(seed)
        ops = gen_trace(rng)
        proto = PROTOS[seed % 3]
        pw = [32, 64][seed % 2]
        ref = RegCRuntime(3, page_words=pw, protocol=proto,
                          track_values=False, prefetch=1,
                          cache_pages=cache_pages)
        fast = RegCScaleRuntime(3, page_words=pw, protocol=proto, prefetch=1,
                                model_mechanism=False,
                                cache_pages=cache_pages)
        run_trace(ref, ops, [ref.alloc(256), ref.alloc(256)])
        run_trace(fast, ops, [fast.alloc(256), fast.alloc(256)])
        assert_same(ref, fast, f"seed={seed} proto={proto} pw={pw} "
                               f"cache={cache_pages}")
        if cache_pages is not None and proto != IDEAL_PROTO:
            # occupancy counter == per-worker LRU dict length of the ref
            occ = [sum(int(d.incache[w, :d.length[w]].sum())
                       for d in fast.dirs if d.base[w] >= 0)
                   for w in range(3)]
            assert occ == [len(ref.lru[w]) for w in range(3)]
            assert occ == fast.resident.tolist()


# ---------------------------------------------------------------------------
# LRU equivalence of the epoch-batched eviction (cache-spill traces)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("proto", [FINE_PROTO, PAGE_PROTO])
@pytest.mark.parametrize("cache_pages", [3, 6, 11])
def test_epoch_batched_eviction_matches_per_op_lru(proto, cache_pages):
    """Streaming sweeps over a working set larger than the cache: the
    scale engine's watermark-triggered batched eviction must produce the
    reference's per-op LRU traffic exactly — same fetch counts (capacity
    misses), same dirty-victim writebacks, same sharer invalidations."""
    ref = RegCRuntime(2, page_words=64, protocol=proto, track_values=False,
                      prefetch=1, cache_pages=cache_pages)
    fast = RegCScaleRuntime(2, page_words=64, protocol=proto, prefetch=1,
                            model_mechanism=False, cache_pages=cache_pages)
    for rt in (ref, fast):
        a = rt.alloc(64 * 10)
        b = rt.alloc(64 * 10)
        for sweep in range(3):
            for w in range(2):
                for blk in range(5):
                    rt.read(w, a, blk * 128, blk * 128 + 128)
                    rt.write(w, b, blk * 128 + 7, blk * 128 + 121)  # partial
            rt.barrier()
    assert_same(ref, fast, f"{proto} cache={cache_pages}")


def test_danger_path_prefetch_refetch():
    """The op pattern where batched eviction alone would diverge: a read
    whose prefetch page is valid at op start but evicted by the same op's
    earlier fetches (the reference refetches it mid-op)."""
    ref = RegCRuntime(1, page_words=64, protocol=FINE_PROTO,
                      track_values=False, prefetch=1, cache_pages=2)
    fast = RegCScaleRuntime(1, page_words=64, protocol=FINE_PROTO,
                            prefetch=1, model_mechanism=False, cache_pages=2)
    for rt in (ref, fast):
        ga = rt.alloc(256)
        rt.write(0, ga, 140, 148)      # page 2 resident + dirty
        rt.read(0, ga, 16, 73)         # pages 0-1 + prefetch 2: evicts 2
        rt.barrier()
    assert_same(ref, fast, "prefetch-refetch")
    assert ref.traffic.page_fetches == 4      # page 2 fetched twice


# ---------------------------------------------------------------------------
# paper apps at small W (interval fast path end-to-end)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("proto", PROTOS)
def test_apps_match_reference_stream(proto):
    ref = RegCRuntime(4, protocol=proto, track_values=False, prefetch=1)
    fast = RegCScaleRuntime(4, protocol=proto, prefetch=1,
                            model_mechanism=False)
    stream_triad(ref, 64 * 1024, 3)
    stream_triad(fast, 64 * 1024, 3)
    assert_same(ref, fast, f"stream {proto}")


@pytest.mark.parametrize("proto", PROTOS)
@pytest.mark.parametrize("mode", ["lock", "reduction"])
def test_apps_match_reference_jacobi_md(proto, mode):
    for app, kw in ((jacobi, dict(n=256, iters=3, mode=mode)),
                    (molecular_dynamics,
                     dict(n_particles=256, iters=2, mode=mode))):
        ref = RegCRuntime(4, protocol=proto, track_values=False, prefetch=1)
        fast = RegCScaleRuntime(4, protocol=proto, prefetch=1,
                                model_mechanism=False)
        app(ref, **kw)
        app(fast, **kw)
        assert_same(ref, fast, f"{app.__name__} {proto} {mode}")


def test_apps_match_reference_spill():
    """STREAM under a cache smaller than the per-worker working set."""
    for W, cache in ((4, 10), (2, 5)):
        ref = RegCRuntime(W, protocol=FINE_PROTO, track_values=False,
                          prefetch=1, cache_pages=cache)
        fast = RegCScaleRuntime(W, protocol=FINE_PROTO, prefetch=1,
                                model_mechanism=False, cache_pages=cache)
        stream_triad(ref, 64 * 1024, 3)
        stream_triad(fast, 64 * 1024, 3)
        assert_same(ref, fast, f"spill W={W} cache={cache}")
