"""A plain model of the RegC protocol's traffic and modeled clocks.

Written from the protocol's stated rules (arXiv:1301.4490 §III-§V) and
the cost rules that the configuration file states; it shares no code with
the runtime under test.  It keeps, for every worker, plain sets of pages
as sorted interval lists -- the pages it holds valid, the pages it holds
dirty, and, with a finite cache, its cache slots in least-recently-used
order -- and walks the operations one worker at a time, in worker order,
as the fork-join program would run them.

The rules it follows:

* A read of words [lo, hi) touches its pages in order, then the next
  ``prefetch`` pages of the array.  A touched page that is not valid is
  fetched: one page of bytes, and the op's misses travel in request /
  reply pairs of up to ``fetch_batch`` pages.
* A write touches its pages the same way, in order; only a partial page
  is fetched (write-allocate), each such edge page as its own request.  Every
  written word costs the instrumented store ``instr_s_per_word``, and the
  pages turn dirty.
* With a cache of ``cache_pages`` slots per worker, a touch moves the
  page to the most recent end; a page that takes a new slot past the
  limit evicts the least recently touched one.  An evicted dirty page is
  written back (one message per page) and invalidates every other valid
  copy; an evicted page is no longer valid.  An invalidated page keeps
  its slot until evicted.
* A phase's compute costs ``max(flops / flops_per_worker, mem_bytes /
  bandwidth)``, the bandwidth being the node's share per worker.
* A barrier flushes every worker's dirty pages in worker order: per
  worker and array, the pages' bytes in messages of up to
  ``fetch_batch`` pages, and every other worker's valid copy of a
  flushed page is invalidated (one invalidation and one control message
  each).  A reduction costs W - 1 messages.  Then every clock joins at
  the latest plus ``ceil(log2 W)`` levels of network latency and of
  ``barrier_s_per_level``.

Only the fine-grain protocol outside lock spans is modeled: what the
DSM cells run.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

WORD_BYTES = 4
FIELDS = ("page_fetches", "fetch_bytes", "writeback_bytes", "diff_bytes",
          "invalidations", "control_msgs", "reduction_msgs")


class Pages:
    """A set of page numbers as sorted, disjoint, non-adjacent [a, b)."""

    def __init__(self):
        self.iv: List[List[int]] = []

    def count(self, a: int, b: int) -> int:
        return sum(max(0, min(b, y) - max(a, x)) for x, y in self.iv)

    def add(self, a: int, b: int):
        if a >= b:
            return
        out, placed = [], False
        for x, y in self.iv:
            if y < a:
                out.append([x, y])
            elif x > b:
                if not placed:
                    out.append([a, b])
                    placed = True
                out.append([x, y])
            else:
                a, b = min(a, x), max(b, y)
        if not placed:
            out.append([a, b])
        self.iv = out

    def remove(self, a: int, b: int) -> List[Tuple[int, int]]:
        """Take [a, b) out; return the pieces that were in the set."""
        out, gone = [], []
        for x, y in self.iv:
            if y <= a or x >= b:
                out.append([x, y])
                continue
            gone.append((max(a, x), min(b, y)))
            if x < a:
                out.append([x, a])
            if y > b:
                out.append([b, y])
        self.iv = out
        return gone

    def first_in(self, a: int, b: int):
        """The first page of the set in [a, b), or None."""
        for x, y in self.iv:
            if y > a and x < b:
                return max(a, x)
        return None

    def run_end(self, p: int, b: int) -> int:
        """The end (below b) of the set's run that holds page p."""
        for x, y in self.iv:
            if x <= p < y:
                return min(y, b)
        raise KeyError(p)

    def total(self) -> int:
        return sum(y - x for x, y in self.iv)

    def __contains__(self, p: int) -> bool:
        return any(x <= p < y for x, y in self.iv)


class Array:
    """A page-aligned allocation: its first page and its length."""

    def __init__(self, page_lo: int, n_elems: int, n_pages: int):
        self.page_lo, self.n_elems, self.n_pages = page_lo, n_elems, n_pages


class PlainDSM:
    """The ``Session`` surface (``alloc``, ``phase``, ``reduce``,
    ``barrier``) over the plain model.  ``traffic`` and ``clock`` are the
    compared results."""

    def __init__(self, cfg: dict, clock_dtype=np.float64):
        if cfg["protocol"] != "fine":
            raise ValueError("the plain model covers the fine protocol only")
        cost = cfg["cost"]
        self.W = int(cfg["workers"])
        self.pw = int(cfg["page_words"])
        self.pb = self.pw * WORD_BYTES
        self.cache = cfg["cache_pages"]
        self.prefetch = int(cfg["prefetch"])
        self.fb = int(cfg["fetch_batch"])
        self.lat = float(cost["net_latency_s"])
        self.bw = float(cost["net_bw_Bps"])
        self.fpw = float(cost["flops_per_worker"])
        self.instr = float(cost["instr_s_per_word"])
        self.bar_s = float(cost["barrier_s_per_level"])
        sharing = min(self.W, int(cost["node_size"]))
        node_bw = float(cost["node_mem_bw_Bps"])
        sock = int(cost["socket_size"])
        if sock and sharing <= sock:
            node_bw /= max(1, int(cost["node_size"]) // sock)
        self.mem_bw = node_bw / max(1, sharing)
        self.levels = max(1, math.ceil(math.log2(max(self.W, 2))))
        self.arrays: List[Array] = []
        self.n_pages = 0
        self.valid = [[] for _ in range(self.W)]    # [w][array] -> Pages
        self.dirty = [[] for _ in range(self.W)]
        self.slots = [Pages() for _ in range(self.W)]   # cache slots
        self.lru: List[List[List[int]]] = [[] for _ in range(self.W)]
        self.used = [0] * self.W
        self.clock = np.zeros(self.W, clock_dtype)
        self.traffic: Dict[str, int] = {f: 0 for f in FIELDS}
        self.reductions: Dict[str, int] = {}
        self.alloc = self._alloc

    # -- the Session surface ---------------------------------------------

    def _alloc(self, n_elems: int) -> Array:
        n_pages = -(-n_elems // self.pw)
        a = Array(self.n_pages, n_elems, n_pages)
        self.n_pages += n_pages
        self.arrays.append(a)
        for w in range(self.W):
            self.valid[w].append(Pages())
            self.dirty[w].append(Pages())
        return a

    def phase(self, reads=(), writes=(), flops=0.0, mem_bytes=0.0):
        fl = np.broadcast_to(np.asarray(flops, np.float64), (self.W,))
        mb = np.broadcast_to(np.asarray(mem_bytes, np.float64), (self.W,))
        for w in range(self.W):
            for arr, lo, hi in reads:
                self._read(w, arr, int(_at(lo, w)), int(_at(hi, w)))
            for arr, lo, hi in writes:
                self._write(w, arr, int(_at(lo, w)), int(_at(hi, w)))
            if fl[w] or mb[w]:
                self.clock[w] += max(fl[w] / self.fpw, mb[w] / self.mem_bw)

    def span(self, *a, **kw):
        raise NotImplementedError("the plain model has no lock spans")

    def reduce(self, name: str, value=1.0):
        self.reductions[name] = self.reductions.get(name, 0) + self.W

    def barrier(self):
        for w in range(self.W):
            for i, arr in enumerate(self.arrays):
                d = self.dirty[w][i]
                n = d.total()
                if not n:
                    continue
                self.traffic["writeback_bytes"] += n * self.pb
                self.clock[w] += (self.lat * -(-n // self.fb)
                                  + n * self.pb / self.bw)
                for a, b in d.iv:
                    self._invalidate_others(w, i, a, b)
                self.dirty[w][i] = Pages()
        for _ in self.reductions:
            self.traffic["reduction_msgs"] += self.W - 1
        self.reductions.clear()
        t = (float(np.max(self.clock)) + self.lat * self.levels
             + self.bar_s * self.levels)
        self.clock[:] = t

    # -- reads, writes, the cache ----------------------------------------

    def _pages(self, arr: Array, lo: int, hi: int) -> Tuple[int, int]:
        a = arr.page_lo + lo // self.pw
        b = arr.page_lo + max(hi - 1, lo) // self.pw + 1
        return a, b

    def _read(self, w: int, arr: Array, lo: int, hi: int):
        a, b = self._pages(arr, lo, hi)
        b = max(b, min(b + self.prefetch, arr.page_lo + arr.n_pages))
        self._charge_fetch(w, self._touch(w, arr, a, b, dirty=False))

    def _write(self, w: int, arr: Array, lo: int, hi: int):
        a, b = self._pages(arr, lo, hi)
        self.clock[w] += (hi - lo) * self.instr
        first = (hi - lo < self.pw) if b - a == 1 else bool(lo % self.pw)
        last = b - a > 1 and bool(hi % self.pw)
        # in page order; a partial page is fetched first (write-allocate),
        # a full page is written whole and turns valid without a fetch
        if first:
            self._charge_fetch(w, self._touch(w, arr, a, a + 1, dirty=True))
        self._touch(w, arr, a + first, b - last, dirty=True, fetch=False)
        if last:
            self._charge_fetch(w, self._touch(w, arr, b - 1, b, dirty=True))

    def _charge_fetch(self, w: int, n_miss: int):
        if not n_miss:
            return
        self.traffic["page_fetches"] += n_miss
        self.traffic["fetch_bytes"] += n_miss * self.pb
        self.clock[w] += (self.lat * (2 * -(-n_miss // self.fb))
                          + n_miss * self.pb / self.bw)

    def _touch(self, w: int, arr: Array, a: int, b: int, *, dirty: bool,
               fetch: bool = True) -> int:
        """Touch pages [a, b) of ``arr`` in order; return the misses."""
        i = self.arrays.index(arr)
        valid = self.valid[w][i]
        if self.cache is None:
            miss = (b - a) - valid.count(a, b)
            valid.add(a, b)
            if dirty:
                self.dirty[w][i].add(a, b)
            return miss if fetch else 0
        slots, miss, p = self.slots[w], 0, a
        while p < b:
            if p in slots:
                # pages that hold a slot: a touch moves them to the
                # recent end and evicts nothing
                q = slots.run_end(p, b)
                miss += (q - p) - valid.count(p, q)
                self._lru_take(w, p, q)
            else:
                q = slots.first_in(p, b)
                q = b if q is None else q
                miss += q - p
                slots.add(p, q)
                self.used[w] += q - p
            self.lru[w].append([p, q])
            valid.add(p, q)
            if dirty:
                self.dirty[w][i].add(p, q)
            if self.used[w] > self.cache:
                # each new slot past the limit evicted the oldest page
                # before the touch went on: all of them lie ahead of q
                self._evict(w, self.used[w] - self.cache)
            p = q
        return miss if fetch else 0

    def _lru_take(self, w: int, a: int, b: int):
        runs = []
        for x, y in self.lru[w]:
            if y <= a or x >= b:
                runs.append([x, y])
                continue
            if x < a:
                runs.append([x, a])
            if y > b:
                runs.append([b, y])
        self.lru[w] = runs

    def _evict(self, w: int, k: int):
        wb = 0
        while k:
            run = self.lru[w][0]
            n = min(k, run[1] - run[0])
            a, b = run[0], run[0] + n
            if b == run[1]:
                self.lru[w].pop(0)
            else:
                run[0] = b
            k -= n
            self.used[w] -= n
            self.slots[w].remove(a, b)
            i = self._array_of(a)
            self.valid[w][i].remove(a, b)
            for x, y in self.dirty[w][i].remove(a, b):
                wb += y - x
                self._invalidate_others(w, i, x, y)
        if wb:
            self.traffic["writeback_bytes"] += wb * self.pb
            self.clock[w] += self.lat * wb + wb * self.pb / self.bw

    def _invalidate_others(self, w: int, i: int, a: int, b: int):
        for v in range(self.W):
            iv = self.valid[v][i].iv
            if v == w or not iv or iv[0][0] >= b or iv[-1][1] <= a:
                continue
            n = sum(y - x for x, y in self.valid[v][i].remove(a, b))
            self.traffic["invalidations"] += n
            self.traffic["control_msgs"] += n

    def _array_of(self, page: int) -> int:
        for i, arr in enumerate(self.arrays):
            if arr.page_lo <= page < arr.page_lo + arr.n_pages:
                return i
        raise KeyError(page)


def _at(v, w: int):
    return v[w] if np.ndim(v) else v
