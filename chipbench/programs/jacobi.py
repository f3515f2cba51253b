"""OmpSCR Jacobi as ``repro.dsm.apps.jacobi`` runs it (copied at c6212da),
one iteration per call: a 5-point stencil on an n x n grid with a global
residual, three barriers per iteration.  The configuration gives
``workers`` and ``grid_n``; the traffic file gives ``mode`` (``reduction``
or ``lock``).  ``placement[w]`` is the row block worker w owns.
"""
import numpy as np

from chipbench.flops import range_pages

RES_LOCK = 0      # apps.RES_LOCK


def blocks(n: int, W: int):
    """Copied from ``repro.dsm.apps._blocks`` (c6212da): block partition of
    [0, n), the last worker taking the remainder."""
    chunk = n // W
    lo = np.arange(W, dtype=np.int64) * chunk
    hi = lo + chunk
    hi[-1] = n
    return lo, hi


class Program:
    def __init__(self, drv, config: dict, traffic: dict, placement):
        W, n, mode = int(config["workers"]), int(config["grid_n"]), \
            traffic["mode"]
        if mode not in ("lock", "reduction"):
            raise ValueError(f"jacobi mode {mode!r}: lock or reduction")
        self.drv, self.mode = drv, mode
        self.u = drv.alloc(n * n)
        self.uold = drv.alloc(n * n)
        self.f = drv.alloc(n * n)
        self.res = drv.alloc(1)      # global residual accumulator
        r0, r1 = blocks(n, W)
        r0, r1 = r0[placement], r1[placement]
        self.lo_b, self.hi_b = r0 * n, r1 * n
        self.lo_h = np.maximum(r0 - 1, 0) * n     # halo rows
        self.hi_h = np.minimum(r1 + 1, n) * n
        self.pts = (r1 - r0) * n
        self.zero = np.zeros(W, np.int64)
        self.one = np.ones(W, np.int64)

    def written_cells(self, page_words: int) -> int:
        """(worker, page) pairs one iteration writes: uold and u over the
        own block, and the residual word in lock mode."""
        w = 2 * range_pages(self.lo_b, self.hi_b, page_words)
        return w + (len(self.zero) if self.mode == "lock" else 0)

    def iteration(self):
        d, u, uold, f, res = self.drv, self.u, self.uold, self.f, self.res
        lo_b, hi_b = self.lo_b, self.hi_b
        # phase 1: copy own block u -> uold
        d.phase(reads=((u, lo_b, hi_b),), writes=((uold, lo_b, hi_b),),
                mem_bytes=2.0 * 4 * (hi_b - lo_b))
        d.barrier()
        # phase 2: stencil + residual, then the global accumulate
        d.phase(reads=((uold, self.lo_h, self.hi_h), (f, lo_b, hi_b)),
                writes=((u, lo_b, hi_b),),
                flops=50.0 * self.pts, mem_bytes=4.0 * 4 * self.pts)
        if self.mode == "lock":
            d.span(RES_LOCK, reads=((res, self.zero, self.one),),
                   writes=((res, self.zero, self.one),))
        else:
            d.reduce("residual")
        d.barrier()
        # phase 3: convergence test -- everyone reads the residual
        if self.mode == "lock":
            d.phase(reads=((res, self.zero, self.one),))
        d.barrier()
