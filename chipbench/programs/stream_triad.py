"""McCalpin's STREAM TRIAD as ``repro.dsm.apps.stream_triad`` runs it
(copied at c6212da), one iteration per call: A = B + alpha*C over
block-partitioned arrays, one barrier per iteration.  The configuration
gives ``workers`` and ``array_words``.  ``placement[w]`` is the block
worker w owns.
"""
import numpy as np

from chipbench.flops import range_pages


def blocks(n: int, W: int):
    """Copied from ``repro.dsm.apps._blocks`` (c6212da)."""
    chunk = n // W
    lo = np.arange(W, dtype=np.int64) * chunk
    hi = lo + chunk
    hi[-1] = n
    return lo, hi


class Program:
    def __init__(self, drv, config: dict, traffic: dict, placement):
        W, n = int(config["workers"]), int(config["array_words"])
        self.drv = drv
        self.A, self.B, self.C = drv.alloc(n), drv.alloc(n), drv.alloc(n)
        lo, hi = blocks(n, W)
        self.lo, self.hi = lo[placement], hi[placement]
        self.flops = 2.0 * (self.hi - self.lo)
        self.mem_bytes = 3.0 * 4 * (self.hi - self.lo)

    def written_cells(self, page_words: int) -> int:
        """(worker, page) pairs one iteration writes: A over the block."""
        return range_pages(self.lo, self.hi, page_words)

    def iteration(self):
        d, lo, hi = self.drv, self.lo, self.hi
        d.phase(reads=((self.B, lo, hi), (self.C, lo, hi)),
                writes=((self.A, lo, hi),),
                flops=self.flops, mem_bytes=self.mem_bytes)
        d.barrier()
