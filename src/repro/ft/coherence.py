"""Fault tolerance for the RegC coherence engine: barrier-consistent
checkpoints, chaos-driven crash recovery, and the exactness bar.

The paper's rules 2-3 make region and barrier boundaries the ONLY points
where coherence state is globally reconciled — which also makes them
natural *consistent cuts*: at a barrier every span is closed, every
reduction resolved, every dirty page flushed, every lock log replayed.
``RegCScaleRuntime.snapshot()`` serializes the complete protocol state
at such a cut; this module glues it to the sharded-npz + atomic-manifest
checkpoint store (numpy-only — no jax on the recovery path) and runs the
crash-recovery analogue of the trace-fuzz lockstep:

    run with failures -> crash -> restore last barrier checkpoint ->
    replay the suffix -> traffic field-for-field and clocks bit-equal
    with the run that never failed.

The guarantee is *exact replay*, not approximate resumption: message
loss (``dsm.costmodel.ChaosNet``) is deterministic in each worker's own
event counters — part of the checkpointed state — so the replayed suffix
re-experiences the same drops and retry charges the uninjected run did.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence

import numpy as np

from repro.checkpoint.store import load_arrays, save_arrays
from repro.core.regc_scale import RegCScaleRuntime
from repro.ft.runtime import WorkerFailure


def save_runtime(rt: RegCScaleRuntime, root, step: int):
    """Checkpoint a runtime at a barrier-consistent cut into the store's
    npz-shard + atomic-manifest layout (``step`` is the caller's resume
    cursor, e.g. the index of the next program event)."""
    arrays, meta = rt.snapshot()
    save_arrays(root, step, arrays, extra=meta)


def load_runtime(root, step: int, *, injector=None) -> RegCScaleRuntime:
    """Rebuild a bit-identical runtime from a :func:`save_runtime`
    checkpoint.  ``injector`` (typically the SAME, partially-fired
    FailureInjector) rearms crash injection on the replayed suffix."""
    arrays, meta = load_arrays(root, step)
    return RegCScaleRuntime.from_snapshot(arrays, meta, injector=injector)


def harness_ticks(ev, driver: str) -> bool:
    """Whether the harness must call ``rt.chaos_tick()`` for this event.

    The batched driver's bulk entry points (``phase_all``/``span_all``)
    and ``barrier`` (both drivers) tick internally; per-worker loop
    events and the scalar span walks have no single runtime entry, so
    the harness ticks once per event — giving both drivers the same
    per-event injection schedule."""
    kind = ev[0]
    if kind == "barrier":
        return False
    if driver == "batched":
        return kind not in ("phase", "span_phase")
    return True


@dataclasses.dataclass
class RecoveryReport:
    """What a :class:`ChaosHarness` run went through."""

    n_events: int = 0
    n_crashes: int = 0
    n_checkpoints: int = 0
    n_replayed_events: int = 0
    crashed_workers: List[int] = dataclasses.field(default_factory=list)


class ChaosHarness:
    """Run a trace-fuzz phase program under failure injection with
    checkpoint-at-barrier recovery.

    ``make_rt`` builds a fresh runtime (chaos / straggler already
    attached); allocation sizes are replayed through
    ``gas_for_region`` after a restore, so callers keep indexing the
    same region handles across crashes.  On ``WorkerFailure`` the
    harness restores the LAST barrier checkpoint — reattaching the same
    (now partially fired) injector so one configured crash fires once —
    and resumes from the checkpointed event cursor.  ``apply_event`` is
    the trace-fuzz executor (injected to avoid a src->tests import)."""

    def __init__(self, make_rt: Callable[[], RegCScaleRuntime],
                 gas_words: Sequence[int], driver: str, root,
                 apply_event: Callable, *, injector=None):
        self.make_rt = make_rt
        self.gas_words = list(gas_words)
        self.driver = driver
        self.root = root
        self.apply_event = apply_event
        self.injector = injector

    def _alloc(self, rt):
        return [rt.alloc(n) for n in self.gas_words]

    def _regas(self, rt):
        return [rt.gas_for_region(r, n)
                for r, n in enumerate(self.gas_words)]

    def run(self, prog) -> "tuple[RegCScaleRuntime, RecoveryReport]":
        rep = RecoveryReport(n_events=len(prog))
        rt = self.make_rt()
        rt.injector = self.injector
        gas = self._alloc(rt)
        save_runtime(rt, self.root, 0)          # the t=0 cut
        rep.n_checkpoints += 1
        last_ckpt = 0
        i = 0
        while i < len(prog):
            ev = prog[i]
            try:
                if harness_ticks(ev, self.driver):
                    rt.chaos_tick()
                self.apply_event(rt, ev, gas, self.driver)
            except WorkerFailure as e:
                rep.n_crashes += 1
                rep.crashed_workers.append(e.worker)
                rep.n_replayed_events += i - last_ckpt
                rt = load_runtime(self.root, last_ckpt,
                                  injector=self.injector)
                gas = self._regas(rt)
                i = last_ckpt
                continue
            i += 1
            if ev[0] == "barrier":
                # post-barrier state is a consistent cut; cursor = next
                # event index, so recovery replays exactly the suffix
                save_runtime(rt, self.root, i)
                rep.n_checkpoints += 1
                last_ckpt = i
        return rt, rep


class ClusterChaosHarness:
    """:class:`ChaosHarness`'s process-level sibling: run a trace program
    on the sharded multi-process runtime (``repro.cluster``) under
    *process* faults — SIGKILL and one-directional link partitions from
    ``FailureInjector.cluster_at`` — with the same contract: recover
    through the last barrier checkpoint and finish traffic
    field-for-field and clock bit-equal to the unfailed single-process
    run.  The control plane performs detection/quarantine/re-shard
    itself; this wrapper only gives tests the familiar
    construct-run-report shape (and keeps ``repro.cluster`` a lazy
    import so the ft module stays importable everywhere)."""

    def __init__(self, cfg: dict, gas_words: Sequence[int], driver: str,
                 root, apply_ref: "tuple[str, str]", *, n_shards: int,
                 injector=None, recovery: str = "respawn",
                 rpc_timeout_s: float = 0.25, rpc_attempts: int = 4):
        self.cfg = dict(cfg)
        self.gas_words = list(gas_words)
        self.driver = driver
        self.root = root
        self.apply_ref = tuple(apply_ref)
        self.n_shards = int(n_shards)
        self.injector = injector
        self.recovery = recovery
        self.rpc_timeout_s = float(rpc_timeout_s)
        self.rpc_attempts = int(rpc_attempts)

    def run(self, prog):
        """Returns ``(ClusterResult, ClusterReport, digests)`` where
        ``digests`` maps event index -> the digest every shard agreed
        on (the lockstep trace a single-process run must reproduce)."""
        from repro.cluster.control import ClusterRuntime
        with ClusterRuntime(self.cfg, self.gas_words,
                            n_shards=self.n_shards, driver=self.driver,
                            apply_ref=self.apply_ref, root=self.root,
                            recovery=self.recovery,
                            injector=self.injector,
                            rpc_timeout_s=self.rpc_timeout_s,
                            rpc_attempts=self.rpc_attempts) as cluster:
            result = cluster.run(prog)
            return result, result.report, dict(cluster.digests)


def run_uninjected(make_rt: Callable[[], RegCScaleRuntime],
                   gas_words: Sequence[int], driver: str, prog,
                   apply_event: Callable) -> RegCScaleRuntime:
    """The no-failures baseline a recovered run must match bit-for-bit.
    Ticks the same per-event schedule as :class:`ChaosHarness` (ticks
    carry no cost — this just keeps ``_phase_idx`` comparable)."""
    rt = make_rt()
    gas = [rt.alloc(n) for n in gas_words]
    for ev in prog:
        if harness_ticks(ev, driver):
            rt.chaos_tick()
        apply_event(rt, ev, gas, driver)
    return rt


def assert_bit_equal(a: RegCScaleRuntime, b: RegCScaleRuntime, ctx=""):
    """The recovery exactness bar: traffic field-for-field, clocks
    bit-equal, stats counters identical."""
    from repro.core.regc import Traffic
    for f in dataclasses.fields(Traffic):
        av, bv = getattr(a.traffic, f.name), getattr(b.traffic, f.name)
        assert av == bv, (ctx, f.name, av, bv)
    np.testing.assert_array_equal(a.clock, b.clock, err_msg=str(ctx))
    # jit_*/pallas_* counters record dispatch topology (how many device
    # programs ran), which legitimately differs between a sharded run and
    # its single-process baseline or between backends, and
    # jit_cache_misses mirrors the process-wide compile cache — none is
    # protocol state, so they sit outside the exactness bar
    # (traffic/clocks/protocol counters).
    dispatch = ("jit_", "pallas_")
    sa = {k: v for k, v in a.stats.items() if not k.startswith(dispatch)}
    sb = {k: v for k, v in b.stats.items() if not k.startswith(dispatch)}
    assert sa == sb, (ctx, sa, sb)
