"""CPU rehearsal of every cell through the harness's own code path.

    python -m pytest -q chipbench/tests

Each cell runs at a tiny worker count and problem size with the look for
a chip skipped; the runtime's jitted kernels run on XLA's CPU backend.
A real run (``chipbench/run.py``) must refuse the CPU.
"""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]
TINY = {
    "jacobi.weak": {"workers": 16, "grid_n": 1024},
    "stream.spill": {"workers": 16, "array_words": 2 * 16 * 1024 * 64,
                     "cache_pages": 3 * 16 * 4 + 8},
}


def run_tiny(name, trace, seed=2 ** 31 + 7, root=ROOT, overrides=None):
    return harness.run_cell(name, seed, 0.3, trace,
                            t_start=time.perf_counter(), require_chip=False,
                            config_overrides=overrides or TINY[name],
                            root=root)


def test_every_cell_has_a_rehearsal_size():
    """DSM cells are rehearsed here, the training cell in test_train.py."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = {w["name"] for w in bench["workloads"]}
    assert set(TINY) <= names <= set(TINY) | {"sync.lazy_object"}


@pytest.mark.parametrize("name", sorted(TINY))
def test_cell_runs_and_is_correct(name):
    res = run_tiny(name, False)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {m["name"] for m in
                                   harness.load_cell(name).end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["traffic_gap"]["value"] == 0
    assert res["checks"]["clock_gap"]["value"] == 0.0


@pytest.mark.parametrize("name", sorted(TINY))
def test_traced_cell_reports_host_metrics(name):
    res = run_tiny(name, True)
    assert res["correct"]
    # no device plane on the CPU: the trace readers return nothing
    assert {"phase_ms", "barrier_ms", "dispatches"} <= set(res["metrics"])
    assert "kernel_ms" not in res["metrics"]
    assert "flush_roofline" not in res["metrics"]
    assert res["metrics"]["dispatches"]["value"] >= 1
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "jacobi.weak",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_real_run_refuses_the_cpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "JAX found no TPU" in p.stderr
    assert not p.stdout.strip()


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench")
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_refuses_another_tier(monkeypatch):
    import repro.kernels.protocol_sweep as ps
    monkeypatch.setattr(ps, "resolve_backend", lambda b: "numpy")
    with pytest.raises(harness.Refused, match="resolved tier"):
        run_tiny("jacobi.weak", False)


def test_refuses_a_window_without_a_device_kernel():
    with pytest.raises(harness.Refused, match="no protocol kernel"):
        run_tiny("jacobi.weak", False,
                 overrides={**TINY["jacobi.weak"], "backend": "numpy"})


def test_new_cell_from_added_files_alone(tmp_path):
    """A cell, its traffic and its configuration added as data only."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench")
    (tmp_path / "src").symlink_to(ROOT / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "chipbench/configs/stream-w256.json")
                     .read_text())
    cfg.update(array_words=16 * 1024 * 256, cache_pages=None)
    (tmp_path / "chipbench/configs/stream-w16.json").write_text(
        json.dumps(cfg))
    (tmp_path / "chipbench/traffic/fits.json").write_text(json.dumps(
        {"program": "stream_triad", "placement": "identity"}))
    bench["configs"].append({"name": "stream-w16", "source": "test",
                             "file": "chipbench/configs/stream-w16.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "stream.fits", "config": "stream-w16",
                               "traffic": "fits", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "stream.spill" in m.get("workloads", []):
            m["workloads"].append("stream.fits")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    res = run_tiny("stream.fits", False, root=tmp_path,
                   overrides={"workers": 16})
    assert res["correct"]
    assert set(res["metrics"]) == {"iter_ms", "setup_s"}


COPY_PROGRAM = '''"""STREAM COPY, A = B over block-partitioned arrays."""
import numpy as np

from chipbench.flops import range_pages


class Program:
    def __init__(self, drv, config, traffic, placement):
        W, n = int(config["workers"]), int(config["array_words"])
        self.drv = drv
        self.A, self.B = drv.alloc(n), drv.alloc(n)
        lo = np.arange(W, dtype=np.int64) * (n // W)
        hi = lo + n // W
        hi[-1] = n
        self.lo, self.hi = lo[placement], hi[placement]

    def written_cells(self, page_words):
        return range_pages(self.lo, self.hi, page_words)

    def iteration(self):
        self.drv.phase(reads=((self.B, self.lo, self.hi),),
                       writes=((self.A, self.lo, self.hi),),
                       mem_bytes=2.0 * 4 * (self.hi - self.lo))
        self.drv.barrier()
'''


def test_new_program_from_added_files_alone(tmp_path):
    """A user program the benchmark did not have, added as a file of its
    own with its traffic and its cell: no existing file changes."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench")
    (tmp_path / "src").symlink_to(ROOT / "src")
    (tmp_path / "chipbench/programs/stream_copy.py").write_text(COPY_PROGRAM)
    (tmp_path / "chipbench/traffic/copy.json").write_text(json.dumps(
        {"program": "stream_copy", "placement": "seeded"}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "stream.copy", "config": "stream-w256",
                               "traffic": "copy", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "stream.spill" in m.get("workloads", []):
            m["workloads"].append("stream.copy")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    # the cache first spills in the second iteration: a new shape there
    res = run_tiny("stream.copy", False, root=tmp_path,
                   overrides={**TINY["stream.spill"], "warmup_min": 2})
    assert res["correct"], res["checks"]
    assert res["iterations"] >= 2
