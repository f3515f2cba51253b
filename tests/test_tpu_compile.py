"""Compile the device kernels of the main path for a described TPU v5e.

No chip is attached: the TPU compiler that ships with jax compiles for a
described ``v5e:2x2`` topology and refuses what the chip's compiler would
refuse (unaligned blocks, unsupported lowerings, VMEM overruns).  Nothing
runs, so these tests say nothing about results or times; the interpret-mode
oracles in ``test_directory.py`` / ``test_kernels.py`` cover results.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
so under several test workers only the worker given this file loads it.
"""
from __future__ import annotations

import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp                                     # noqa: E402

from repro.kernels import page_diff, protocol_sweep as ps  # noqa: E402

pytestmark = pytest.mark.skipif(not ps.HAVE_PALLAS, reason="no pallas")

W = 256                 # the paper's largest worker count
NW_64K = 2048           # a 64k-page window, 32 pages per packed word


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    return compiled, compiled.as_text()


@pytest.mark.parametrize("R,nw", [(3, 1024), (1, 1024), (1, 513)])
def test_phase_step_compiles(one_chip, R, nw):
    """The fused flush at the benchmark cells' shapes (one region of 1024
    or 513 words, a 32k- or 16k-page window) and a three-region stack.
    Its scratch stays below one int32 entry per page of one region, the
    (W, nw, 32) grid a per-page coverage stab would build."""
    i32 = lambda: _spec(one_chip, (R, W), jnp.int32)  # noqa: E731
    compiled, _ = _compile(
        ps._phase_step_jit, _spec(one_chip, (R, W, nw), jnp.uint32), i32(),
        _spec(one_chip, (R, W), jnp.bool_), i32(), i32())
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < W * 1024 * 32 * 4     # 33,554,432


def test_take_and_cut_compiles(one_chip):
    _compile(ps._take_and_cut_jit,
             _spec(one_chip, (W, NW_64K), jnp.uint32),
             _spec(one_chip, (W,), jnp.int32))


@pytest.mark.parametrize("workers", [16, W])
@pytest.mark.parametrize("kernel", ["popcount", "take_first_k",
                                    "kth_set_index"])
def test_rank_kernel_compiles(one_chip, kernel, workers):
    call = getattr(ps, f"_{kernel}_call")(workers, NW_64K, False)
    args = [_spec(one_chip, (workers, NW_64K), jnp.uint32)]
    if kernel != "popcount":
        args.append(_spec(one_chip, (workers, 1), jnp.int32))
    _, hlo = _compile(call, *args)
    assert "tpu_custom_call" in hlo


def test_coverage_kernel_compiles(one_chip):
    npad = 2 * W                                   # 2W sorted window bounds
    _, hlo = _compile(ps._coverage_call(npad, False),
                      _spec(one_chip, (1, npad), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_diff_encode_compiles(one_chip):
    n, w = 64, 1024                                # 64 pages of 4 KiB
    page = _spec(one_chip, (n, w), jnp.float32)
    fn = jax.jit(lambda c, t: page_diff.diff_encode(c, t, interpret=False))
    _, hlo = _compile(fn, page, page)
    assert "tpu_custom_call" in hlo


def test_pallas_interpret_follows_the_backend(monkeypatch):
    from repro.kernels import platform
    for backend, want in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert platform.pallas_interpret() is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        platform.pallas_interpret()
