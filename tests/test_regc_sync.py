"""Tests for the RegC training-layer sync policies (repro.regc_sync).

Single-device parts run inline; multi-device semantics (psum vs int8 ring,
lazy vs eager, GSPMD vs shard_map equivalence) run in a subprocess with
``--xla_force_host_platform_device_count=8`` because the main test process
must keep seeing exactly one device (DESIGN.md §6).
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip(
    "jax", reason="jax-dependent suite; the no-jax CI leg covers the numpy fallbacks")
import jax.numpy as jnp
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:           # tier-1 env may lack hypothesis
    from _hypothesis_stub import given, settings, st

from repro.regc_sync.policies import (
    RegCSyncPolicy, _dequant, _flatten_to_buckets, _quant, _unflatten_buckets,
)


# ---------------------------------------------------------------------------
# bucketing (page-granularity analogue): lossless round trip
# ---------------------------------------------------------------------------


@st.composite
def tree_shapes(draw):
    n = draw(st.integers(1, 6))
    return [tuple(draw(st.lists(st.integers(1, 7), min_size=1, max_size=3)))
            for _ in range(n)]


def _check_bucket_roundtrip(shapes, bucket_bytes):
    rng = np.random.RandomState(0)
    tree = {f"p{i}": jnp.asarray(rng.randn(*s).astype(np.float32))
            for i, s in enumerate(shapes)}
    buckets, shp, treedef = _flatten_to_buckets(tree, bucket_bytes)
    out = _unflatten_buckets(buckets, shp, treedef)
    assert jax.tree.structure(out) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(a, b)


@given(tree_shapes(), st.integers(8, 512))
@settings(max_examples=25, deadline=None)
def test_bucket_roundtrip_property(shapes, bucket_bytes):
    _check_bucket_roundtrip(shapes, bucket_bytes)


def test_bucket_roundtrip_seeded():
    """Deterministic twin: seeded shape lists across the bucket-size
    range, plus the degenerate single-scalar tree."""
    _check_bucket_roundtrip([(1,)], 8)
    rng = np.random.RandomState(11)
    for bucket_bytes in (8, 64, 200, 512):
        shapes = [tuple(int(rng.randint(1, 8))
                        for _ in range(int(rng.randint(1, 4))))
                  for _ in range(int(rng.randint(1, 7)))]
        _check_bucket_roundtrip(shapes, bucket_bytes)


def test_bucket_sizes_respect_threshold():
    tree = {f"p{i}": jnp.ones((1024,), jnp.float32) for i in range(16)}
    buckets, _, _ = _flatten_to_buckets(tree, 8192)   # 2 leaves per bucket
    assert len(buckets) == 8
    assert all(b.size * 4 >= 8192 for b in buckets[:-1])


# ---------------------------------------------------------------------------
# int8 quantization (compressed-diff analogue)
# ---------------------------------------------------------------------------


def _check_quant_error_bound(n, scale_mag):
    rng = np.random.RandomState(n)
    x = jnp.asarray(rng.randn(n).astype(np.float32) * scale_mag)
    q, s = _quant(x)
    err = np.abs(np.asarray(_dequant(q, s) - x))
    # error bounded by half a quantization step
    assert err.max() <= float(s) * 0.5 + 1e-6


@given(st.integers(1, 2000), st.floats(1e-3, 1e3))
@settings(max_examples=30, deadline=None)
def test_quant_error_bound(n, scale_mag):
    _check_quant_error_bound(n, scale_mag)


def test_quant_error_bound_seeded():
    """Deterministic twin: size/magnitude edges plus seeded draws."""
    for n, mag in [(1, 1e-3), (2000, 1e3), (7, 1.0)]:
        _check_quant_error_bound(n, mag)
    rng = np.random.RandomState(3)
    for _ in range(10):
        _check_quant_error_bound(int(rng.randint(1, 2001)),
                                 float(10.0 ** rng.uniform(-3, 3)))


def test_quant_preserves_zero():
    q, s = _quant(jnp.zeros(16))
    np.testing.assert_array_equal(np.asarray(_dequant(q, s)), 0.0)


def test_policy_validation():
    with pytest.raises(AssertionError):
        RegCSyncPolicy(ordinary_sync="nope")
    with pytest.raises(AssertionError):
        RegCSyncPolicy(granularity="page")


_PERMUTE_HLO = """ENTRY %main (p: s8[128]) -> s8[128] {
  %p = s8[128]{0} parameter(0)
  ROOT %cp = s8[128]{0} collective-permute(%p), source_target_pairs={{0,1}}
}"""
# the TPU's async form of the same permute: the start's output tuple holds
# the operand, the result and two context scalars
_PERMUTE_ASYNC_HLO = """ENTRY %main (p: s8[128]) -> s8[128] {
  %p = s8[128]{0} parameter(0)
  %cps = (s8[128]{0}, s8[128]{0}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%p), source_target_pairs={{0,1}}
  ROOT %cpd = s8[128]{0} collective-permute-done(%cps)
}"""


@pytest.mark.parametrize("text", [_PERMUTE_HLO, _PERMUTE_ASYNC_HLO],
                         ids=["sync", "async"])
def test_hlo_collective_bytes(text):
    from repro.launch import hlo_analysis
    st = hlo_analysis.analyze(text)
    assert st.collective_count["collective-permute"] == 1
    assert st.total_collective_bytes == 128


# ---------------------------------------------------------------------------
# multi-device semantics (subprocess, 8 fake host devices)
# ---------------------------------------------------------------------------


def _run_multidev(script: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


MULTIDEV_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.regc_sync.policies import (RegCSyncPolicy, barrier_sync_grads,
                                      ring_allreduce_int8, span_reduce)

mesh = jax.make_mesh((8,), ("data",))
x = jnp.arange(8 * 64, dtype=jnp.float32).reshape(8, 64) / 100.0 - 2.0

# --- int8 ring all-reduce approximates fp32 psum --------------------------
def ring(v):
    return ring_allreduce_int8(v, "data", 8)
from repro.compat import shard_map
ring_out = shard_map(ring, mesh=mesh, in_specs=P("data"),
                         out_specs=P("data"))(x.reshape(-1))
psum_out = np.asarray(x).sum(0)
ring_first = np.asarray(ring_out.reshape(8, 64))[0]
rel = np.abs(ring_first - psum_out) / (np.abs(psum_out) + 1e-3)
assert rel.max() < 0.05, rel.max()
# every shard holds the same reduced vector (all-gather phase correctness)
rr = np.asarray(ring_out.reshape(8, 64))
assert np.allclose(rr, rr[0:1], atol=1e-6)

# --- object vs bucket granularity agree exactly (both are psum) ------------
grads = {"a": x, "b": (x * 3 + 1).reshape(8, 8, 8)}
outs = {}
for gran in ("object", "bucket"):
    pol = RegCSyncPolicy(granularity=gran, bucket_bytes=128)
    f = lambda g: barrier_sync_grads(g, ("data",), pol, axis_sizes={"data": 8})
    o = shard_map(f, mesh=mesh,
                      in_specs=({"a": P("data"), "b": P("data")},),
                      out_specs={"a": P("data"), "b": P("data")})(
        {"a": grads["a"].reshape(8, 1, 64), "b": grads["b"]})
    outs[gran] = o
for k in outs["object"]:
    np.testing.assert_allclose(np.asarray(outs["object"][k]),
                               np.asarray(outs["bucket"][k]), rtol=1e-6)

# --- span_reduce == the reduction extension --------------------------------
val = jnp.arange(8.0)
got = shard_map(lambda v: span_reduce(v, ("data",), "sum"),
                    mesh=mesh, in_specs=P("data"), out_specs=P("data"))(val)
np.testing.assert_allclose(np.asarray(got), 28.0)
print("MULTIDEV_OK")
"""


@pytest.mark.slow
def test_multidevice_sync_semantics():
    out = _run_multidev(MULTIDEV_SCRIPT)
    assert "MULTIDEV_OK" in out


TRAIN_EQUIV_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_reduced
from repro.models import model as M
from repro.optim.adamw import init_opt_state
from repro.regc_sync.policies import RegCSyncPolicy
from repro.train.train_step import (TrainHParams, make_train_step,
                                    make_train_step_regc)

cfg = get_reduced("internlm2-1.8b")
params = M.init_model_params(cfg, jax.random.PRNGKey(0), jnp.float32)
opt = init_opt_state(params)
ks = jax.random.split(jax.random.PRNGKey(1), 2)
B, S = 16, 32   # 8-way DP -> local batch 2, divisible by n_micro=2
batch = {"tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size),
         "targets": jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size)}
step0 = jnp.zeros((), jnp.int32)

mesh = jax.make_mesh((8,), ("data",))

# reference: single-device GSPMD step (global batch)
hp = TrainHParams(remat=None, ce_chunk=32)
ref_p, ref_o, ref_m = jax.jit(make_train_step(cfg, hp))(params, opt, batch, step0)

results = {}
for tag, policy, n_micro in (
    ("lazy_object", RegCSyncPolicy("lazy", "object"), 1),
    ("lazy_bucket", RegCSyncPolicy("lazy", "bucket", 1 << 16), 1),
    ("eager_object", RegCSyncPolicy("eager", "object"), 2),
    ("lazy_micro", RegCSyncPolicy("lazy", "object"), 2),
):
    hp2 = TrainHParams(remat=None, ce_chunk=32, n_micro=n_micro,
                       sync=policy)
    step = make_train_step_regc(cfg, hp2, mesh, dp_axes=("data",))
    p2, o2, m2 = step(params, opt, batch, step0)
    results[tag] = (p2, m2)
    assert np.isfinite(float(m2["loss"])), (tag, m2)
    np.testing.assert_allclose(float(m2["loss"]), float(ref_m["loss"]),
                               rtol=2e-4, err_msg=tag)

# RegC lazy and RC eager produce the same update (DRF program: both
# consistent at the step barrier; only traffic schedules differ)
for a, b in zip(jax.tree.leaves(results["eager_object"][0]),
                jax.tree.leaves(results["lazy_micro"][0])):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=5e-3, atol=5e-5)
# shard_map lazy == GSPMD reference update
for a, b in zip(jax.tree.leaves(results["lazy_object"][0]),
                jax.tree.leaves(ref_p)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=5e-3, atol=5e-5)
print("TRAIN_EQUIV_OK")
"""


@pytest.mark.slow
def test_regc_train_equivalence_8dev():
    """GSPMD vs explicit-RegC shard_map vs eager-RC: same update, different
    collective schedule (the paper's Table I executable at trainer scale)."""
    out = _run_multidev(TRAIN_EQUIV_SCRIPT)
    assert "TRAIN_EQUIV_OK" in out
