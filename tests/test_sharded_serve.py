"""Mesh-sharded serve: exact parity with single-device, deterministic
tie-break, geometric device-cache growth, and the mesh=None fast path.

The multi-device checks run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (this pytest process
must keep seeing exactly 1 device — test_dryrun_smoke enforces that); the
actual assertions live in tests/sharded_parity_check.py. Everything else
here runs in-process on the single device.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# deterministic tie-break (satellite: applies to single-device topk too)
# ---------------------------------------------------------------------------
def test_merge_topk_breaks_ties_by_ascending_index():
    import jax.numpy as jnp
    from repro.kernels.topk_sim import merge_topk

    # candidate pool with duplicate scores in shuffled index order
    vals = jnp.asarray([[1.0, 3.0, 3.0, 2.0, 3.0, 1.0]], jnp.float32)
    idx = jnp.asarray([[50, 40, 7, 12, 19, 3]], jnp.int32)
    v, i = merge_topk(vals, idx, 4)
    assert np.allclose(np.asarray(v)[0], [3.0, 3.0, 3.0, 2.0])
    # ties at 3.0 resolve to ascending global row ids: 7 < 19 < 40
    assert np.asarray(i)[0].tolist() == [7, 19, 40, 12]


def test_merge_topk_masks_padding():
    import jax.numpy as jnp
    from repro.kernels.topk_sim import NEG_INF, merge_topk

    vals = jnp.asarray([[2.0, NEG_INF, 1.0, NEG_INF]], jnp.float32)
    idx = jnp.asarray([[4, -1, 9, -1]], jnp.int32)
    v, i = merge_topk(vals, idx, 3)
    assert np.asarray(i)[0].tolist() == [4, 9, -1]


@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
def test_single_device_topk_tie_break(impl):
    """Duplicate key rows must surface in ascending-row-id order for every
    kernel impl — the contract the sharded merge relies on for exactness."""
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    row = rng.standard_normal(32).astype(np.float32)
    other = rng.standard_normal((64, 32)).astype(np.float32)
    keys = np.concatenate([other, row[None], other[-8:], row[None]])
    dup_a, dup_b = 64, 73  # identical rows -> identical scores
    q = row[None] / np.linalg.norm(row)
    vals, idx = ops.topk_sim(q, keys, 4, impl=impl)
    idx = np.asarray(idx)[0]
    assert dup_a in idx and dup_b in idx, f"duplicate rows missing: {idx}"
    pos_a, pos_b = list(idx).index(dup_a), list(idx).index(dup_b)
    assert pos_a < pos_b, f"tie not broken by ascending id: {idx}"
    # tail duplicates (rows 65..72 copy rows 56..63): lower id always first
    for g in range(65, 73):
        if g in idx and (g - 9) in idx:
            assert list(idx).index(g - 9) < list(idx).index(g)


# ---------------------------------------------------------------------------
# geometric device-cache growth (satellite: no full re-upload on growth)
# ---------------------------------------------------------------------------
def test_device_cache_grows_without_reupload():
    from repro.config import MemForestConfig
    from repro.core.memforest import MemForestSystem
    from repro.data.synthetic import make_workload

    wl = make_workload(num_entities=4, num_sessions=12, num_queries=6, seed=3)
    mf = MemForestSystem(MemForestConfig())
    third = len(wl.sessions) // 3
    for s in wl.sessions[:third]:
        mf.ingest_session(s)
    mf.query_batch(wl.queries)          # builds the device caches
    up0, gr0 = mf.forest.index_uploads, mf.forest.index_grows
    assert up0 > 0 and gr0 == 0
    for s in wl.sessions[third:]:
        mf.ingest_session(s)            # host capacity grows past cache cap
    res = mf.query_batch(wl.queries)
    assert mf.forest.index_uploads == up0, \
        "capacity growth re-uploaded the whole index"
    assert mf.forest.index_grows >= 1

    fresh = MemForestSystem(MemForestConfig())
    for s in wl.sessions:
        fresh.ingest_session(s)
    for a, b in zip(res, fresh.query_batch(wl.queries)):
        assert a.answer == b.answer and a.evidence == b.evidence


def test_grow_rows_preserves_existing():
    import jax.numpy as jnp
    from repro.kernels import ops

    arr = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
    grown = ops.grow_rows(arr, 4)
    assert grown.shape == (8, 3)
    assert np.array_equal(np.asarray(grown[:4]), np.asarray(arr))
    assert not np.asarray(grown[4:]).any()


# ---------------------------------------------------------------------------
# mesh plumbing on a single device (fast path; no silent clipping)
# ---------------------------------------------------------------------------
def test_make_data_mesh_single_device_is_none():
    from repro.launch.mesh import make_data_mesh

    assert make_data_mesh() is None      # 1 visible device
    assert make_data_mesh(1) is None
    with pytest.raises(ValueError, match="4 devices requested, 1 present"):
        make_data_mesh(4)                # never clipped to what is present


def test_set_mesh_none_is_identity():
    from repro.config import MemForestConfig
    from repro.core.memforest import MemForestSystem
    from repro.data.synthetic import make_workload

    wl = make_workload(num_entities=3, num_sessions=5, num_queries=5, seed=9)
    a = MemForestSystem(MemForestConfig())
    b = MemForestSystem(MemForestConfig())
    b.set_mesh(None)
    for s in wl.sessions:
        a.ingest_session(s)
        b.ingest_session(s)
    for ra, rb in zip(a.query_batch(wl.queries), b.query_batch(wl.queries)):
        assert ra.answer == rb.answer and ra.evidence == rb.evidence


def test_sharded_serve_config_single_device_fallback():
    """ShardedServeConfig asking for more devices than the host has raises
    instead of degrading to mesh=None serve; one device is the fast path."""
    from repro.config import MemForestConfig
    from repro.core.memforest import MemForestSystem
    from repro.serving.engine import ServeEngine, ShardedServeConfig

    class _NoModel:
        class cfg:
            num_layers = 0

        def prefill(self, params, batch, max_len):
            import jax.numpy as jnp
            B = batch["tokens"].shape[0]
            return jnp.zeros((B, 4)), {}

        def decode(self, params, batch, cache):
            import jax.numpy as jnp
            B = batch["tokens"].shape[0]
            return jnp.zeros((B, 4)), cache

    mf = MemForestSystem(MemForestConfig())
    with pytest.raises(ValueError, match="4 devices requested"):
        ServeEngine(_NoModel(), None, memory=mf,
                    sharded=ShardedServeConfig(devices=4))
    assert mf.forest.mesh is None
    eng = ServeEngine(_NoModel(), None, memory=mf,
                      sharded=ShardedServeConfig(devices=1))
    assert eng.serve_mesh is None
    assert mf.forest.mesh is None
    assert eng.metrics()["serve_devices"] == 1


# ---------------------------------------------------------------------------
# multi-device parity (subprocess: forced host device count)
# ---------------------------------------------------------------------------
def test_multi_device_parity_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "sharded_parity_check.py"),
         "--meshes", "2,4"],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT,
    )
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    assert "PARITY OK" in r.stdout
