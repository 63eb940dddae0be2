"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracle,
across shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa, ops, ref

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _mk(rng, shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(rng.normal(size=shape) * scale, dtype)


# ---------------------------------------------------------------------------
def _grid(q, k, v, **kw):
    """The grid of the one Pallas call ``flash_attention`` launches: rank 1
    on the packed path, rank 4 on the tiled one."""
    jaxpr = jax.make_jaxpr(lambda q, k, v: fa.flash_attention(q, k, v, interpret=True, **kw))
    (eqn,) = [e for e in jaxpr(q, k, v).eqns if e.primitive.name == "pallas_call"]
    return eqn.params["grid_mapping"].grid


@pytest.mark.parametrize("B,S,Hq,Hkv,D,bq,bk", [
    (1, 128, 4, 4, 32, 64, 64),      # MHA
    (2, 256, 8, 2, 64, 64, 128),     # GQA 4:1
    (1, 512, 4, 1, 16, 128, 256),    # MQA
    (2, 128, 6, 2, 24, 32, 64),      # non-pow2 head_dim
    # packed path: the whole sequence fits one tile
    (2, 16, 4, 4, 96, 512, 512),     # MHA, encoder head_dim
    (2, 128, 4, 4, 96, 512, 512),    # MHA, encoder head_dim, widest encoder text
    (2, 32, 8, 2, 64, 512, 512),     # GQA 4:1
    (2, 32, 4, 1, 32, 512, 512),     # MQA
    (3, 16, 2, 2, 32, 512, 512),     # B*Hkv = 6: G = 2, not the 128 rows allow
    (3, 32, 4, 1, 32, 32, 32),       # B*Hkv = 3: one slab a step; S == block
    (2, 256, 4, 4, 96, 128, 128),    # S > block: tiled path
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(rng, B, S, Hq, Hkv, D, bq, bk, dtype):
    q = _mk(rng, (B, S, Hq, D), dtype)
    k = _mk(rng, (B, S, Hkv, D), dtype)
    v = _mk(rng, (B, S, Hkv, D), dtype)
    packed = S <= bq and S <= bk
    assert len(_grid(q, k, v, block_q=bq, block_kv=bk)) == (1 if packed else 4)
    out_ref = ops.attention(q, k, v, impl="reference")
    out_pal = ops.attention(q, k, v, impl="pallas_interpret", block_q=bq, block_kv=bk)
    np.testing.assert_allclose(
        np.asarray(out_ref, np.float32), np.asarray(out_pal, np.float32),
        atol=ATOL[dtype], rtol=1e-2,
    )


def test_flash_attention_noncausal(rng):
    q = _mk(rng, (2, 128, 4, 32))
    k = _mk(rng, (2, 128, 2, 32))
    v = _mk(rng, (2, 128, 2, 32))
    o1 = ops.attention(q, k, v, causal=False, impl="reference")
    o2 = ops.attention(q, k, v, causal=False, impl="pallas_interpret",
                       block_q=64, block_kv=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5, rtol=1e-3)


@pytest.mark.parametrize("B,S,Hq,Hkv,D", [
    (2, 16, 4, 4, 96), (2, 128, 4, 4, 96), (2, 32, 8, 2, 64), (2, 32, 4, 1, 32),
    (3, 16, 2, 2, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_packed_noncausal(rng, B, S, Hq, Hkv, D, dtype):
    q = _mk(rng, (B, S, Hq, D), dtype)
    k = _mk(rng, (B, S, Hkv, D), dtype)
    v = _mk(rng, (B, S, Hkv, D), dtype)
    assert len(_grid(q, k, v, causal=False)) == 1
    o1 = ops.attention(q, k, v, causal=False, impl="reference")
    o2 = ops.attention(q, k, v, causal=False, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o1, np.float32), np.asarray(o2, np.float32),
                               atol=ATOL[dtype], rtol=1e-2)


@pytest.mark.parametrize("B,S,H,Dk,Dv,bq,bk", [
    (2, 32, 4, 24, 16, 512, 512),    # packed: latent attention's shape at a tiny size
    (4, 32, 16, 192, 128, 512, 512),  # packed: DeepSeek-V2's q/k 192, v 128
    (2, 256, 4, 24, 16, 128, 128),   # tiled
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_v_narrower_with_a_scale(rng, B, S, H, Dk, Dv, bq, bk, dtype):
    """v of its own width and a softmax scale passed in, on both paths."""
    q = _mk(rng, (B, S, H, Dk), dtype)
    k = _mk(rng, (B, S, H, Dk), dtype)
    v = _mk(rng, (B, S, H, Dv), dtype)
    scale = 1.5896 / Dk ** 0.5
    assert len(_grid(q, k, v, block_q=bq, block_kv=bk, sm_scale=scale)) == (
        1 if S <= bq else 4)
    o1 = ops.attention(q, k, v, impl="reference", sm_scale=scale)
    o2 = ops.attention(q, k, v, impl="pallas_interpret", block_q=bq, block_kv=bk,
                       sm_scale=scale)
    assert o2.shape == (B, S, H, Dv)
    np.testing.assert_allclose(np.asarray(o1, np.float32), np.asarray(o2, np.float32),
                               atol=ATOL[dtype], rtol=1e-2)


def test_flash_attention_default_scale_is_the_head_width():
    """With Dk == Dv and no scale the call is the one it always was."""
    q = jax.ShapeDtypeStruct((2, 32, 4, 96), jnp.bfloat16)
    plain = jax.make_jaxpr(lambda q, k, v: fa.flash_attention(q, k, v, interpret=True))
    named = jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
        q, k, v, sm_scale=1.0 / 96 ** 0.5, interpret=True))
    assert str(plain(q, q, q)) == str(named(q, q, q))


@pytest.mark.parametrize("sizes", [[300, 0, 17, 195], [0, 0, 0, 0], [512, 0, 0, 0],
                                   [1, 2, 3, 4]])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul(rng, sizes, dtype):
    """Rows grouped by expert against each expert's matrix; rows past the
    groups are not the kernel's to write and are not compared."""
    m, K, N = 512, 64, 96
    lhs = _mk(rng, (m, K), dtype)
    rhs = _mk(rng, (4, K, N), dtype, scale=K ** -0.5)
    gs = jnp.asarray(sizes, jnp.int32)
    want = np.concatenate([np.asarray(lhs[a:a + n], np.float32) @ np.asarray(rhs[g], np.float32)
                           for g, (a, n) in enumerate(zip(np.cumsum([0] + sizes), sizes))]
                          + [np.zeros((0, N), np.float32)])
    n = sum(sizes)
    for impl in ("reference", "pallas_interpret"):
        got = ops.grouped_matmul(lhs, rhs, gs, impl=impl)
        assert got.shape == (m, N) and got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32)[:n], want,
                                   atol=ATOL[dtype] * 4, rtol=1e-2)


ENCODER = [(rows, width, 32, 32, 96) for rows in (8, 16, 32, 64, 128, 256)
           for width in (16, 32, 64, 128)]
GRANITE = [(rows, width, 32, 8, 128) for rows in (1, 8, 64, 256)
           for width in (16, 32, 64, 128)]


@pytest.mark.parametrize("itemsize", [2, 4])
def test_packed_slabs_rule(itemsize):
    """G divides B*Hkv, is a power of two, keeps the VMEM estimate under
    the budget (itself under the 16 MiB scoped limit) and the rows of a
    step at the target, and is the largest such G."""
    assert fa.VMEM_BUDGET <= 16 * 2**20
    for B, S, Hq, Hkv, D in ENCODER + GRANITE:
        n, g = B * Hkv, Hq // Hkv
        G = fa.slabs_per_step(n, g, S, D, itemsize)
        assert G >= 1 and n % G == 0 and G & (G - 1) == 0, (B, S, G)
        assert fa.packed_vmem_bytes(G, g, S, D, itemsize) <= fa.VMEM_BUDGET
        assert G == 1 or G * g * S <= fa.PACKED_ROWS
        grows = (n % (2 * G) == 0 and 2 * G * g * S <= fa.PACKED_ROWS
                 and fa.packed_vmem_bytes(2 * G, g, S, D, itemsize) <= fa.VMEM_BUDGET)
        assert not grows, (B, S, G)


def test_packed_slabs_steps():
    """The encoder's forwards take at most 512 steps a layer at bf16; a
    shape whose single slab would not fit VMEM gets 0 (the tiled path)."""
    for B, S, Hq, Hkv, D in ENCODER:
        assert B * Hkv // fa.slabs_per_step(B * Hkv, 1, S, D, 2) <= 512
    assert fa.slabs_per_step(8 * 8, 4, 512, 128, 2) == 0
    assert fa.packed_vmem_bytes(1, 4, 512, 128, 2) > fa.VMEM_BUDGET


def test_blockwise_causal_matches_exact(rng):
    q = _mk(rng, (2, 192, 4, 16))
    k = _mk(rng, (2, 192, 2, 16))
    v = _mk(rng, (2, 192, 2, 16))
    o1 = ref.attention_ref(q, k, v, causal=True)
    o2 = ref.blockwise_causal_attention(q, k, v, block_q=64, block_kv=32)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5, rtol=1e-3)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,Smax,Hq,Hkv,D,bk", [
    (2, 128, 4, 2, 32, 32),
    (1, 256, 8, 8, 64, 64),
    (3, 64, 4, 1, 16, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(rng, B, Smax, Hq, Hkv, D, bk, dtype):
    q = _mk(rng, (B, Hq, D), dtype)
    kc = _mk(rng, (B, Smax, Hkv, D), dtype)
    vc = _mk(rng, (B, Smax, Hkv, D), dtype)
    lens = jnp.asarray(rng.integers(1, Smax, size=(B,)), jnp.int32)
    o1 = ops.decode_attention(q, kc, vc, lens, impl="reference")
    o2 = ops.decode_attention(q, kc, vc, lens, impl="pallas_interpret", block_kv=bk)
    np.testing.assert_allclose(
        np.asarray(o1, np.float32), np.asarray(o2, np.float32),
        atol=ATOL[dtype], rtol=1e-2,
    )


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("Q,N,D,K", [(1, 50, 32, 4), (7, 300, 64, 8),
                                     (16, 1000, 128, 16), (3, 10, 16, 4)])
def test_topk_sim(rng, Q, N, D, K):
    q = _mk(rng, (Q, D))
    keys = _mk(rng, (N, D))
    v1, i1 = ops.topk_sim(q, keys, K, impl="reference")
    v2, i2 = ops.topk_sim(q, keys, K, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-5)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))


def test_topk_sim_num_valid(rng):
    q = _mk(rng, (2, 16))
    keys = _mk(rng, (32, 16))
    padded = jnp.concatenate([keys[:20], jnp.zeros((12, 16))], axis=0)
    v1, i1 = ops.topk_sim(q, keys[:20], 5, impl="reference")
    v2, i2 = ops.topk_sim(q, padded, 5, num_valid=20, impl="reference")
    v3, i3 = ops.topk_sim(q, padded, 5, num_valid=20, impl="pallas_interpret")
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    assert np.array_equal(np.asarray(i1), np.asarray(i3))


@pytest.mark.parametrize("block_kv", [8, 16, 512])
def test_topk_sim_planted_ties_keep_index_order(block_kv):
    """Keys repeat 5 distinct rows, so every score is held by ~8 keys spread
    over several key tiles: the kernel must order them (score desc, key
    index asc), exactly as the oracle's two-key sort does."""
    from repro.kernels.topk_sim import topk_sim as topk_kernel

    rng = np.random.default_rng(3)
    base = rng.normal(size=(5, 16)).astype(np.float32)
    group = rng.integers(0, 5, size=40)
    keys = jnp.asarray(base[group])
    q = jnp.asarray(rng.normal(size=(3, 16)), jnp.float32)
    vals, idx = topk_kernel(q, keys, 12, block_kv=block_kv, interpret=True)
    _, ridx = ops.topk_sim(q, keys, 12, impl="reference")
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    bn = base / np.linalg.norm(base, axis=-1, keepdims=True)
    qn = np.asarray(q) / np.linalg.norm(np.asarray(q), axis=-1, keepdims=True)
    for r in range(3):
        order = np.argsort(-(bn @ qn[r]), kind="stable")
        want = [i for g in order for i in np.flatnonzero(group == g)][:12]
        assert list(np.asarray(idx[r])) == want
        assert np.all(np.diff(np.asarray(vals[r])) <= 0)


def test_resolve_impl_platform_default():
    """Unset kernel/attention implementations resolve by platform: the
    reference off the TPU, so every CPU test keeps its path."""
    from repro.config import MemForestConfig, ModelConfig
    from repro.core.forest import Forest

    assert jax.default_backend() == "cpu"
    assert ops.resolve_impl() == "reference"
    assert ops.resolve_impl(None) == "reference"
    for impl in ops.VALID_IMPLS:
        assert ops.resolve_impl(impl) == impl
    with pytest.raises(ValueError):
        ops.resolve_impl("mosaic")
    assert Forest(MemForestConfig()).kernel_impl == "reference"
    assert Forest(MemForestConfig(),
                  kernel_impl="pallas_interpret").kernel_impl == "pallas_interpret"
    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=8,
                      num_heads=2, num_kv_heads=2, d_ff=16, vocab_size=32)
    assert cfg.attention_impl is None
    assert ops.resolve_impl(cfg.attention_impl) == "reference"


def test_resolve_impl_on_tpu_skips_kernels_that_do_not_lower(monkeypatch):
    """On a TPU an unset implementation is Pallas, except for the kernels
    that do not lower there yet; a named implementation always wins."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.resolve_impl() == "pallas"
    assert ops.resolve_impl(kernel="topk_sim") == "pallas"
    assert ops.NOT_ON_TPU == {"rwkv6_scan", "mamba2_ssd"}
    for kernel in ops.NOT_ON_TPU:
        assert ops.resolve_impl(kernel=kernel) == "reference"
        assert ops.resolve_impl("pallas", kernel=kernel) == "pallas"


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("P,K,D", [(1, 2, 16), (10, 8, 32), (33, 16, 256)])
def test_tree_refresh(rng, P, K, D):
    emb = _mk(rng, (P, K, D))
    mask = jnp.asarray(rng.random((P, K)) > 0.4)
    # ensure at least one child each
    mask = mask.at[:, 0].set(True)
    o1 = ops.tree_refresh(emb, mask, impl="reference")
    o2 = ops.tree_refresh(emb, mask, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)
    # unit norm
    np.testing.assert_allclose(np.linalg.norm(np.asarray(o1), axis=-1), 1.0, atol=1e-3)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("F,K,D", [(1, 4, 64), (7, 8, 256), (64, 8, 128),
                                   (130, 16, 32)])
def test_browse_scores(rng, F, K, D):
    emb = _mk(rng, (F, K, D))
    q = _mk(rng, (F, D))
    mask = jnp.asarray((rng.random((F, K)) > 0.3).astype(np.float32))
    o1 = ops.browse_scores(emb, q, mask, impl="reference")
    o2 = ops.browse_scores(emb, q, mask, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)
    # oracle: per-row masked matvec
    want = np.einsum("fkd,fd->fk", np.asarray(emb), np.asarray(q)) * np.asarray(mask)
    np.testing.assert_allclose(np.asarray(o1), want, atol=2e-5)


def test_normalize_rows_matches_kernel_formula(rng):
    x = _mk(rng, (33, 64), scale=3.0)
    out = np.asarray(ops.normalize_rows(x))
    want = np.asarray(x, np.float32)
    want = want / (np.linalg.norm(want, axis=-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(out, want, atol=1e-6)
    # pre-normalized keys + normalize=False == raw keys + normalize=True
    keys = _mk(rng, (50, 64))
    q = _mk(rng, (4, 64))
    v1, i1 = ops.topk_sim(q, keys, 5, impl="reference")
    v2, i2 = ops.topk_sim(ops.normalize_rows(q), ops.normalize_rows(keys), 5,
                          normalize=False, impl="reference")
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_scatter_normalize_rows(rng):
    base = np.asarray(rng.normal(size=(16, 32)), np.float32)
    arr = ops.normalize_rows(jnp.asarray(base))
    rows = np.asarray(rng.normal(size=(4, 32)), np.float32)
    idx = np.asarray([3, 7, 16, 16], np.int32)   # two padding slots (dropped)
    out = np.asarray(ops.scatter_normalize_rows(
        arr, jnp.asarray(idx), jnp.asarray(rows)))
    want = base / (np.linalg.norm(base, axis=-1, keepdims=True) + 1e-6)
    want[3] = rows[0] / (np.linalg.norm(rows[0]) + 1e-6)
    want[7] = rows[1] / (np.linalg.norm(rows[1]) + 1e-6)
    np.testing.assert_allclose(out, want, atol=1e-6)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,T,H,K,V,chunk", [
    (1, 64, 2, 8, 8, 16), (2, 128, 2, 16, 16, 32), (1, 96, 3, 8, 16, 32),
])
def test_rwkv6_scan(rng, B, T, H, K, V, chunk):
    r = _mk(rng, (B, T, H, K), scale=0.5)
    k = _mk(rng, (B, T, H, K), scale=0.5)
    v = _mk(rng, (B, T, H, V), scale=0.5)
    w = _mk(rng, (B, T, H, K), scale=0.5)
    u = _mk(rng, (H, K), scale=0.5)
    s0 = _mk(rng, (B, H, K, V), scale=0.1)
    o1, s1 = ops.rwkv6_scan(r, k, v, w, u, s0, impl="reference")
    o2, s2 = ops.rwkv6_scan(r, k, v, w, u, s0, impl="pallas_interpret", chunk=chunk)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-4, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-4, rtol=1e-2)
    # chunked jnp (model path) against exact too
    o3, s3 = ref.rwkv6_chunked(r, k, v, w, u, s0, chunk=chunk)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o3), atol=2e-4, rtol=1e-2)


def test_rwkv6_decode_step_matches_scan(rng):
    B, H, K, V = 2, 2, 8, 8
    r = _mk(rng, (B, 1, H, K)); k = _mk(rng, (B, 1, H, K))
    v = _mk(rng, (B, 1, H, V)); w = _mk(rng, (B, 1, H, K))
    u = _mk(rng, (H, K)); s0 = _mk(rng, (B, H, K, V), scale=0.1)
    o1, s1 = ref.rwkv6_scan_ref(r, k, v, w, u, s0)
    o2, s2 = ref.rwkv6_decode_step(r[:, 0], k[:, 0], v[:, 0], w[:, 0], u, s0)
    np.testing.assert_allclose(np.asarray(o1[:, 0]), np.asarray(o2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,T,H,P,N,chunk", [
    (1, 64, 2, 8, 4, 16), (2, 128, 3, 16, 8, 32),
])
def test_mamba2_ssd(rng, B, T, H, P, N, chunk):
    x = _mk(rng, (B, T, H, P))
    dt = jnp.asarray(rng.random((B, T, H)) * 0.5 + 0.01, jnp.float32)
    A = -jnp.asarray(rng.random((H,)) + 0.1, jnp.float32)
    Bm = _mk(rng, (B, T, N))
    C = _mk(rng, (B, T, N))
    s0 = _mk(rng, (B, H, P, N), scale=0.1)
    y1, s1 = ops.mamba2_ssd(x, dt, A, Bm, C, s0, impl="reference")
    y2, s2 = ops.mamba2_ssd(x, dt, A, Bm, C, s0, impl="pallas_interpret", chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-4, rtol=1e-2)
    y3, s3 = ref.mamba2_ssd_chunked(x, dt, A, Bm, C, s0, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y3), atol=2e-4, rtol=1e-2)
