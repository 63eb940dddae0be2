"""Pure-jnp oracles for every Pallas kernel.

These are the ground truth used by the per-kernel allclose tests and by the
models when ``attention_impl`` resolves to ``"reference"`` (every platform
but the TPU). They are written for clarity and exactness, not speed: the
memory-index scores use f32 at HIGHEST precision, as the Pallas kernels do,
so on a TPU the two agree to f32 rounding rather than to bf16 passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# attention (prefill, causal, GQA)
# --------------------------------------------------------------------------
def attention_ref(
    q: jax.Array,  # (B, S, Hq, D)
    k: jax.Array,  # (B, S, Hkv, D)
    v: jax.Array,  # (B, S, Hkv, D)
    *,
    causal: bool = True,
    sm_scale=None,
) -> jax.Array:
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    scale = (1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32)) if sm_scale is None
             else sm_scale)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    logits = logits * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), dtype=bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def grouped_matmul_ref(lhs: jax.Array, rhs: jax.Array,
                       group_sizes: jax.Array) -> jax.Array:
    """lhs (m, K) rows grouped by group_sizes, each times rhs[g] (K, N);
    rows past the groups are zero."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32).astype(lhs.dtype)


def cross_attention_ref(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Skv, Hkv, D)
    v: jax.Array,  # (B, Skv, Hkv, D)
) -> jax.Array:
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# --------------------------------------------------------------------------
# decode attention (single new token vs KV cache)
# --------------------------------------------------------------------------
def decode_attention_ref(
    q: jax.Array,        # (B, Hq, D) — one new token per sequence
    k_cache: jax.Array,  # (B, Smax, Hkv, D)
    v_cache: jax.Array,  # (B, Smax, Hkv, D)
    lengths: jax.Array,  # (B,) int32 — valid cache entries per sequence
) -> jax.Array:
    B, Smax, Hkv, D = k_cache.shape
    Hq = q.shape[1]
    group = Hq // Hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    qf = q.astype(jnp.float32).reshape(B, Hkv, group, D)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    logits = jnp.einsum("bhgd,bshd->bhgs", qf, kf) * scale
    pos = jnp.arange(Smax)[None, None, None, :]
    valid = pos < lengths[:, None, None, None]
    logits = jnp.where(valid, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", probs, vf)
    return out.reshape(B, Hq, D).astype(q.dtype)


# --------------------------------------------------------------------------
# fused similarity + top-k (forest recall / fact recall hot path)
# --------------------------------------------------------------------------
def topk_sim_ref(
    queries: jax.Array,  # (Q, D)
    keys: jax.Array,     # (N, D)
    k: int,
    *,
    normalize: bool = True,
    num_valid=None,      # optional traced scalar: rows >= num_valid masked out
):
    qf = queries.astype(jnp.float32)
    kf = keys.astype(jnp.float32)
    if normalize:
        qf = qf / (jnp.linalg.norm(qf, axis=-1, keepdims=True) + 1e-6)
        kf = kf / (jnp.linalg.norm(kf, axis=-1, keepdims=True) + 1e-6)
    scores = jnp.matmul(qf, kf.T, precision=jax.lax.Precision.HIGHEST)  # (Q, N)
    if num_valid is not None:
        cols = jnp.arange(scores.shape[1])[None, :]
        scores = jnp.where(cols < num_valid, scores, -1e30)
    # deterministic selection ordered by (score desc, key index asc) — the
    # same tie-break the Pallas kernel's first-occurrence argmax applies and
    # the cross-shard candidate merge (topk_sim.merge_topk) relies on for
    # exact single-device/multi-device parity. lax.top_k's tie order is
    # backend-defined, so the lexicographic two-key sort is explicit here.
    cols = jnp.broadcast_to(
        jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :], scores.shape)
    sneg, sidx = jax.lax.sort((-scores, cols), dimension=-1, num_keys=2)
    vals = -sneg[:, :k]
    idx = jnp.where(vals > -1e29, sidx[:, :k], -1)
    return vals, idx.astype(jnp.int32)


# --------------------------------------------------------------------------
# level-synchronous browse scoring: per-frontier-entry masked matvec
# --------------------------------------------------------------------------
def browse_scores_ref(
    child_emb: jax.Array,   # (F, K, D) — packed frontier children
    q_emb: jax.Array,       # (F, D) — per-entry query vector
    child_mask: jax.Array,  # (F, K) — 1.0 for real child slots
):
    s = jnp.einsum(
        "fkd,fd->fk", child_emb.astype(jnp.float32), q_emb.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    return s * child_mask.astype(jnp.float32)


# --------------------------------------------------------------------------
# tree refresh: masked segment-mean of child embeddings -> parent embedding
# --------------------------------------------------------------------------
def tree_refresh_ref(
    child_emb: jax.Array,   # (P, K, D) — padded children per dirty parent
    child_mask: jax.Array,  # (P, K) bool/float — which slots are real children
) -> jax.Array:
    m = child_mask.astype(jnp.float32)[..., None]          # (P, K, 1)
    s = jnp.sum(child_emb.astype(jnp.float32) * m, axis=1)  # (P, D)
    cnt = jnp.maximum(jnp.sum(m, axis=1), 1.0)              # (P, 1)
    mean = s / cnt
    norm = jnp.linalg.norm(mean, axis=-1, keepdims=True) + 1e-6
    return (mean / norm).astype(child_emb.dtype)


# --------------------------------------------------------------------------
# RWKV6 (Finch) WKV recurrence with data-dependent decay
# --------------------------------------------------------------------------
def rwkv6_scan_ref(
    r: jax.Array,      # (B, T, H, K)
    k: jax.Array,      # (B, T, H, K)
    v: jax.Array,      # (B, T, H, V)
    w: jax.Array,      # (B, T, H, K) raw; decay = exp(-exp(w))
    u: jax.Array,      # (H, K) bonus
    state: jax.Array,  # (B, H, K, V) carried state
):
    """Exact sequential recurrence.

        o_t = r_t · (S_{t-1} + diag(u) k_tᵀ v_t)
        S_t = diag(exp(-exp(w_t))) S_{t-1} + k_tᵀ v_t
    """
    rf, kf, vf, wf = (x.astype(jnp.float32) for x in (r, k, v, w))
    uf = u.astype(jnp.float32)
    s0 = state.astype(jnp.float32)

    def step(s, inp):
        rt, kt, vt, wt = inp  # (B,H,K),(B,H,K),(B,H,V),(B,H,K)
        kv = kt[..., :, None] * vt[..., None, :]           # (B,H,K,V)
        o = jnp.einsum("bhk,bhkv->bhv", rt, s + uf[None, :, :, None] * kv)
        s_new = jnp.exp(-jnp.exp(wt))[..., None] * s + kv
        return s_new, o

    xs = (
        jnp.moveaxis(rf, 1, 0),
        jnp.moveaxis(kf, 1, 0),
        jnp.moveaxis(vf, 1, 0),
        jnp.moveaxis(wf, 1, 0),
    )
    s_final, outs = jax.lax.scan(step, s0, xs)
    out = jnp.moveaxis(outs, 0, 1)  # (B, T, H, V)
    return out.astype(r.dtype), s_final.astype(state.dtype)


# --------------------------------------------------------------------------
# model-grade chunked implementations (memory-sane XLA fallbacks; same math
# as the Pallas kernels — these are what the models lower on the CPU dry-run)
# --------------------------------------------------------------------------
def blockwise_causal_attention(
    q: jax.Array,  # (B, S, Hq, D)
    k: jax.Array,  # (B, S, Hkv, D)
    v: jax.Array,  # (B, S, Hkv, D)
    *,
    block_q: int = 0,      # 0 -> pick so there are <= 16 q blocks
    block_kv: int = 512,
) -> jax.Array:
    """Exact-FLOPs causal attention: python loop over q blocks, each block
    attends to its *static* KV prefix with an online-softmax scan over KV
    chunks. No (S, S) logits materialization, no above-diagonal compute
    (except intra-diagonal-block masking) — this is flash attention in XLA.
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    if block_q == 0:
        block_q = max(-(-S // 16), 128)
        block_q = min(block_q, S)
    while S % block_q:
        block_q //= 2
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    nq = S // block_q

    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    kf = k.astype(jnp.float32).transpose(0, 2, 1, 3)  # (B, H, S, D)
    vf = v.astype(jnp.float32).transpose(0, 2, 1, 3)
    qf = q.astype(jnp.float32).transpose(0, 2, 1, 3)

    outs = []
    for iq in range(nq):
        q_blk = qf[:, :, iq * block_q:(iq + 1) * block_q]       # (B,H,bq,D)
        kv_len = (iq + 1) * block_q                              # static prefix
        bkv = min(block_kv, kv_len)
        while kv_len % bkv:
            bkv //= 2
        nkv = kv_len // bkv
        k_pre = kf[:, :, :kv_len].reshape(B, Hq, nkv, bkv, D)
        v_pre = vf[:, :, :kv_len].reshape(B, Hq, nkv, bkv, D)

        def kv_step(carry, kv, _iq=iq, _bkv=bkv):
            m, l, acc, ik = carry
            kb, vb = kv                                          # (B,H,bkv,D)
            s = jnp.einsum("bhqd,bhkd->bhqk", q_blk, kb) * scale
            rows = _iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            cols = ik * _bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
            s = jnp.where(rows >= cols, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, vb)
            return (m_new, l_new, acc_new, ik + 1), None

        init = (
            jnp.full((B, Hq, block_q, 1), -1e30, jnp.float32),
            jnp.zeros((B, Hq, block_q, 1), jnp.float32),
            jnp.zeros((B, Hq, block_q, D), jnp.float32),
            jnp.asarray(0, jnp.int32),
        )
        (m, l, acc, _), _ = jax.lax.scan(
            kv_step, init, (k_pre.transpose(2, 0, 1, 3, 4), v_pre.transpose(2, 0, 1, 3, 4))
        )
        outs.append(acc / jnp.maximum(l, 1e-30))
    out = jnp.concatenate(outs, axis=2)                          # (B,H,S,D)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def rwkv6_chunked(
    r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
    u: jax.Array, state: jax.Array, *, chunk: int = 64,
):
    """Chunked WKV6 in pure jnp — same math as kernels/rwkv6_scan.py.
    scan over T/chunk steps carrying the (B, H, K, V) state."""
    B, T, H, K = r.shape
    V = v.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0
    nc = T // chunk
    rf, kf, vf, wf = (x.astype(jnp.float32) for x in (r, k, v, w))
    uf = u.astype(jnp.float32)

    def resh(x, d):
        return x.reshape(B, nc, chunk, H, d).transpose(1, 0, 3, 2, 4)  # (nc,B,H,C,d)

    rc, kc, wc = resh(rf, K), resh(kf, K), resh(wf, K)
    vc = resh(vf, V)

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    strict = (t_idx > s_idx)[..., None]
    diag = (t_idx == s_idx)

    def step(s, inp):
        rb, kb, vb, wb = inp                       # (B,H,C,K/V)
        logdec = -jnp.exp(wb)
        cum = jnp.cumsum(logdec, axis=2)
        cum_excl = cum - logdec
        o_inter = jnp.einsum("bhck,bhkv->bhcv", rb * jnp.exp(cum_excl), s)
        diff = cum_excl[:, :, :, None, :] - cum[:, :, None, :, :]   # (B,H,C,C,K)
        gate = jnp.where(strict[None, None], jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
        A = jnp.einsum("bhtk,bhsk,bhtsk->bhts", rb, kb, gate)
        A_diag = jnp.sum(rb * uf[None, :, None, :] * kb, axis=-1)      # (B,H,C)
        A = A + jnp.where(diag[None, None], A_diag[:, :, :, None], 0.0)
        o_intra = jnp.einsum("bhts,bhsv->bhtv", A, vb)
        total = cum[:, :, -1]                      # (B,H,K)
        k_scaled = kb * jnp.exp(jnp.minimum(total[:, :, None, :] - cum, 0.0))
        s_new = jnp.exp(total)[..., None] * s + jnp.einsum("bhck,bhcv->bhkv", k_scaled, vb)
        return s_new, o_inter + o_intra

    s_final, outs = jax.lax.scan(step, state.astype(jnp.float32), (rc, kc, vc, wc))
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, T, H, V)
    return out.astype(r.dtype), s_final.astype(state.dtype)


def rwkv6_decode_step(r, k, v, w, u, state):
    """Single-token WKV6 step. r/k/w: (B,H,K); v: (B,H,V); state (B,H,K,V)."""
    rf, kf, vf, wf = (x.astype(jnp.float32) for x in (r, k, v, w))
    uf = u.astype(jnp.float32)
    sf = state.astype(jnp.float32)
    kv = kf[..., :, None] * vf[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", rf, sf + uf[None, :, :, None] * kv)
    s_new = jnp.exp(-jnp.exp(wf))[..., None] * sf + kv
    return o.astype(r.dtype), s_new.astype(state.dtype)


def mamba2_ssd_chunked(
    x: jax.Array, dt: jax.Array, A: jax.Array,
    Bm: jax.Array, C: jax.Array, state: jax.Array, *, chunk: int = 64,
):
    """Chunked SSD in pure jnp — same math as kernels/mamba2_ssd.py."""
    B, T, H, Pd = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0
    nc = T // chunk
    xf = x.astype(jnp.float32).reshape(B, nc, chunk, H, Pd).transpose(1, 0, 3, 2, 4)
    dtf = dt.astype(jnp.float32).reshape(B, nc, chunk, H).transpose(1, 0, 3, 2)
    Bf = Bm.astype(jnp.float32).reshape(B, nc, chunk, N).transpose(1, 0, 2, 3)
    Cf = C.astype(jnp.float32).reshape(B, nc, chunk, N).transpose(1, 0, 2, 3)
    Af = A.astype(jnp.float32)

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = t_idx >= s_idx

    def step(s, inp):
        xb, dtb, Bb, Cb = inp          # (B,H,C,P),(B,H,C),(B,C,N),(B,C,N)
        cum = jnp.cumsum(dtb * Af[None, :, None], axis=2)       # (B,H,C)
        dmat = jnp.where(
            lower[None, None], jnp.exp(jnp.minimum(cum[:, :, :, None] - cum[:, :, None, :], 0.0)), 0.0
        )                                                        # (B,H,C,C)
        cb = jnp.einsum("btn,bsn->bts", Cb, Bb)                  # (B,C,C)
        G = cb[:, None] * dmat * dtb[:, :, None, :]              # (B,H,C,C)
        y_intra = jnp.einsum("bhts,bhsp->bhtp", G, xb)
        y_inter = jnp.exp(cum)[..., None] * jnp.einsum("btn,bhpn->bhtp", Cb, s)
        total = cum[:, :, -1]                                    # (B,H)
        xw = xb * (dtb * jnp.exp(jnp.minimum(total[:, :, None] - cum, 0.0)))[..., None]
        s_new = jnp.exp(total)[..., None, None] * s + jnp.einsum(
            "bhcp,bcn->bhpn", xw, Bb
        )
        return s_new, y_intra + y_inter

    s_final, ys = jax.lax.scan(step, state.astype(jnp.float32), (xf, dtf, Bf, Cf))
    y = ys.transpose(1, 0, 3, 2, 4).reshape(B, T, H, Pd)
    return y.astype(x.dtype), s_final.astype(state.dtype)


def mamba2_decode_step(x, dt, A, Bm, C, state):
    """Single-token SSD step. x: (B,H,P); dt: (B,H); Bm/C: (B,N)."""
    xf, dtf, Bf, Cf = (a.astype(jnp.float32) for a in (x, dt, Bm, C))
    Af = A.astype(jnp.float32)
    sf = state.astype(jnp.float32)
    decay = jnp.exp(dtf * Af[None, :])
    upd = (dtf[..., None] * xf)[..., None] * Bf[:, None, None, :]
    s_new = decay[..., None, None] * sf + upd
    y = jnp.einsum("bhpn,bn->bhp", s_new, Cf)
    return y.astype(x.dtype), s_new.astype(state.dtype)


# --------------------------------------------------------------------------
# Mamba2 SSD recurrence (scalar decay per head)
# --------------------------------------------------------------------------
def mamba2_ssd_ref(
    x: jax.Array,      # (B, T, H, P)
    dt: jax.Array,     # (B, T, H) — already softplus'd, > 0
    A: jax.Array,      # (H,) — negative
    Bm: jax.Array,     # (B, T, N) — input matrix (single group)
    C: jax.Array,      # (B, T, N) — output matrix (single group)
    state: jax.Array,  # (B, H, P, N)
):
    """Exact sequential SSD recurrence.

        S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t ⊗ B_t
        y_t = S_t C_t
    """
    xf, dtf, Bf, Cf = (a.astype(jnp.float32) for a in (x, dt, Bm, C))
    Af = A.astype(jnp.float32)
    s0 = state.astype(jnp.float32)

    def step(s, inp):
        xt, dtt, Bt, Ct = inp  # (B,H,P),(B,H),(B,N),(B,N)
        decay = jnp.exp(dtt * Af[None, :])                  # (B,H)
        upd = (dtt[..., None] * xt)[..., None] * Bt[:, None, None, :]  # (B,H,P,N)
        s_new = decay[..., None, None] * s + upd
        y = jnp.einsum("bhpn,bn->bhp", s_new, Ct)
        return s_new, y

    xs = (
        jnp.moveaxis(xf, 1, 0),
        jnp.moveaxis(dtf, 1, 0),
        jnp.moveaxis(Bf, 1, 0),
        jnp.moveaxis(Cf, 1, 0),
    )
    s_final, ys = jax.lax.scan(step, s0, xs)
    y = jnp.moveaxis(ys, 0, 1)  # (B, T, H, P)
    return y.astype(x.dtype), s_final.astype(state.dtype)
