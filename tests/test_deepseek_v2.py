"""DeepSeek-V2 (latent attention, YaRN, leading dense layer, a dropless
expert layer holding a slice of the routed experts, shared experts)
against its plain float32 reference, ``bench/arch/deepseek_v2.py``, at a
tiny size: d 64, latent 16, nope/rope/v heads 16/8/16, one dense and two
expert layers, a router over 16 experts of which 4 are held, top-4, two
shared experts."""
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.encoder import ModelEncoder
from repro.data.tokenizer import HashTokenizer
from repro.models import get_model
from repro.models import layers as L
from repro.serving.engine import ServeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_arch():
    path = os.path.join(ROOT, "bench", "arch", "deepseek_v2.py")
    spec = importlib.util.spec_from_file_location("bench_arch_deepseek_v2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ds = _load_arch()

TINY = {
    "name": "tiny-deepseek-v2", "hidden_size": 64, "intermediate_size": 128,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "num_attention_heads": 4, "num_key_value_heads": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "moe_intermediate_size": 32, "n_routed_experts": 4,
    "num_experts_per_tok": 4, "n_shared_experts": 2, "norm_topk_prob": False,
    "routed_scaling_factor": 1, "vocab_size": 512, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "first_expert_held": 4,
    "rope_scaling": {"type": "yarn", "factor": 40, "original_max_position_embeddings": 64,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707},
    "reduced": {"n_routed_experts": {"published": 16}},
}
# the program's logits against the reference's: bf16 weights and caches
# put the served logits ~0.06 off (the largest is ~3.5); the float8
# control is ~1.1 off
LOGIT_TOL = 0.15


def _model(impl="reference", **cfg):
    mc = ds.model_config({**TINY, **cfg}).replace(attention_impl=impl)
    model = get_model(mc)
    return mc, model, model.init(jax.random.key(1))


@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
def test_prefill_then_absorbed_decode_matches_the_reference(impl):
    mc, model, params = _model(impl)
    toks = [(13 * i + 7) % 512 for i in range(20)]
    logits, cache = jax.jit(lambda p, b: model.prefill(p, b, 32))(
        params, {"tokens": jnp.asarray([toks[:14]] * 2, jnp.int32)})
    assert set(cache) == {"dense_c_kv", "dense_k_pe", "c_kv", "k_pe", "lengths"}
    assert cache["c_kv"].shape == (2, 2, 32, 16) and cache["k_pe"].shape == (2, 2, 32, 8)
    served = [np.asarray(logits, np.float32)[0]]
    dec = jax.jit(model.decode)
    for t in toks[14:19]:
        logits, cache = dec(params, {"tokens": jnp.asarray([t, t], jnp.int32)}, cache)
        served.append(np.asarray(logits, np.float32)[0])
    ref = ds.forward(params, TINY, toks[:19], 13, 6)
    np.testing.assert_allclose(np.stack(served), ref, atol=LOGIT_TOL, rtol=0)
    low = ds.forward(params, TINY, toks[:19], 13, 6, fp8=True)
    assert np.abs(low - ref).max() > LOGIT_TOL


def test_the_held_shares_add_up_to_the_uncut_layer():
    """Each of the four slices of 4 experts computes its experts' part; the
    parts, with the shared experts counted once, are the whole layer."""
    full_cfg, _, _ = _model(n_routed_experts=16, first_expert_held=0)
    full_cfg = full_cfg.replace(dtype="float32", param_dtype="float32")
    p = L.moe_init(jax.random.key(2), full_cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(3), (2, 24, 64), jnp.float32)
    whole, _, rows_whole = L.moe_block(p, x, full_cfg)
    shared = L.mlp_block(p["shared"], x, full_cfg)
    parts, rows = 0.0, 0
    for lo in range(0, 16, 4):
        cut = full_cfg.replace(experts_held=4, first_expert=lo)
        sl = {**p, **{k: p[k][lo:lo + 4] for k in ("we_gate", "we_up", "we_down")}}
        y, _, r = L.moe_block(sl, x, cut)
        parts = parts + (y - shared)
        rows += int(r)
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(whole),
                               atol=1e-5, rtol=1e-5)
    assert rows == int(rows_whole) == 2 * 24 * 4       # top-4, all held


def test_the_expert_layer_is_the_references_share():
    """The program's expert layer, one slice held, against the reference's
    routing and experts (float32 weights, so only summation order differs)."""
    cfg = {**TINY, "torch_dtype": "float32"}
    mc = ds.model_config(cfg)
    p = L.moe_init(jax.random.key(4), mc, jnp.float32)
    x = jax.random.normal(jax.random.key(5), (1, 16, 64), jnp.float32)
    y, _, rows = L.moe_block(p, x, mc)
    probs = jax.nn.softmax(x[0] @ p["router"], axis=-1)
    top, idx = jax.lax.top_k(probs, 4)
    want = L.mlp_block(p["shared"], x[0], mc)
    for t in range(16):
        for w, e in zip(np.asarray(top[t]), np.asarray(idx[t])):
            if 4 <= e < 8:
                m = {"w_gate": p["we_gate"][e - 4], "w_up": p["we_up"][e - 4],
                     "w_down": p["we_down"][e - 4]}
                want = want.at[t].add(w * L.mlp_block(m, x[0, t], mc))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), atol=1e-5, rtol=1e-5)
    assert int(rows) == int(np.sum((np.asarray(idx) >= 4) & (np.asarray(idx) < 8)))


@pytest.mark.parametrize("lean", [False, True])
def test_the_buffer_tier_taken_leaves_the_layer_unchanged(lean, monkeypatch):
    """The dropless layer takes the smallest buffer that holds the rows
    routed here; its output is the one the largest buffer gives. ``lean``
    routes every token to the held experts, so only the largest holds them."""
    mc = ds.model_config({**TINY, "torch_dtype": "float32"})
    assert L.expert_buffer_tiers(mc, 512) == (768, 1280, 2048)
    p = L.moe_init(jax.random.key(6), mc, jnp.float32)
    x = jax.random.normal(jax.random.key(7), (1, 512, 64), jnp.float32)
    if lean:
        x = x.at[..., 0].set(8.0)
        p["router"] = p["router"].at[0, 4:8].set(8.0)
    y, _, rows = jax.jit(lambda p, x: L.moe_block(p, x, mc))(p, x)
    assert (int(rows) == 2048) == lean and int(rows) > 0
    assert lean or int(rows) <= 1280
    monkeypatch.setattr(L, "expert_buffer_tiers", lambda cfg, tokens: (2048,))
    y_bound, _, rows_bound = jax.jit(lambda p, x: L.moe_block(p, x, mc))(p, x)
    assert int(rows_bound) == int(rows)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_bound), atol=1e-5, rtol=1e-5)


def test_an_embedding_is_the_same_alone_and_in_a_batch_of_64():
    """Dropless: no token's expert rows depend on what shares its forward."""
    mc, _, params = _model()
    enc = ModelEncoder(mc, params, HashTokenizer(512), max_len=32)
    words = "alpha bravo charlie delta echo foxtrot golf hotel india juliet kilo lima".split()
    texts = [" ".join(words[(i + j) % 12] for j in range(9)) for i in range(64)]
    alone = enc.encode([texts[5]])[0]
    batch = enc.encode(texts)
    np.testing.assert_allclose(batch[5], alone, atol=2e-3, rtol=0)
    assert enc.obs.registry.counter("moe.expert_rows").value > 0


def test_yarn_frequencies_and_mscale_are_the_closed_form():
    cfg = get_config("deepseek_v2_lite")
    inv = L.rope_inv_freq(64, cfg)
    # pairs 0..10 keep 10000^(-2i/64); pairs from 23 are stretched 40x; a
    # linear ramp between (correction dims 10.47 -> 10 and 22.6 -> 23)
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    for i in range(32):
        plain = 10000.0 ** (-2 * i / 64)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        assert inv[i] == pytest.approx(plain * (1 - ramp) + plain / 40 * ramp, rel=1e-6)
    assert L.mla_softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2, rel=1e-12)
    assert L.mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * 1.5896, rel=1e-4)
    # rotation of adjacent pairs, written out for one position
    x = jax.random.normal(jax.random.key(0), (1, 1, 1, 64), jnp.float32)
    out = np.asarray(L.rope_pairs(x, jnp.asarray([[7]]), inv))[0, 0, 0]
    xv = np.asarray(x)[0, 0, 0]
    a = 7 * inv.astype(np.float64)
    np.testing.assert_allclose(out[:32], xv[0::2] * np.cos(a) - xv[1::2] * np.sin(a), atol=1e-5)
    np.testing.assert_allclose(out[32:], xv[1::2] * np.cos(a) + xv[0::2] * np.sin(a), atol=1e-5)


def test_a_request_admitted_into_a_running_batch_decodes_as_alone():
    """Both cache stacks (the dense layer's and the expert layers') take
    the new lane's rows on admission."""
    mc, model, params = _model()
    prompt = [(5 * i + 3) % 512 for i in range(12)]

    def engine():
        return ServeEngine(model, params, max_batch=4, max_len=64, eos_id=-1)

    alone = engine()
    rid = alone.submit(prompt, max_new_tokens=6)
    alone.run_until_drained()
    want = next(r for r in alone.finished if r.req_id == rid)

    busy = engine()
    busy.submit([9] * 20, max_new_tokens=12)
    busy.step()
    busy.step()
    rid = busy.submit(prompt, max_new_tokens=6)
    busy.step()                                   # admitted beside the first
    slot = next(i for i, a in enumerate(busy.active) if a is not None and a.req_id == rid)
    ref = engine()
    ref.submit(prompt, max_new_tokens=6)
    ref._admit()
    for key in ("dense_c_kv", "dense_k_pe", "c_kv", "k_pe"):
        got = np.asarray(busy.cache[key][:, slot, :len(prompt)], np.float32)
        exp = np.asarray(ref.cache[key][:, 0, :len(prompt)], np.float32)
        for layer in range(got.shape[0]):
            np.testing.assert_allclose(got[layer], exp[layer], atol=1e-2, rtol=0)
    busy.run_until_drained()
    got = next(r for r in busy.finished if r.req_id == rid)
    assert got.out_tokens == want.out_tokens


def test_full_config_shapes():
    cfg = get_config("deepseek_v2_lite")
    assert cfg.moe_layers == 26 and cfg.held_experts == 64
    assert cfg.replace(experts_held=16).param_count() == pytest.approx(4.91e9, rel=2e-3)
    assert L.expert_buffer_rows(cfg, 8192) == 8192 * 6
    assert L.expert_buffer_tiers(cfg, 8192) == (8192 * 6,)
    # 16 of 64 held: 1.25x the 1.5 rows a token an even router sends, doubled
    assert L.expert_buffer_tiers(cfg.replace(experts_held=16), 8192) == (15360, 30720, 49152)
