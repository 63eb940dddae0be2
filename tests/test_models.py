"""Per-architecture smoke tests: reduced configs, one forward/train step on
CPU, output shapes + finiteness; prefill->decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.configs.shapes import SHAPES, cells_for_arch
from repro.models import get_model
from repro.models.factory import input_specs, make_batch


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = get_smoke_config(arch)
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_batch(cfg, "train", 2, 32, jax.random.key(1))
    loss, metrics = jax.jit(model.loss)(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), (arch, loss)


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_smoke(arch):
    cfg = get_smoke_config(arch)
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    pb = make_batch(cfg, "prefill", 2, 16, jax.random.key(1))
    logits, cache = jax.jit(lambda p, b: model.prefill(p, b, 32))(params, pb)
    assert logits.shape == (2, cfg.vocab_size)
    db = make_batch(cfg, "decode", 2, 16, jax.random.key(2))
    logits2, cache2 = jax.jit(model.decode)(params, db, cache)
    assert logits2.shape == (2, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits2.astype(jnp.float32)))), arch


@pytest.mark.parametrize("arch", ["llama3_8b", "rwkv6_1b6", "zamba2_7b"])
def test_decode_matches_prefill(arch):
    """Prefill(t[0:n]) then decode(t[n]) must equal prefill(t[0:n+1])'s last
    logits — the KV-cache/state path is consistent with the parallel path."""
    cfg = get_smoke_config(arch)
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(3), (2, 17), 0, cfg.vocab_size)

    logits_full, _ = model.prefill(params, {"tokens": toks}, 32)
    _, cache = model.prefill(params, {"tokens": toks[:, :16]}, 32)
    logits_step, _ = model.decode(params, {"tokens": toks[:, 16]}, cache)
    np.testing.assert_allclose(
        np.asarray(logits_full, np.float32), np.asarray(logits_step, np.float32),
        atol=5e-2, rtol=5e-2,
    )


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_param_count(arch):
    """Full configs' analytic param counts are in the advertised ballpark."""
    cfg = get_config(arch)
    n = cfg.param_count()
    expected = {
        "whisper-base": (60e6, 120e6),
        "rwkv6-1.6b": (1.2e9, 2.2e9),
        # assigned config (81 full mamba2 layers at d_model 3584) evaluates
        # above the checkpoint's 7.4B — the vendor interleaves narrower
        # blocks; we implement the assignment as specified (DESIGN.md §5)
        "zamba2-7b": (5e9, 13e9),
        "qwen3-moe-235b-a22b": (180e9, 280e9),
        "olmoe-1b-7b": (5e9, 9e9),
        "starcoder2-7b": (6e9, 9e9),
        "phi3-mini-3.8b": (3e9, 5e9),
        "llama3-8b": (6.5e9, 9e9),
        "granite-3-8b": (6.5e9, 10e9),
        "pixtral-12b": (10e9, 14e9),
        "deepseek-v2-lite": (15.0e9, 16.5e9),
    }[cfg.name]
    assert expected[0] <= n <= expected[1], (cfg.name, f"{n:,}")
    if cfg.family == "moe":
        active = cfg.param_count(active_only=True)
        assert active < n / 4, "MoE active params should be far below total"


@pytest.mark.parametrize("arch", ARCHS)
def test_input_specs_cover_all_cells(arch):
    cfg = get_config(arch)
    for shape in cells_for_arch(cfg):
        specs = input_specs(cfg, shape)
        assert "tokens" in specs
        for v in specs.values():
            assert v.shape[0] == shape.global_batch
        if cfg.family == "encdec":
            assert "frames" in specs or shape.kind == "decode"
        if cfg.family == "vlm" and shape.kind != "decode":
            assert "patch_embeds" in specs
            assert specs["tokens"].shape[1] + specs["patch_embeds"].shape[1] == shape.seq_len


def test_long_500k_applicability():
    from repro.configs.shapes import shape_applicable, SHAPES
    assert shape_applicable(get_config("rwkv6_1b6"), SHAPES["long_500k"])[0]
    assert shape_applicable(get_config("zamba2_7b"), SHAPES["long_500k"])[0]
    ok, why = shape_applicable(get_config("llama3_8b"), SHAPES["long_500k"])
    assert not ok and "full-attention" in why
