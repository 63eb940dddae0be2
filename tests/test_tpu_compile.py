"""Compile the serve path's Pallas kernels for a TPU v5e chip, without one.

The TPU compiler is installed even where no chip is attached. Each test
compiles one kernel at the widths the served phi3-mini path uses (D=3072
memory index, 32 heads of 96, prefill buckets, a 1024-slot decode cache)
for one chip of a described ``v5e:2x2`` topology, and checks that the
program holds the kernel (``tpu_custom_call``). Nothing runs: a compile
that passes says nothing about results or times.

The topology is described only inside the ``topo`` fixture, never while a
module is imported, so that only the worker given this file loads the TPU
library. Keep these tests in this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

D = 3072                     # phi3-mini d_model = MemForest embed_dim
HEADS, HEAD_DIM = 32, 96     # phi3-mini attention (MHA)
MAX_LEN = 1024               # served decode cache


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies

        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for an absent device can be written but never read back."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.mark.parametrize("dim,n,k", [(D, 4096, 16), (D, 4096, 24),
                                     (D, 300, 16), (256, 4096, 16)])
def test_topk_sim_compiles(one_chip, dim, n, k):
    _compile(lambda q, keys: ops.topk_sim(q, keys, k, normalize=False,
                                          num_valid=n - 7, impl="pallas"),
             one_chip, ((32, dim), F32), ((n, dim), F32))


@pytest.mark.parametrize("f,k", [(64, 8), (8, 4)])
def test_browse_scores_compiles(one_chip, f, k):
    _compile(lambda e, q, m: ops.browse_scores(e, q, m, impl="pallas"),
             one_chip, ((f, k, D), F32), ((f, D), F32), ((f, k), F32))


@pytest.mark.parametrize("p", [16, 1])
def test_tree_refresh_compiles(one_chip, p):
    _compile(lambda e, m: ops.tree_refresh(e, m, impl="pallas"),
             one_chip, ((p, 8, D), F32), ((p, 8), F32))


@pytest.mark.parametrize("b,s", [
    pytest.param(8, 16, id="16"), pytest.param(8, 64, id="64"),
    pytest.param(8, 128, id="128"),
    pytest.param(8, 1024, id="1024"),       # past one tile: the tiled path
    pytest.param(256, 128, id="256x128"),   # encoder forwards: the packed path
    pytest.param(256, 32, id="256x32"),
])
def test_flash_attention_compiles(one_chip, b, s):
    qkv = ((b, s, HEADS, HEAD_DIM), BF16)
    _compile(lambda q, k, v: ops.attention(q, k, v, impl="pallas"),
             one_chip, qkv, qkv, qkv)


def test_decode_attention_compiles(one_chip):
    cache = ((8, MAX_LEN, HEADS, HEAD_DIM), BF16)
    _compile(lambda q, k, v, n: ops.decode_attention(q, k, v, n, impl="pallas"),
             one_chip, ((8, HEADS, HEAD_DIM), BF16), cache, cache, ((8,), I32))


# DeepSeek-V2-Lite: latent attention decompressed to 16 heads of q/k 192
# and v 128, and the expert layer's grouped matmuls (16 experts held, d
# 2048, expert width 1408) at an encoder forward's buffer (256 x 32 tokens,
# 6 rows a token)
@pytest.mark.parametrize("b,s", [pytest.param(256, 32, id="256x32"),
                                 pytest.param(256, 128, id="256x128"),
                                 pytest.param(8, 1024, id="1024")])
def test_latent_attention_flash_compiles(one_chip, b, s):
    qk, v = ((b, s, 16, 192), BF16), ((b, s, 16, 128), BF16)
    _compile(lambda q, k, v: ops.attention(q, k, v, impl="pallas",
                                           sm_scale=1.5896 / 192 ** 0.5),
             one_chip, qk, qk, v)


@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)])
def test_grouped_matmul_compiles(one_chip, k, n):
    _compile(lambda x, w, g: ops.grouped_matmul(x, w, g, impl="pallas"),
             one_chip, ((49152, k), BF16), ((16, k, n), BF16), ((16,), I32))


def test_dropless_expert_layer_compiles(one_chip):
    """One expert layer of DeepSeek-V2-Lite with 16 of 64 experts held, at
    an encoder forward of 256 x 32 tokens: the switch over its three
    buffer sizes, each with its grouped matmuls."""
    from repro.configs import get_config
    from repro.models import layers as L

    cfg = get_config("deepseek_v2_lite").replace(experts_held=16, attention_impl="pallas")
    assert len(L.expert_buffer_tiers(cfg, 256 * 32)) == 3
    p = jax.eval_shape(lambda: L.moe_init(jax.random.key(0), cfg, BF16))
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in jax.tree.leaves(p)]
    x = jax.ShapeDtypeStruct((256, 32, 2048), BF16, sharding=one_chip)
    tree = jax.tree.structure(p)
    text = jax.jit(lambda x, *a: L.moe_block(jax.tree.unflatten(tree, a), x, cfg)[0]
                   ).lower(x, *args).compile().as_text()
    assert text.count("tpu_custom_call") >= 9
