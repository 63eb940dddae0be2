"""Everything of the harness that depends on a model's architecture lives in
``bench/arch/<arch>.py``, chosen by the configuration's ``"arch"`` key.

* The dense module gives the numbers the harness gave before the module
  existed (recorded from that code): operation counts, the plain reference
  and its float8 control, and the readers that count work.
* A file's explicit ``head_dim`` is honoured alike by the builder, the
  reference and the counts.
* An architecture is added by files alone, and the harness runs through it.
"""
import json
import os
import shutil
import time

import numpy as np
import pytest

from _paths import BENCH, FIXTURES, ROOT

import peaks
import spec
from loop import Window
from test_bench_readers import full  # noqa: F401  (the fixture)
from tokenizer import HashTokenizer
from view import RunView

dense = spec.arch("dense")

with open(os.path.join(ROOT, "bench", "configs", "phi3-mini.json")) as _f:
    PHI = json.load(_f)
PREFILL, DECODE = [17, 128, 40], [21, 300]


def _tiny(**extra):
    with open(os.path.join(FIXTURES, "tiny.json")) as f:
        return {**json.load(f), **extra}


# -- the dense module: the numbers of the code it replaced --------------------
def test_dense_counts_are_the_old_counts():
    assert dense.prefill_flops(PHI, PREFILL, logits_per_seq=0) == 1344464093184.0
    assert dense.prefill_flops(PHI, PREFILL, logits_per_seq=1) == 1345055096832.0
    assert dense.decode_flops(PHI, DECODE) == 15015739392.0


def test_dense_attention_calls_are_one_per_layer_as_before():
    assert dense.prefill_attention_calls(PHI, PREFILL) == [(113405952.0, 4546560.0)] * 32
    assert dense.decode_attention_calls(PHI, DECODE) == [(3944448.0, 3969024.0)] * 32


@pytest.fixture(scope="module")
def tiny_weights():
    import system
    from repro.models import get_model

    cfg = _tiny()
    return cfg, system.make_weights(get_model(dense.model_config(cfg)), 0)


@pytest.mark.parametrize("fn,fp8", [("embed", False), ("embed", True),
                                    ("forward", False), ("forward", True)],
                         ids=["embed", "embed_fp8", "forward", "forward_fp8"])
def test_dense_reference_is_the_old_reference_to_the_ulp(tiny_weights, fn, fp8):
    cfg, params = tiny_weights
    toks = [(7 * i + 3) % 512 for i in range(40)]
    if fn == "embed":
        got = dense.embed(params, cfg, toks[:20], 32, fp8=fp8)
    else:
        got = dense.forward(params, cfg, toks, 35, 4, fp8=fp8)
    want = np.load(os.path.join(FIXTURES, "dense_reference.npz"))[fn + ("_fp8" if fp8 else "")]
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name,value", [
    ("encoder_mfu.backfill", 9.967106598984772e-07),
    ("flash_attention_roofline.backfill", 0.1279302779984908),
    ("lm_mfu.agent", 2.040722842639594e-06),
    ("decode_attention_roofline.agent", 1.1481438767795589)])
def test_readers_that_count_work_read_the_old_values(full, name, value):  # noqa: F811
    assert full.arch is dense
    assert spec.reader(name).read(full) == value


# -- an explicit head_dim -------------------------------------------------------
def test_an_explicit_head_dim_is_honoured_by_builder_reference_and_counts():
    import jax.numpy as jnp

    import system
    from repro.core.encoder import ModelEncoder
    from repro.data.tokenizer import HashTokenizer as ProgramTokenizer
    from repro.models import get_model

    cfg = _tiny(head_dim=24)               # hidden_size / heads would be 16
    H, Hkv, L = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["num_hidden_layers"])
    mc = dense.model_config(cfg)
    assert mc.head_dim == 24
    model = get_model(mc)
    params = system.make_weights(model, 3)
    attn = params["layers"]["attn"]
    assert attn["wq"].shape[-1] == H * 24 and attn["wk"].shape[-1] == Hkv * 24

    # counts: two flops per weight of a layer's matrices, per token, and
    # attention at the builder's head width
    matrices = [x for x in (list(attn.values()) + list(params["layers"]["mlp"].values()))
                if x.ndim == 3]
    per_token = sum(2 * x[0].size for x in matrices)
    n = 10
    f, b = dense.prefill_attention_calls(cfg, [n])[0]
    assert (f, b) == (4 * H * 24 * n * (n + 1) / 2, 2 * n * 24 * (2 * H + 2 * Hkv))
    assert dense.prefill_flops(cfg, [n], logits_per_seq=0) == L * (per_token * n + f)
    f, _ = dense.decode_attention_calls(cfg, [n])[0]
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    assert dense.decode_flops(cfg, [n]) == L * (per_token + f) + head

    # reference against the program: the LM's last logits and the encoder
    toks = [(11 * i + 5) % cfg["vocab_size"] for i in range(24)]
    served, _ = model.prefill(params, {"tokens": jnp.asarray([toks], jnp.int32)}, 32)
    served = np.asarray(served, np.float32)[0]
    ref = dense.forward(params, cfg, toks, len(toks) - 1, 1)[0]
    assert ref.max() - ref[int(np.argmax(served))] <= cfg["limits"]["lm_gap"]
    assert np.corrcoef(served, ref)[0, 1] > 0.99
    width = cfg["serve"]["encoder_max_tokens"]
    text = "[user] Bob moved from Boston to Miami in May 2021."
    enc = ModelEncoder(mc, params, ProgramTokenizer(cfg["vocab_size"]), max_len=width)
    emb = np.asarray(enc.encode([text])[0], np.float64)
    ids = HashTokenizer(cfg["vocab_size"]).encode(text)[:width]
    assert np.linalg.norm(emb - dense.embed(params, cfg, ids, width)) <= cfg["limits"]["encoder_gap"]


# -- an architecture added by files alone --------------------------------------
TOY = '''"""A toy architecture: the dense decoder, with its forward counted twice."""
import os

import spec

_dense = spec.arch("dense", os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
model_config, forward, embed = _dense.model_config, _dense.forward, _dense.embed
decode_flops = _dense.decode_flops
prefill_attention_calls = _dense.prefill_attention_calls
decode_attention_calls = _dense.decode_attention_calls


def prefill_flops(cfg, lengths, *, logits_per_seq):
    return 2 * _dense.prefill_flops(cfg, lengths, logits_per_seq=logits_per_seq)
'''


def _add_config(root, name: str, cfg: dict, bench: dict):
    """A configuration and its backfill cell, as a new file and entries."""
    (root / "bench" / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    with open(os.path.join(FIXTURES, "tiny-backfill.json")) as f:
        mix = json.load(f)
    (root / "bench" / "traffic" / f"{mix['name']}.json").write_text(json.dumps(mix))
    cell = f"{name}.backfill"
    bench["configs"].append({"name": name, "source": "bench/tests/fixtures/tiny.json",
                             "file": f"bench/configs/{name}.json", "reduced": [],
                             "why": "a CPU-sized decoder"})
    bench["workloads"].append({"name": cell, "config": name, "traffic": mix["name"],
                               "chips": 1, "why": "backfill at a tiny size"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "phi3-mini.backfill" in m.get("workloads", []):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell


@pytest.fixture
def checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    return root


def test_an_architecture_added_as_files_only_runs(checkout):
    import run

    root = checkout
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    (root / "bench" / "arch" / "toy.py").write_text(TOY)
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell_name = _add_config(root, "tiny-toy", _tiny(name="tiny-toy", arch="toy"), bench)

    cell = spec.resolve(cell_name, root=str(root))
    assert cell.arch is spec.arch("toy", root=str(root))
    assert cell.arch.__file__ == str(root / "bench" / "arch" / "toy.py")
    assert cell.arch is not dense
    # its counts are the ones that the readers use
    text = "[user] Bob moved from Boston to Miami in May 2021."
    views = [RunView(cell.config, Window(close=1.0), setup_s=0.0, encoder_calls=[[text]],
                     peaks=peaks.peaks("TPU v5 lite"), arch=a) for a in (cell.arch, dense)]
    mfu = spec.reader("encoder_mfu.backfill", root=str(root))
    assert mfu.read(views[0]) == pytest.approx(2 * mfu.read(views[1]), rel=1e-12)

    res = run.run_cell(cell, 2**32 + 21, 1.5, False, t_start=time.perf_counter(),
                       cache=False, control=True)
    assert res["correct"], res["checks"]
    limit = cell.config["limits"]["encoder_gap"]
    assert res["checks"]["encoder_gap"]["value"] <= limit
    assert res["control_checks"]["encoder_gap"]["value"] > limit, res["control_checks"]
    for p, data in before.items():        # no file that was there has changed
        assert p.read_bytes() == data


def test_a_configuration_without_its_architecture_is_refused(checkout):
    root = checkout
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell = _add_config(root, "tiny-none", _tiny(name="tiny-none", arch="nosuch"), bench)
    with pytest.raises(FileNotFoundError) as e:
        spec.resolve(cell, root=str(root))
    assert str(root / "bench" / "arch" / "nosuch.py") in str(e.value)

    cfg = _tiny(name="tiny-none")
    del cfg["arch"]
    (root / "bench" / "configs" / "tiny-none.json").write_text(json.dumps(cfg))
    with pytest.raises(KeyError) as e:
        spec.resolve(cell, root=str(root))
    assert "bench/configs/tiny-none.json" in str(e.value)


@pytest.mark.parametrize("cell", [w["name"] for w in spec.load_benchmark()["workloads"]])
def test_every_cell_has_an_architecture_with_the_whole_interface(cell):
    c = spec.resolve(cell)
    assert c.arch is spec.arch(c.config["arch"])
    for fn in ("model_config", "forward", "embed", "prefill_flops", "decode_flops",
               "prefill_attention_calls", "decode_attention_calls"):
        assert callable(getattr(c.arch, fn)), fn
