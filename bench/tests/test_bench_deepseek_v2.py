"""The DeepSeek-V2 architecture (``bench/arch/deepseek_v2.py``) in the
harness: a tiny cell of it runs on the CPU and is correct while its float8
control is not; its counts are the model's parameter shapes."""
import json
import os
import shutil
import time

import pytest

from _paths import BENCH, FIXTURES, ROOT

import spec
from test_bench_arch import _add_config

ds = spec.arch("deepseek_v2")


def _tiny(**extra):
    with open(os.path.join(FIXTURES, "tiny-deepseek-v2.json")) as f:
        return {**json.load(f), **extra}


@pytest.fixture(scope="module")
def tiny_weights():
    import system
    from repro.models import get_model

    cfg = _tiny()
    return cfg, system.make_weights(get_model(ds.model_config(cfg)), 5)


def test_a_tiny_cell_runs_correct_and_its_control_does_not(tmp_path):
    import run

    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    name = _add_config(root, "tiny-deepseek-v2", _tiny(), bench)
    cell = spec.resolve(name, root=str(root))
    assert cell.arch.model_config(cell.config).experts_held == 4
    res = run.run_cell(cell, 2**33 + 7, 1.5, False, t_start=time.perf_counter(),
                       cache=False, control=True)
    assert res["correct"], res["checks"]
    limit = cell.config["limits"]["encoder_gap"]
    assert res["checks"]["encoder_gap"]["value"] <= limit
    assert res["control_checks"]["encoder_gap"]["value"] > limit, res["control_checks"]


def test_counts_are_the_models_parameter_shapes(tiny_weights):
    cfg, params = tiny_weights
    n_dense, n_moe = cfg["first_k_dense_replace"], cfg["num_hidden_layers"] - 1

    def per_token(tree):                # two flops per weight of each matrix
        return sum(2 * x[0].size for x in tree.values() if x.ndim == 3)

    attn = per_token(params["layers"]["attn"])
    assert attn == per_token(params["dense_layers"]["attn"])
    dense = per_token(params["dense_layers"]["mlp"])
    moe = params["layers"]["moe"]
    held, E, K = moe["we_gate"].shape[1], moe["router"].shape[-1], cfg["num_experts_per_tok"]
    assert (held, E) == (4, 16)
    one_expert = sum(2 * moe[k][0, 0].size for k in ("we_gate", "we_up", "we_down"))
    rows = K * held / E
    expert_layer = per_token(moe["shared"]) + 2 * moe["router"][0].size + rows * one_expert
    n = 10
    (att, _), = set(ds.prefill_attention_calls(cfg, [n]))
    H = cfg["num_attention_heads"]
    qk, v = params["layers"]["attn"]["wq"].shape[-1] // H, params["layers"]["attn"]["wo"].shape[1] // H
    assert att == 2 * H * (qk + v) * n * (n + 1) / 2
    assert ds.prefill_flops(cfg, [n], logits_per_seq=0) == pytest.approx(
        (n_dense + n_moe) * (attn * n + att) + n * (n_dense * dense + n_moe * expert_layer),
        rel=1e-12)
    head = 2 * params["unembed"].size
    assert ds.prefill_flops(cfg, [n], logits_per_seq=1) - ds.prefill_flops(
        cfg, [n], logits_per_seq=0) == pytest.approx(head, rel=1e-12)

    r = 37.0
    calls = ds.expert_matmul_calls(cfg, r)
    assert sum(f for f, _ in calls) == pytest.approx(r * one_expert, rel=1e-12)
    weights = sum(moe[k][0].size for k in ("we_gate", "we_up", "we_down"))
    d, f = moe["we_gate"].shape[2:]
    assert sum(b for _, b in calls) == pytest.approx(
        2 * (weights + r * (2 * d + f) + r * (f + f + d)), rel=1e-12)
    assert ds.expert_layers(cfg) == moe["router"].shape[0] == n_moe
