"""Trace reduction, operation counts (``flops.py`` and the dense
architecture's) and the peak table."""
import os

import pytest

from _paths import FIXTURES

import flops
import peaks
import spec
import trace_reduce

dense = spec.arch("dense")

PROBE = os.path.join(FIXTURES, "probe.xplane.pb")


@pytest.fixture(scope="module")
def probe():
    # one v5e chip: flash_attention (2x256x4x128, 2 KV heads),
    # decode_attention (2x1024x2x128), topk_sim and a matmul, each run twice
    # under a "bench.window" annotation; the first window is the one read
    return trace_reduce.load(PROBE)


def test_window_is_the_first_annotation(probe):
    assert probe.window == (45548336.0, 45548336.0 + 1746619.0)
    assert probe.window_s == pytest.approx(1.746619e-3)


def test_device_clock_is_moved_onto_the_host_clock(probe):
    # enqueue (host) precedes start (device) and completion (host) follows
    # the end: the offset lies between 1.244 and 1.356 ms in this trace
    first = min(o.start_ns for o in probe.ops["/device:TPU:0"])
    assert 44638720.0 + 1.244e6 <= first <= 44638720.0 + 1.356e6


def test_kernel_time(probe):
    # one call of each in the first window
    assert trace_reduce.kernel_s(probe, "flash_attention") == pytest.approx(9529e-9)
    assert trace_reduce.kernel_s(probe, "decode_attention") == pytest.approx(2668e-9)


def test_busy_and_idle(probe):
    busy = trace_reduce.busy_s(probe)
    # the five programs of the first window run 44.8 us in all
    assert 30e-6 < busy <= 44.848e-6
    assert 0.95 < 1 - busy / probe.window_s < 1


def test_breakdown(probe):
    ops = trace_reduce.top_ops(probe)
    assert 0 < len(ops) <= 10
    assert ["jit_attention/attention", pytest.approx(9529e-9)] in ops
    assert [o[1] for o in ops] == sorted((o[1] for o in ops), reverse=True)
    gaps = trace_reduce.idle_gaps(probe, [("engine.step", probe.window[0],
                                           probe.window[1])])
    assert gaps[0][0] == "engine.step"
    assert gaps[0][1] == pytest.approx(probe.window_s - trace_reduce.busy_s(probe))
    untagged = trace_reduce.idle_gaps(probe)
    assert sum(g[1] for g in untagged) == pytest.approx(gaps[0][1])


def test_kernel_signature_when_named_otherwise():
    flash = trace_reduce.Op(0, 1, "%custom-call.3 = bf16[8,32,1024,96]{3,2,1,0} custom-call("
                            "bf16[8,32,1024,96]{3,2,1,0} %a, bf16[8,32,1024,96]{3,2,1,0} %b, "
                            "bf16[8,32,1024,96]{3,2,1,0} %c), custom_call_target=\"tpu_custom_call\"")
    dec = trace_reduce.Op(0, 1, "%custom-call.4 = bf16[8,8,4,128]{3,2,1,0} custom-call("
                          "s32[8]{0} %l, bf16[8,8,4,128]{3,2,1,0} %q, bf16[8,8,4096,128]{3,2,1,0} %k, "
                          "bf16[8,8,4096,128]{3,2,1,0} %v), custom_call_target=\"tpu_custom_call\"")
    assert trace_reduce.KERNELS["flash_attention"](flash)
    assert not trace_reduce.KERNELS["decode_attention"](flash)
    assert trace_reduce.KERNELS["decode_attention"](dec)
    assert not trace_reduce.KERNELS["flash_attention"](dec)


@pytest.mark.parametrize("name,heads,kv,hd", [("phi3-mini", 32, 32, 96),
                                             ("granite-3-8b-20L", 32, 8, 128)])
def test_attention_counts_at_both_configurations(name, heads, kv, hd):
    n, layers = 1024, 20
    cfg = {"hidden_size": heads * hd, "num_attention_heads": heads,
           "num_key_value_heads": kv, "num_hidden_layers": layers}
    calls = dense.prefill_attention_calls(cfg, [n])
    assert len(calls) == layers and len(set(calls)) == 1
    f, b = calls[0]
    assert f == 4 * heads * hd * n * (n + 1) / 2
    assert b == 2 * n * hd * (2 * heads + 2 * kv)
    calls = dense.decode_attention_calls(cfg, [n, 10])
    assert len(calls) == layers and len(set(calls)) == 1
    f, b = calls[0]
    assert f == 4 * heads * hd * (n + 10)
    assert b == 2 * (2 * (n + 10) * kv * hd + 2 * 2 * heads * hd)
    # both are bandwidth-bound on a v5e: far under its ridge of ~240 flop/B
    pk = peaks.peaks("TPU v5 lite")
    assert flops.roofline_seconds(f, b, pk)[1] == "bandwidth"


def test_phi3_forward_flops_per_token():
    cfg = {"hidden_size": 3072, "num_attention_heads": 32, "num_key_value_heads": 32,
           "intermediate_size": 8192, "num_hidden_layers": 32, "vocab_size": 32064}
    per_layer = 2 * 3072 * 3 * 3072 + 2 * 3072 * 3072 + 6 * 3072 * 8192
    trunk = dense.prefill_flops(cfg, [1], logits_per_seq=0)
    assert trunk == 32 * (per_layer + 4 * 3072)
    one = dense.prefill_flops(cfg, [1], logits_per_seq=1)
    assert one == 32 * (per_layer + 4 * 3072) + 2 * 3072 * 32064


def test_peak_table_refuses_an_unknown_kind():
    assert peaks.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
