"""decode_attention's share of its roofline: the least time that one-token
attention over the caches of every lane that decoded in the window needs
(each layer, each step), over the kernel's time in the device trace.
Reading the cache bounds it."""
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "token_gap_p95_ms"


def read(run):
    calls = []
    for lengths in run.window.decode_lengths:
        if lengths:
            calls += run.arch.decode_attention_calls(run.cfg, lengths)
    return run.roofline("decode_attention", calls) if calls else None
