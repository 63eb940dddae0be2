"""flash_attention's share of its roofline: the least time that the causal
attention over the real tokens of every encoder call in the window needs
(each layer, max of flops at peak and bytes at peak bandwidth), over the
kernel's time in the device trace. Bandwidth bounds it at these lengths."""
import flops

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "ingest_tokens_per_s"


def read(run):
    layers = run.cfg["num_hidden_layers"]
    calls = []
    for lengths in run.encoder_lengths():
        f, b = flops.causal_attention(lengths, **run.heads())
        calls += [(f, b)] * layers
    return run.roofline("flash_attention", calls) if calls else None
