"""The encoder's share of the chip's peak: forward flops of the real
(unpadded) tokens of every text the encoder embedded in the window, causal
attention included, over the window at peak bf16 FLOP/s."""
import flops

UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "encoder"
MOVES = "ingest_tokens_per_s"


def read(run):
    lengths = [n for call in run.encoder_lengths() for n in call]
    if not lengths:
        return None
    return run.peak_share(flops.model_flops(run.cfg, lengths, logits_per_seq=0))
