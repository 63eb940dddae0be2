"""The whole LM step's share of the chip's peak: forward flops of every
prompt prefilled and every token decoded in the window (one LM-head row a
prefill, causal attention over the real tokens), over the window at peak
bf16 FLOP/s."""
import flops

UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "LM"
MOVES = "token_gap_p95_ms"


def read(run):
    w = run.window
    if not w.prefill_lengths and not any(w.decode_lengths):
        return None
    total = flops.model_flops(run.cfg, w.prefill_lengths, logits_per_seq=1)
    total += sum(flops.decode_flops(run.cfg, lengths)
                 for lengths in w.decode_lengths if lengths)
    return run.peak_share(total)
