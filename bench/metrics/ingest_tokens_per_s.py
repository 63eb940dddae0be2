"""Memory-construction throughput: history tokens (the configuration's
tokenizer) of the sessions acknowledged in the window, over the window.
Acknowledged: journaled with fsync, applied, flushed, and returned."""
UNIT = "tokens/s"
BETTER = "higher"
SOURCE = "host_clock"


def read(run):
    if not run.window.sessions or run.window_s <= 0:
        return None
    return run.history_tokens() / run.window_s
