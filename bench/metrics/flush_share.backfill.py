"""Share of the window spent in forest.flush spans (summary refresh)."""
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "index / flush"
MOVES = "ingest_tokens_per_s"


def read(run):
    durs = run.span_durations("forest.flush")
    if not durs or run.window_s <= 0:
        return None
    return 100.0 * sum(durs) / run.window_s
