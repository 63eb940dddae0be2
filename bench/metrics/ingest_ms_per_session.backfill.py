"""Ingest drain time per session: total engine.drain.ingest span time over
the sessions those drains took."""
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "memory facade / ingest"
MOVES = "ingest_tokens_per_s"


def read(run):
    durs = run.span_durations("engine.drain.ingest")
    n = run.span_attr_sum("engine.drain.ingest", "sessions")
    if not durs or not n:
        return None
    return 1000.0 * sum(durs) / n
