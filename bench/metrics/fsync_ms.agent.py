"""Mean journal.fsync span: one fsync of the write-ahead journal."""
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "durability"
MOVES = "write_ack_p95_ms"


def read(run):
    durs = run.span_durations("journal.fsync")
    if not durs:
        return None
    return 1000.0 * sum(durs) / len(durs)
