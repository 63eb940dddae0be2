"""Mean engine.drain.query span: one drain of the query lane (encode,
forest recall, browse, answer) as the program times it."""
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "retrieval"
MOVES = "query_p95_ms"


def read(run):
    durs = run.span_durations("engine.drain.query")
    if not durs:
        return None
    return 1000.0 * sum(durs) / len(durs)
