"""95th percentile over every gap between consecutive output tokens of every
LM request in the window. A token's time is the end of the engine step that
appended it; a request still decoding at the close adds its open gap."""
from stats import percentile

UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    gaps = run.token_gaps()
    return 1000.0 * percentile(gaps, 95) if gaps else None
