"""95th percentile over every turn write due in the window, from its due
time to its acknowledgement (the ingest batch that journaled, fsynced and
applied it has returned); one not acknowledged at the close counts at its
age."""
from stats import percentile

UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    lat = run.latencies("write")
    return 1000.0 * percentile(lat, 95) if lat else None
