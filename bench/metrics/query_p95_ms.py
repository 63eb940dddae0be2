"""95th percentile over every query due in the window, from its due time to
its answer; one unanswered at the close counts at its age."""
from stats import percentile

UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    lat = run.latencies("query")
    return 1000.0 * percentile(lat, 95) if lat else None
