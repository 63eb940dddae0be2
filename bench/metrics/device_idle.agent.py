"""Share of the traced window in which no operation ran on the device
(1 minus the union of the operations' intervals, over the window)."""
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "token_gap_p95_ms"


def read(run):
    from trace_reduce import busy_s

    if run.trace is None:
        return None
    return 100.0 * (1.0 - busy_s(run.trace) / run.trace.window_s)
