"""Engine step time: the window's seconds over the ServeEngine.step calls
made in it."""
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "serve loop"
MOVES = "token_gap_p95_ms"


def read(run):
    steps = len(run.window.step_ends)
    if not steps or run.window_s <= 0:
        return None
    return 1000.0 * run.window_s / steps
