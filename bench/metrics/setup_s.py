"""Set-up: process start to the window's open. Loading, weights, the
history preload the mix needs, compiles or compile-cache loads, warm-up."""
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    return run.setup_s
