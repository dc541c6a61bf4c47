"""Seconds from process start to the first timed call: imports, device
start-up, compilation or compile-cache reads, and warm-up."""


def read(run):
    return run.setup_s
