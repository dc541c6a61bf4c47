"""Share of the traced window in which no XLA op ran on the fullest
device, in %: 100 x (1 - busy union / window)."""


def read(run):
    dev = run.fullest_device()
    if dev is None or not run.trace_window_s:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / run.trace_window_s)
