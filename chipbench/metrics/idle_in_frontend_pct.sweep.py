"""Share of the traced window, in %, in which the fullest device is idle
while a ``sweep`` span is open but its ``sweep.columns`` span is not:
the front end (structure build, label gathers)."""
from chipbench import recorder

TIMED = recorder.ARM


def read(run):
    whole = recorder.idle_pct(run, "sweep")
    columns = recorder.idle_pct(run, "sweep.columns")
    if whole is None or columns is None:
        return None
    return whole - columns
