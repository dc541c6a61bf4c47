"""Share of the traced window, in %, in which the fullest device is idle
while the program's ``sweep.columns`` span is open: copies and launch
around the kernel."""
from chipbench import recorder

TIMED = recorder.ARM


def read(run):
    return recorder.idle_pct(run, "sweep.columns")
