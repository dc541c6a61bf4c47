"""Host ms per sweep in the program's ``sweep.labels`` span: gathering
the label columns of a sweep's first table slice, done while the device
computes the columns (inside ``sweep.columns``)."""
from chipbench import recorder

TIMED = recorder.ARM


def read(run):
    return recorder.ms_per_sweep(run, "sweep.labels")
