"""Host ms per sweep in the program's ``sweep.columns.fetch`` span: the
device-to-host copies of the numeric result columns."""
from chipbench import recorder

TIMED = recorder.ARM


def read(run):
    return recorder.ms_per_sweep(run, "sweep.columns.fetch")
