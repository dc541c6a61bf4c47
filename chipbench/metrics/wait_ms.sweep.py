"""Host ms per sweep in the program's ``sweep.columns.wait`` span: the
device work (transfers and kernel) that the host waits for after the
launch."""
from chipbench import recorder

TIMED = recorder.ARM


def read(run):
    return recorder.ms_per_sweep(run, "sweep.columns.wait")
