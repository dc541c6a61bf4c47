"""Host ms per sweep in the program's ``sweep.columns.call`` span:
flattening the kernel's arguments, placing every host array on the
device, and the launch."""
from chipbench import recorder

TIMED = recorder.ARM


def read(run):
    return recorder.ms_per_sweep(run, "sweep.columns.call")
