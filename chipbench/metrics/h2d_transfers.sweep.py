"""Host arrays placed on the device per sweep by the kernel call, from
the program's counter ``sweep.h2d_arrays``."""
from chipbench import recorder

TIMED = recorder.ARM


def read(run):
    return recorder.per_sweep(run, "sweep.h2d_arrays")
