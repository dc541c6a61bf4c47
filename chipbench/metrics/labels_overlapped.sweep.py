"""Label gathers that ran while the kernel was in flight, per sweep,
from the program's counter ``sweep.labels_overlapped`` (1 when every
sweep gathers its labels under its kernel)."""
from chipbench import recorder

TIMED = recorder.ARM


def read(run):
    return recorder.per_sweep(run, "sweep.labels_overlapped")
