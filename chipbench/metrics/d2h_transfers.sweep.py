"""Device arrays brought to the host per sweep by the fetch of the kernel's
result, from the program's counter ``sweep.d2h_arrays``."""
from chipbench import recorder

TIMED = recorder.ARM


def read(run):
    return recorder.per_sweep(run, "sweep.d2h_arrays")
