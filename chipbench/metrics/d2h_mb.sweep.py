"""MB (1e6 bytes) brought to the host per sweep by the fetch of the
kernel's result, from the program's counter ``sweep.d2h_bytes``."""
from chipbench import recorder

TIMED = recorder.ARM


def read(run):
    b = recorder.per_sweep(run, "sweep.d2h_bytes")
    return None if b is None else b / 1e6
