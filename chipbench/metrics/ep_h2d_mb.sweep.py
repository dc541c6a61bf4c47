"""MB (1e6 bytes) of expert-parallel tables and codes placed on the
device per sweep by the kernel call, from the program's counter
``sweep.ep_h2d_bytes``."""
from chipbench import recorder

TIMED = recorder.ARM


def read(run):
    b = recorder.per_sweep(run, "sweep.ep_h2d_bytes")
    return None if b is None else b / 1e6
