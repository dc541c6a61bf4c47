"""Host ms per sweep in the program's ``sweep.build`` span: building the
grid's structure for the jax kernel on a structure-memo miss."""
from chipbench import recorder

TIMED = recorder.ARM


def read(run):
    return recorder.ms_per_sweep(run, "sweep.build")
