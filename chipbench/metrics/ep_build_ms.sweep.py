"""Host ms per sweep in the program's ``sweep.build.ep`` span: building
the expert-parallel tables (one row per workload and EP group size)
inside the grid's structure build."""
from chipbench import recorder

TIMED = recorder.ARM


def read(run):
    return recorder.ms_per_sweep(run, "sweep.build.ep")
