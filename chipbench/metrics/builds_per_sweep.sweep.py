"""Grid-structure builds per sweep, from the program's counter
``sweep.builds`` (1 when the structure memo misses every sweep)."""
from chipbench import recorder

TIMED = recorder.ARM


def read(run):
    return recorder.per_sweep(run, "sweep.builds")
