"""Device ms per sweep in the XLA module of the sweep kernel
(``jit__columns_jax``), summed from the profiler trace of the fullest
device."""

MODULE = "jit__columns_jax"


def read(run):
    dev = run.fullest_device()
    done = [r for r in run.records if r.error is None]
    if dev is None or not done:
        return None
    t = dev["modules"].get(MODULE)
    return 1e3 * t / len(done) if t else None
