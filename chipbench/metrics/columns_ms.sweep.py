"""Host ms per sweep inside ``JaxGridEvaluator.columns``: host-to-device
copies, dispatch, the kernel and the device-to-host copy of the columns."""

TIMED = ("repro.core.batched_jax:JaxGridEvaluator.columns",)


def read(run):
    spans = run.timers.spans.get(TIMED[0])
    done = [r for r in run.records if r.error is None]
    if spans is None or not done:
        return None
    return 1e3 * sum(spans) / len(done)
