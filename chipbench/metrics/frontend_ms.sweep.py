"""Host ms per sweep outside the device path: ``sweep()`` wall time less
the spans of ``JaxGridEvaluator.columns`` (grid validation, structure
memo, label gathers)."""

TIMED = ("repro.core.batched_jax:JaxGridEvaluator.columns",)


def read(run):
    spans = run.timers.spans.get(TIMED[0])
    done = [r for r in run.records if r.error is None]
    if spans is None or not done:
        return None
    return 1e3 * (sum(r.latency_s for r in done) - sum(spans)) / len(done)
