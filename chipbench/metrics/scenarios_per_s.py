"""Scenarios returned by ``sweep()`` per second, over every sweep of the
window and the whole window."""


def read(run):
    done = [r for r in run.records if r.error is None]
    return sum(r.request.size for r in done) / run.window_s if done else None
