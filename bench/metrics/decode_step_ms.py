"""Engine decode: harness-clock time inside ``step`` calls made in the
window, over the number of calls. Moves itl_p95_ms."""


def read(run):
    calls = run.window_calls("step")
    if not calls:
        return None
    return 1e3 * sum(c.t1 - c.t0 for c in calls) / len(calls)
