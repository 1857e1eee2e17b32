"""Engine admission: harness-clock time inside ``admit_many`` calls made
in the window, over the number of calls. Moves ttft_p50_ms."""


def read(run):
    calls = run.window_calls("admit")
    if not calls:
        return None
    return 1e3 * sum(c.t1 - c.t0 for c in calls) / len(calls)
