"""Slot scheduler: 90th percentile of the time from a request's due time
to the moment the engine proxy received it in ``admit_many``, over every
request due in the window (one never admitted enters at the drain
deadline). Moves ttft_p50_ms."""
from bench.harness.common import percentile



def read(run):
    waits = [((q["received"] if q["received"] is not None else run.deadline)
              - q["due"]) * 1e3 for q in run.requests]
    return percentile(waits, 90) if waits else None
