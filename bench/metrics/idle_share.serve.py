"""Device: 1 - (union of device op intervals / traced window), in %, for
serving cells. Moves itl_p95_ms."""
from bench.harness.readers import idle_share



def read(run):
    return idle_share(run)
