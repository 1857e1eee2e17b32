"""Control plane: mean wait of an item in the syncer's fair queue, from
the UsageMeter's queue_wait_s over queue_items, summed over the
control-plane tenants across the window (metering is on in the traced
run only). Moves propagation_p90_ms."""


def read(run):
    if not run.meter:
        return None
    items = sum(v["queue_items"] for v in run.meter.values())
    wait = sum(v["queue_wait_s"] for v in run.meter.values())
    return 1e3 * wait / items if items > 0 else None
