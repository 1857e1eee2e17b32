"""Kernel: the decode attention kernel's share of its roofline, from the
counts in bench/counts/flash_decode.py at the slots' true lengths, over
the summed device time of its events in the traced window, in %.
Moves itl_p95_ms."""
from bench.harness.readers import roofline_share



def read(run):
    return roofline_share(run, "flash_decode")
