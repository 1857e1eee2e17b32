"""Kernel (prefill): the prefill attention kernel's share of its
roofline, from the counts in bench/counts/flash_attention.py at the
prompts' true lengths, over the summed device time of its events in the
traced window, in %. Moves ttft_p50_ms."""
from bench.harness.readers import roofline_share



def read(run):
    return roofline_share(run, "flash_attention")
