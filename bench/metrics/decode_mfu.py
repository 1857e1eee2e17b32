"""Model step (decode): model FLOPs of the decode steps in the traced
window, counted for the slots that held a request and over their live
lengths, over the steps' device time at the chip's bf16 peak, in %.
Moves itl_p95_ms."""
from bench.harness.readers import call_mfu, load_count



def read(run):
    return call_mfu(run, "bench.step", load_count("dense_decoder").decode_flops)
