"""Model step (prefill): model FLOPs of the admission prefills in the
traced window, over each prompt's true length (not its bucket), over the
admission programs' device time at the chip's bf16 peak, in %.
Moves ttft_p50_ms."""
from bench.harness.readers import call_mfu, load_count



def read(run):
    return call_mfu(run, "bench.admit",
                    load_count("dense_decoder").prefill_flops)
