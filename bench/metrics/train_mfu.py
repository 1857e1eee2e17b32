"""Model step (train): model FLOPs of the train steps in the traced
window (``bench/counts/train_step.py``) over their programs' device time
on every chip at the chip's bf16 peak, in %. Recomputed work does not
count. Moves train_tok_s."""
from bench.harness.readers import step_mfu


def read(run):
    return step_mfu(run, "bench.train")
