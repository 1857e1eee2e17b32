"""Model step (train): device self time of the ops remat runs a second
time (op_names under ``rematted_computation``, collectives included) over
the train steps' program time, summed over the chips, in %. Moves
train_tok_s."""
from bench.harness.scopes import attribute, remat


def read(run):
    att = attribute(run)
    return att.share(remat) if att is not None and att.has(remat) else None
