"""Model step (train): device self time of the ops under the program's
``loss`` scope (the logits head and the chunked cross entropy), in every
phase, over the train steps' program time, summed over the chips, in %.
Moves train_tok_s."""
from bench.harness.scopes import attribute, in_scope

LOSS = in_scope("loss")


def read(run):
    att = attribute(run)
    return att.share(LOSS) if att is not None and att.has(LOSS) else None
