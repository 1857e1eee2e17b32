"""Model step (train): device self time of the ops under the program's
``attn/core`` scope (scores, softmax and P.V, with the flash path's custom
VJP), in every phase, over the train steps' program time, summed over the
chips, in %. Moves train_tok_s."""
from bench.harness.scopes import attribute, in_scope

CORE = in_scope("attn/core")


def read(run):
    att = attribute(run)
    return att.share(CORE) if att is not None and att.has(CORE) else None
