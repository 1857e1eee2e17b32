"""Sharding: device time of the train steps' collectives (all-gathers,
reduce-scatters, all-reduces, permutes, and the waits of their async
forms) during which nothing else runs on that chip, over the steps'
device time, summed over the chips, in %. Moves train_tok_s."""
from bench.harness.readers import exposed_collective_share


def read(run):
    return exposed_collective_share(run, "bench.train")
