"""Device: 1 - (union of device op intervals / traced window), in %,
averaged over the chips of a training cell. Moves train_tok_s."""
from bench.harness.readers import idle_share


def read(run):
    return idle_share(run)
