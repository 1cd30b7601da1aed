"""Milliseconds per round in the round engine's structural work: the
fenced ``retry`` (deferred inserts, with the split waves they cause),
``rebalance`` (underfull waves, root shrink) and any wave or shrink span
outside them, a nested span counted once."""

SPANS = ("retry", "split_wave", "underfull_wave", "rebalance", "root_shrink")


def read(run):
    return run.per_round_ms(SPANS)
