"""{"kind": "window_fact", "fact": "set.median_epoch_rate"}: one of
the facts the training path keeps of its window (``paths/train.py:
window_facts``: what the measured set of epochs read, the stalled
epochs), by dotted name; {"...", "count": true} the length of a list
fact. None where the window kept no such fact: a mix that names no
``measure_epochs``, a set the window did not complete."""
from benchmarks import harness


def read(source, ctx):
    try:
        fact = harness.lookup(ctx.get("window"), source["fact"])
    except (KeyError, TypeError):
        return None
    if fact is None:
        return None
    return len(fact) if source.get("count") else fact
