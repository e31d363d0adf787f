"""Host seconds of the window's ``train.bin.quantile`` spans: the split
points computed where the rows live (a local sort a shard, then a search
of O(nbins x columns) counts a round reduced over the chips).  Read by
benchmark/spans.py; left out where the program has no such span."""

from benchmark import spans

UNIT, LAYER, MOVES, SOURCE = "s", "tree driver", "train_rate", "host_clock"


def read(ctx):
    return spans.seconds(spans.window_spans(), "train", "bin.quantile")
