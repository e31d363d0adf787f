"""Share of the children of split nodes that the sparse-frontier cap cut
to leaves in the window's trees: the counters ``frontier_cut`` over
``frontier_split_children`` of the ``train.block.pull`` spans of the
window's job (tree engine: models/tree/jit_engine.py
``build_tree_frontier`` counts them in the program, the tree driver puts them
on the span).  Read by benchmark/spans.py from the program's
``TimeLine`` ring; a program whose spans carry no such counters leaves
the metric out."""

from benchmark import spans

UNIT, LAYER, MOVES, SOURCE = "%", "tree engine", "train_rate", \
    "program_counter"


def read(ctx, events=None):
    pulls = [e for e in spans.window_spans(events)
             if (e["kind"], e["what"]) == ("train", "block.pull")
             and "frontier_split_children" in e]
    total = sum(e["frontier_split_children"] for e in pulls)
    if not total:
        return None
    return 100.0 * sum(e["frontier_cut"] for e in pulls) / total
