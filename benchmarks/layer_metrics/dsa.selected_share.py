"""Share of the causal query-key pairs that the selection kept, from the
step's own counters (median over the window's steps): ``sum_t min(t + 1,
topk)`` over ``S (S + 1) / 2`` — 0.7499 at 4,096 tokens and 2,048 keys a
query; 1.0 means the mechanism is off.  The core's operations follow it in
a core that skips what was not selected; in a masked-dense core nothing
does."""
from benchmarks.lib import trace_sparse_trunk

NAME = "dsa.selected_share"
LAYER = "train step"
UNIT = "ratio"
MOVES = "train_images_per_s_per_chip"
SOURCE = "program_counter"


def read(sources):
    kept = trace_sparse_trunk.pairs_a_pass(sources, "selected_pairs")
    causal = trace_sparse_trunk.pairs_a_pass(sources, "causal_pairs")
    if not kept or not causal:
        return None
    return kept / causal
