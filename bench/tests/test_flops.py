"""The model-FLOP counter against a hand count on one 3-node graph."""
import numpy as np

import flops


def test_three_node_chain_by_hand():
    # stage 0 -> stage 1, and a summary node 2 -> stage 0
    adj = np.zeros((4, 4), bool)
    adj[1, 0] = adj[0, 2] = True
    mask = np.array([True, True, True, False])
    summ = np.array([False, False, True, False])
    # per edge: f3 60->32->16, attention dot 16, f4 edge half 16->32
    per_edge = 2 * (60 * 32 + 32 * 16) + 2 * 16 + 2 * 16 * 32
    # per edge and round: f4 metric half 5->32, f4 second layer 32->5, and
    # the weighted message 5
    per_round = 2 * 5 * 32 + 2 * 32 * 5 + 2 * 5
    # per stage node: f1 36->32->1, f2 33->32->1
    per_node = 2 * (36 * 32 + 32) + 2 * (33 * 32 + 32)
    edges, depth, nodes = 2, 2, 2
    want = edges * per_edge + depth * edges * per_round + nodes * per_node
    assert flops.graph_flops(adj, mask, summ) == want == 23528


def test_masked_parts_do_not_count():
    adj = np.zeros((8, 8), bool)
    adj[1, 0] = adj[5, 4] = True
    mask = np.zeros(8, bool)
    mask[:2] = True
    summ = np.zeros(8, bool)
    alone = flops.graph_flops(adj[:2, :2], mask[:2], summ[:2])
    assert flops.graph_flops(adj, mask, summ) == alone
