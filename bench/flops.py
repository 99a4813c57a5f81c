"""Operations the placement policy needs, counted from shapes.

A multiply-add counts as two operations.  Neighbour aggregation is counted
over the graph's edges (Â holds 2E off-diagonal entries and V self-loops),
not over a dense (V, V) matrix, so the count does not depend on how the
program stores the graph.  The placement head is counted over all V group
slots, the encoder's input MLP on a window's first step only.  Training
counts each sampled step's forward once and its backward as twice the
forward; the replay's recomputation of the forward does not count.
"""
from __future__ import annotations


def step_forward(num_nodes: int, num_edges: int, d_in: int, policy: dict,
                 *, first: bool) -> int:
    v, e, h = num_nodes, num_edges, policy["hidden_channel"]
    d = policy["num_devices"]
    ops = 0
    if first:
        sizes = [d_in] + [h] * policy["layer_trans"]
        ops += sum(2 * v * a * b for a, b in zip(sizes, sizes[1:]))
    ops += policy["layer_gnn"] * (2 * v * h * h + 2 * (2 * e + v) * h)
    phi = [h] * policy["layer_parsingnet"] + [1]
    ops += sum(2 * e * a * b for a, b in zip(phi, phi[1:]))
    head = [h, h, d]
    ops += sum(2 * v * a * b for a, b in zip(head, head[1:]))
    return ops


def train_window(num_nodes: int, num_edges: int, d_in: int, policy: dict,
                 *, chains: int, steps: int, start_first: bool) -> int:
    """Forward + backward operations of one window of ``chains`` chains."""
    fwd = sum(step_forward(num_nodes, num_edges, d_in, policy,
                           first=start_first and t == 0)
              for t in range(steps))
    return 3 * chains * fwd

