"""Graph Parsing Network (paper §2.4, Eq. 7–11; Alg. 2).

Jointly learns *how many* groups a computation graph should be split into and
*which* nodes join each group:

  1. edge scores       S_{v,u} = σ(φ(z_v ⊙ z_u)), masked by A        (Eq. 7)
  2. dominant edges    E' = {(v, argmax_{u∈N(v)} S_{v,u})}            (Eq. 9)
  3. clusters          connected components of E'  →  assignment X   (Eq. 10)
  4. pooled graph      A' = XᵀAX, pooled features Z' = Xᵀ(Z·gate)     (Eq. 11)

Everything is shape-static and jit-able: cluster ids live in [0, V) (the
minimum member index of each component) and an ``active`` mask marks occupied
slots, so the number of groups is *emergent* — never preset (the paper's core
argument against fixed-k grouper-placers).

Differentiability: the discrete parse is made differentiable the GPN way — each
node's pooled contribution is gated by its dominant edge score with a
straight-through estimator, so ∂loss/∂φ exists while the forward pass stays an
exact sum.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from .gnn import mlp_apply, mlp_init

__all__ = ["gpn_init", "edge_scores", "parse_graph", "gpn_apply", "ParseResult"]


class ParseResult(NamedTuple):
    labels: jnp.ndarray        # (V,) int32 — component id (min member index)
    assign: jnp.ndarray        # (V, V) float32 — assignment matrix X
    pooled_adj: jnp.ndarray    # (V, V) float32 — A' (binary, no self loops)
    pooled_z: jnp.ndarray      # (V, d') — Z' (zero rows for inactive slots)
    active: jnp.ndarray        # (V,) bool — occupied cluster slots
    scores: jnp.ndarray        # (E,) float32 — per-edge sigmoid scores
    retained: jnp.ndarray      # (E,) bool — Eq. 9 dominant edges
    num_groups: jnp.ndarray    # () int32


def gpn_init(rng, hidden: int, *, layer_parsingnet: int = 2) -> Dict:
    """φ of Eq. 7 — an MLP from the hidden width to a scalar logit."""
    sizes = [hidden] * layer_parsingnet + [1]
    return {"phi": mlp_init(rng, sizes)}


def edge_scores(params: Dict, z: jnp.ndarray, edges: jnp.ndarray, *,
                dropout_rng=None, dropout_parsing: float = 0.0) -> jnp.ndarray:
    """Eq. 7 per existing edge: σ(φ(z_src ⊙ z_dst)).  The ``S = S ⊙ A``
    constraint holds by construction (only real edges are scored)."""
    src, dst = edges[:, 0], edges[:, 1]
    prod = z[src] * z[dst]
    logit = mlp_apply(params["phi"], prod)[:, 0]
    s = jax.nn.sigmoid(logit)
    if dropout_rng is not None and dropout_parsing > 0.0:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_parsing, s.shape)
        s = s * keep.astype(s.dtype)
    return s


def _dominant_edges(scores: jnp.ndarray, edges: jnp.ndarray,
                    num_nodes: int,
                    edge_mask: "jnp.ndarray | None" = None) -> jnp.ndarray:
    """Eq. 9 — retain, per node, its max-score incident edge (N = in ∪ out).

    An edge survives if it is the dominant edge of either endpoint.  Ties keep
    all tied edges (harmless: merges stay symmetric).  Masked (pad) edges
    score −inf and are never retained.
    """
    src, dst = edges[:, 0], edges[:, 1]
    neg = jnp.float32(-jnp.inf)
    if edge_mask is not None:
        scores = jnp.where(edge_mask, scores, neg)
    node_max = jnp.full((num_nodes,), neg)
    node_max = node_max.at[src].max(scores)
    node_max = node_max.at[dst].max(scores)
    retained = (scores >= node_max[src]) | (scores >= node_max[dst])
    if edge_mask is not None:
        retained = retained & edge_mask
    return retained


def _connected_components(edges: jnp.ndarray, retained: jnp.ndarray,
                          num_nodes: int) -> jnp.ndarray:
    """Min-label propagation over the retained edge set; O(diameter) rounds.

    jit-able: fixed shapes, ``lax.while_loop`` until fixpoint.  Also
    vmap-safe: under a lifted while_loop every chain keeps iterating until
    *all* chains converge, and extra ``body`` passes are no-ops at the
    fixpoint (min-propagation is idempotent) — a property the batched
    multi-chain rollout engine relies on.

    The pass before the loop and the loop carry the
    ``repro.gpn_components`` scope: a profile counts the rounds (the loop's
    ``it`` + 1) as executions of the loop body.
    """
    src, dst = edges[:, 0], edges[:, 1]
    big = jnp.int32(num_nodes)
    # Inactive edges propagate the sentinel ``big`` which never wins a min.
    def body(labels):
        ls = jnp.where(retained, labels[src], big)
        ld = jnp.where(retained, labels[dst], big)
        new = labels.at[dst].min(ls)
        new = new.at[src].min(ld)
        return new

    def cond(state):
        labels, prev, it = state
        return jnp.logical_and(jnp.any(labels != prev), it < num_nodes)

    def step(state):
        labels, _, it = state
        return body(labels), labels, it + 1

    init = jnp.arange(num_nodes, dtype=jnp.int32)
    with jax.named_scope("repro.gpn_components"):
        labels, _, _ = jax.lax.while_loop(
            cond, step, (body(init), init, jnp.int32(0)))
    return labels


def parse_graph(scores: jnp.ndarray, edges: jnp.ndarray, z: jnp.ndarray,
                adj: jnp.ndarray, *, straight_through: bool = True,
                node_mask: "jnp.ndarray | None" = None,
                edge_mask: "jnp.ndarray | None" = None) -> ParseResult:
    """Eq. 9–11: dominant edges → components → X, A', Z'.

    ``node_mask``/``edge_mask`` support padded multi-graph batches: pad edges
    never dominate, never merge components and never gate contributions; pad
    nodes (isolated by construction) end up as singleton clusters that are
    excluded from ``active`` — and therefore from the policy's log-prob,
    entropy and ``num_groups``.  ``None`` masks keep the exact single-graph
    computation.
    """
    num_nodes = z.shape[0]
    if edges.shape[0] == 0:
        labels = jnp.arange(num_nodes, dtype=jnp.int32)
        assign = jnp.eye(num_nodes, dtype=jnp.float32)
        active = (jnp.ones((num_nodes,), bool) if node_mask is None
                  else node_mask)
        return ParseResult(labels, assign, jnp.zeros_like(adj), z,
                           active, scores,
                           jnp.zeros((0,), bool),
                           active.sum().astype(jnp.int32))

    retained = _dominant_edges(scores, edges, num_nodes, edge_mask)
    labels = _connected_components(edges, retained, num_nodes)

    # X: (V, V) one-hot rows into the component-representative slot (Eq. 10).
    assign = jax.nn.one_hot(labels, num_nodes, dtype=jnp.float32)
    if node_mask is None:
        active = assign.sum(0) > 0
    else:
        # A slot is active only if a *real* node landed in it.
        active = (assign * node_mask.astype(assign.dtype)[:, None]).sum(0) > 0

    # Differentiable gate: a node contributes through its dominant edge score.
    src, dst = edges[:, 0], edges[:, 1]
    g_scores = scores if edge_mask is None else \
        jnp.where(edge_mask, scores, -jnp.inf)
    gate = jnp.zeros((num_nodes,), scores.dtype)
    gate = gate.at[src].max(g_scores)
    gate = gate.at[dst].max(g_scores)
    if edge_mask is None:
        has_edge = (jnp.zeros((num_nodes,), bool)
                    .at[src].set(True).at[dst].set(True))
    else:
        has_edge = (jnp.zeros((num_nodes,), bool)
                    .at[src].max(edge_mask).at[dst].max(edge_mask))
    gate = jnp.where(has_edge, gate, 1.0)
    if straight_through:
        gate = gate + jax.lax.stop_gradient(1.0 - gate)

    # Z' = Xᵀ(Z·gate) and A' = XᵀAX, computed sparsely over the edge list
    # (identical results to the dense matmuls; E ≪ V² on paper graphs).
    pooled_z = jax.ops.segment_sum(z * gate[:, None], labels,
                                   num_segments=num_nodes)          # Z'
    ls, ld = labels[src], labels[dst]
    edge_w = (jnp.ones_like(scores) if edge_mask is None
              else edge_mask.astype(adj.dtype))
    pooled_adj = jnp.zeros_like(adj).at[ls, ld].add(edge_w)         # Eq. 11
    pooled_adj = (pooled_adj > 0).astype(adj.dtype)
    pooled_adj = pooled_adj * (1.0 - jnp.eye(num_nodes, dtype=adj.dtype))
    return ParseResult(labels, assign, pooled_adj, pooled_z, active,
                       scores, retained, active.sum().astype(jnp.int32))


def gpn_apply(params: Dict, z: jnp.ndarray, edges: jnp.ndarray,
              adj: jnp.ndarray, *, dropout_rng=None,
              dropout_parsing: float = 0.0,
              node_mask: "jnp.ndarray | None" = None,
              edge_mask: "jnp.ndarray | None" = None) -> ParseResult:
    """Full §2.4 grouping step: scores (Eq. 7) then parse (Eq. 9–11),
    under the ``repro.gpn`` scope."""
    with jax.named_scope("repro.gpn"):
        s = edge_scores(params, z, edges, dropout_rng=dropout_rng,
                        dropout_parsing=dropout_parsing)
        return parse_graph(s, edges, z, adj, node_mask=node_mask,
                           edge_mask=edge_mask)
