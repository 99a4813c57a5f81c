"""Graph/node encoders (paper §2.4, Eq. 6) — pure JAX.

The encoder is ``layer_trans`` MLP layers mapping X^(0) into the hidden width,
followed by ``layer_gnn`` graph-convolution layers over the symmetric-normalized
self-looped adjacency (Eq. 6).  Dense adjacency is used — paper graphs have
≤ ~1k nodes (Table 1).  A GraphSAGE-style mean aggregator is provided as the
alternative ``gnn_model`` (the framework is model-agnostic, §2.4).
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp

__all__ = [
    "mlp_init", "mlp_apply",
    "normalize_adjacency",
    "encoder_init", "encoder_apply",
]

Params = Dict[str, jnp.ndarray]


def _glorot(rng, shape):
    fan_in, fan_out = shape[0], shape[-1]
    lim = jnp.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(rng, shape, jnp.float32, -lim, lim)


# --------------------------------------------------------------------- MLP
def mlp_init(rng, sizes: Sequence[int]) -> List[Params]:
    layers = []
    for i in range(len(sizes) - 1):
        rng, key = jax.random.split(rng)
        layers.append({
            "w": _glorot(key, (sizes[i], sizes[i + 1])),
            "b": jnp.zeros((sizes[i + 1],), jnp.float32),
        })
    return layers


def mlp_apply(layers: List[Params], x: jnp.ndarray, *,
              act=jax.nn.relu, act_final: bool = False) -> jnp.ndarray:
    for i, layer in enumerate(layers):
        x = x @ layer["w"] + layer["b"]
        if i < len(layers) - 1 or act_final:
            x = act(x)
    return x


# --------------------------------------------------------------------- GCN
def normalize_adjacency(adj: jnp.ndarray,
                        add_self_loops: bool = True) -> jnp.ndarray:
    """D̂^{-1/2} Â D̂^{-1/2} with Â = A + I (Eq. 6).

    The computation graph A is asymmetric; Eq. 6 normalizes it directly, so we
    keep direction (information flows source→dest) but use the symmetrized
    degree for stability, matching common DAG-GCN practice.
    """
    a = adj
    if add_self_loops:
        a = a + jnp.eye(a.shape[0], dtype=a.dtype)
    deg = jnp.sum(a, axis=1) + jnp.sum(a, axis=0) - jnp.diag(a)
    inv_sqrt = jnp.where(deg > 0, 1.0 / jnp.sqrt(deg), 0.0)
    return inv_sqrt[:, None] * (a + a.T - jnp.diag(jnp.diag(a))) * inv_sqrt[None, :]


def encoder_init(rng, d_in: int, hidden: int, *, layer_trans: int = 2,
                 layer_gnn: int = 2, gnn_model: str = "gcn") -> Params:
    """Parameters for the §2.4 encoder (Appendix H defaults)."""
    rng, k_mlp = jax.random.split(rng)
    sizes = [d_in] + [hidden] * layer_trans
    params: Params = {"trans": mlp_init(k_mlp, sizes), "gnn": []}
    for _ in range(layer_gnn):
        rng, key = jax.random.split(rng)
        if gnn_model == "gcn":
            params["gnn"].append({"w": _glorot(key, (hidden, hidden))})
        elif gnn_model == "sage":
            k1, k2 = jax.random.split(key)
            params["gnn"].append({
                "w_self": _glorot(k1, (hidden, hidden)),
                "w_nbr": _glorot(k2, (hidden, hidden)),
            })
        else:
            raise ValueError(f"unknown gnn_model {gnn_model!r}")
    return params


def encoder_apply(params: Params, x: jnp.ndarray, adj: jnp.ndarray, *,
                  dropout_rng=None, edge_dropout: float = 0.0,
                  transform: bool = True,
                  node_mask: "jnp.ndarray | None" = None) -> jnp.ndarray:
    """X^(0) → Z (Eq. 6).  ``edge_dropout`` implements Appendix-H
    ``dropout_network`` (edges dropped during exploration).

    ``transform=False`` skips the input MLP — used on rounds ≥ 1 of the
    multi-round rollout (Alg. 1 line 12) where the state is already at the
    hidden width.

    ``node_mask`` (V,) bool marks real nodes of a padded multi-graph batch.
    Pad rows are zeroed after the input MLP (its bias would otherwise give
    them nonzero embeddings); they have no edges, so the GCN layers keep them
    at zero and real nodes never see them.  ``None`` keeps the exact
    single-graph computation.

    Its operations carry the ``repro.encoder`` scope in their ``op_name``.
    """
    with jax.named_scope("repro.encoder"):
        if dropout_rng is not None and edge_dropout > 0.0:
            keep = jax.random.bernoulli(dropout_rng, 1.0 - edge_dropout,
                                        adj.shape)
            adj = adj * keep.astype(adj.dtype)
        a_hat = normalize_adjacency(adj)
        z = mlp_apply(params["trans"], x, act_final=True) if transform else x
        if node_mask is not None:
            z = z * node_mask.astype(z.dtype)[:, None]
        # The layer-param keys identify the model (keeps the pytree
        # string-free).
        model = "gcn" if (params["gnn"] and "w" in params["gnn"][0]) \
            else "sage"
        n_layers = len(params["gnn"])
        for i, layer in enumerate(params["gnn"]):
            if model == "gcn":
                z_new = a_hat @ (z @ layer["w"])
            else:  # sage: mean aggregation over in+out neighbors
                deg = jnp.clip(adj.sum(0) + adj.sum(1), 1.0)
                nbr = ((adj + adj.T) @ z) / deg[:, None]
                z_new = z @ layer["w_self"] + nbr @ layer["w_nbr"]
            z = jax.nn.relu(z_new) if i < n_layers - 1 else z_new
        return z
