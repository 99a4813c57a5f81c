"""Plain reference of the placement policy and its Eq.-14 gradient, in JAX.

One step of the paper's Algorithm 1 for one chain, written out from the
paper and the configuration file, sharing no code with the program:

* encoder (Eq. 6): edges of A are dropped with probability
  ``dropout_network``; Â = A + I is normalised as D^-1/2 (Â + Âᵀ - diag) D^-1/2
  with the symmetrised degree; on the first step an MLP (ReLU after every
  layer) lifts X0 to the hidden width; then GCN layers Z ← Â (Z W), ReLU
  between layers;
* graph parsing (Eq. 7-11): edge scores σ(φ(z_u ⊙ z_v)); every node keeps its
  highest-scoring incident edge; groups are the connected components of the
  kept edges, named by their smallest member; a node's pooled contribution
  is gated by its best incident score with a straight-through estimator
  (value 1, gradient of the score);
* placement head: an MLP (ReLU between layers) over each group's pooled
  embedding gives one logit per device; a device is drawn per group as the
  argmax of logits + Gumbel noise from the step's key;
* state: Z ← rms_normalise(Z_enc + pooled[group]).

The reference never samples on the program's behalf.  Given the program's
placements it *teacher-forces* them: a group's action is the device the
program gave the group's smallest member, so the log-probability, the
gradient and the next state follow the program's trajectory.  Sampling
near-ties therefore cannot fork it: the state does not depend on the sampled
devices at all.  What can fork is the parse, an argmax over edge scores; a
step whose parse the reference cannot confirm (another number of groups, or
a placement that splits one of the reference's groups) ends the chain's
comparison there, and the gradient is compared over the steps before.

``dtype`` float32 is the reference, run at the matmul precision the
configuration states; bfloat16 is the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def glorot(key, shape):
    lim = (6.0 / (shape[0] + shape[-1])) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


def init_mlp(key, sizes):
    layers = []
    for i in range(len(sizes) - 1):
        key, sub = jax.random.split(key)
        layers.append({"w": glorot(sub, (sizes[i], sizes[i + 1])),
                       "b": jnp.zeros((sizes[i + 1],), jnp.float32)})
    return layers


def init_params(key, d_in: int, policy: dict):
    """The policy's weights from one key, in the tree layout the program
    reads (``enc``/``gpn``/``pol``).  Glorot-uniform matrices, zero biases."""
    h = policy["hidden_channel"]
    k_enc, k_gpn, k_pol = jax.random.split(key, 3)
    k_trans, *k_gnn = jax.random.split(k_enc, 1 + policy["layer_gnn"])
    return {
        "enc": {"trans": init_mlp(k_trans, [d_in] + [h] * policy["layer_trans"]),
                "gnn": [{"w": glorot(k, (h, h))} for k in k_gnn]},
        "gpn": {"phi": init_mlp(k_gpn, [h] * policy["layer_parsingnet"] + [1])},
        "pol": {"mlp": init_mlp(k_pol, [h] * 2 + [policy["num_devices"]])},
    }


def mlp(layers, x, act_final=False):
    for i, layer in enumerate(layers):
        x = x @ layer["w"] + layer["b"]
        if i < len(layers) - 1 or act_final:
            x = jax.nn.relu(x)
    return x


def _node_max(values, src, dst, num_nodes):
    """Per node, the largest value over its incident edges (-inf if none).
    Taken in float32: the max of values is exact in any precision."""
    v = values.astype(jnp.float32)
    out = jnp.full((num_nodes,), -jnp.inf, jnp.float32)
    return out.at[src].max(v).at[dst].max(v)


def _second_max(values, src, dst, top, num_nodes):
    """Per node, the best incident score that is below its best one."""
    v = values.astype(jnp.float32)
    lower = lambda ends: jnp.where(v < top[ends], v, -jnp.inf)
    out = jnp.full((num_nodes,), -jnp.inf, jnp.float32)
    return out.at[src].max(lower(src)).at[dst].max(lower(dst))


def _components(src, dst, kept, num_nodes):
    def body(state):
        labels, _ = state
        ls = jnp.where(kept, labels[src], num_nodes)
        ld = jnp.where(kept, labels[dst], num_nodes)
        return labels.at[dst].min(ls).at[src].min(ld), labels

    def cond(state):
        labels, prev, it = state
        return jnp.any(labels != prev) & (it < num_nodes)

    def step_(state):
        labels, _, it = state
        return body((labels, None))[0], labels, it + 1

    init = jnp.arange(num_nodes, dtype=jnp.int32)
    labels, _, _ = jax.lax.while_loop(
        cond, step_, (body((init, init))[0], init, jnp.int32(0)))
    return labels


def step(params, z, x0, adj, src, dst, key, *, first: bool, cfg: dict,
         dtype, train: bool = True):
    """One Algorithm-1 step of one chain → dict of what the step produced.
    ``train=False`` (greedy decoding) drops no edges."""
    n = x0.shape[0]
    k_net, _k_parse, k_pol = jax.random.split(key, 3)
    if train:
        keep = jax.random.bernoulli(k_net, 1.0 - cfg["dropout_network"],
                                    adj.shape)
        adj = adj * keep.astype(dtype)
    a = adj + jnp.eye(n, dtype=dtype)
    deg = a.sum(1) + a.sum(0) - jnp.diag(a)
    inv = jnp.where(deg > 0, 1.0 / jnp.sqrt(deg), 0.0).astype(dtype)
    a_hat = inv[:, None] * (a + a.T - jnp.diag(jnp.diag(a))) * inv[None, :]
    if isinstance(first, bool):
        h = mlp(params["enc"]["trans"], x0, act_final=True) if first else z
    else:       # traced: one program serves first and later steps
        h = jnp.where(first, mlp(params["enc"]["trans"], x0, act_final=True),
                      z)
    gnn = params["enc"]["gnn"]
    for i, layer in enumerate(gnn):
        h = a_hat @ (h @ layer["w"])
        if i < len(gnn) - 1:
            h = jax.nn.relu(h)

    scores = jax.nn.sigmoid(mlp(params["gpn"]["phi"], h[src] * h[dst])[:, 0])
    top = _node_max(scores, src, dst, n)
    kept = (scores >= top[src]) | (scores >= top[dst])
    labels = _components(src, dst, kept, n)
    has_edge = jnp.zeros((n,), bool).at[src].set(True).at[dst].set(True)
    # How close each node's choice of edge came to a tie.
    second = _second_max(scores, src, dst, top, n)
    parse_margin = jnp.where(has_edge, top - second, jnp.inf)
    gate = jnp.where(has_edge, top, 1.0).astype(dtype)
    gate = gate + jax.lax.stop_gradient(1.0 - gate)
    pooled = jax.ops.segment_sum((h * gate[:, None]).astype(jnp.float32),
                                 labels, num_segments=n).astype(dtype)
    active = jnp.zeros((n,), bool).at[labels].set(True)

    logits = mlp(params["pol"]["mlp"], pooled)
    logp_all = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    noisy = logits + jax.random.gumbel(k_pol, logits.shape, logits.dtype)
    own = jnp.argmax(noisy, axis=-1)
    srt = jnp.sort(noisy, axis=-1)
    scale = 1.0 + jnp.max(jnp.abs(logits), axis=-1)
    action_margin = (srt[:, -1] - srt[:, -2]) / scale

    z_next = h + pooled[labels]
    if cfg["state_norm"]:
        z_next = z_next / jnp.sqrt(jnp.mean(jnp.square(z_next)) + 1e-6)
    return dict(z_next=z_next.astype(dtype), labels=labels, active=active,
                logp_all=logp_all, own=own, action_margin=action_margin,
                parse_margin=parse_margin, logits=logits,
                ngroups=active.sum().astype(jnp.int32))


def chain_window(params, x0, adj, src, dst, keys, fines, ngroups, weights,
                 z0, start_first, *, cfg: dict, dtype):
    """Teacher-forced replay of one chain's window.

    keys (T, 2), fines (T, V) the program's placements, ngroups (T,) the
    program's group counts, weights (T,) Eq.-14 step weights.  Returns the
    chain's masked loss (the sum over followed steps of -w·logp) and, as aux,
    per-step diagnostics and the chain's state after the window.
    ``followed`` is False from the first step whose parse the reference
    cannot confirm.  ``start_first`` (a traced bool) says whether the window
    opens with a first step.  ``z0`` is the state the window starts from
    (unused when it starts with a first step).
    """
    def body(carry, xs):
        z, alive, first_step = carry
        key, fine, ng, w = xs
        out = step(params, z, x0, adj, src, dst, key, first=first_step,
                   cfg=cfg, dtype=dtype)
        labels, active = out["labels"], out["active"]
        splits = jnp.any(fine != fine[labels])
        same_parse = (out["ngroups"] == ng) & ~splits
        alive = alive & same_parse
        chosen = jnp.take_along_axis(out["logp_all"], fine[:, None], -1)[:, 0]
        logp = jnp.sum(jnp.where(active, chosen, 0.0))
        loss = jnp.where(alive, -logp * w, 0.0)
        differs = active & (out["own"] != fine)
        flip = jnp.max(jnp.where(differs & alive,
                                 jax.lax.stop_gradient(out["action_margin"]),
                                 0.0))
        diag = dict(followed=alive, flip=flip,
                    parse_margin=jnp.min(out["parse_margin"]),
                    ngroups=out["ngroups"])
        return (out["z_next"], alive, jnp.asarray(False)), (loss, diag)

    carry = (z0.astype(dtype), jnp.asarray(True), jnp.asarray(start_first))
    (z, _, _), (losses, diag) = jax.lax.scan(body, carry,
                                             (keys, fines, ngroups, weights))
    return jnp.sum(losses), (diag, z)


def chain_rollout(params, x0, adj, src, dst, keys, z0, start_first, *,
                  cfg: dict, dtype):
    """The reference sampling its own window (the control runs this in the
    program's place) → (fines (T, V), ngroups (T,), final state)."""
    def body(carry, key):
        z, first_step = carry
        out = step(params, z, x0, adj, src, dst, key, first=first_step,
                   cfg=cfg, dtype=dtype)
        fine = out["own"][out["labels"]].astype(jnp.int32)
        return (out["z_next"], jnp.asarray(False)), (fine, out["ngroups"])

    (z, _), (fines, ngroups) = jax.lax.scan(
        body, (z0.astype(dtype), jnp.asarray(start_first)), keys)
    return fines, ngroups, z


def greedy_logits(params, x0, adj, src, dst, *, cfg: dict, dtype):
    """Greedy decoding's logits, per node: the (V, D) logits of each node's
    group.  A served placement should put every node on its row's best."""
    key = jnp.zeros((2,), jnp.uint32)
    out = step(params, x0, x0, adj, src, dst, key, first=True, cfg=cfg,
               dtype=dtype, train=False)
    return out["logits"][out["labels"]].astype(jnp.float32)
