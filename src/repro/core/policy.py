"""Device-placement policy head (paper §2.5).

An MLP classifies each *coarsened* node (cluster slot) to one of |D| devices;
sampling is categorical; the coarse placement P' maps back to the original
graph through the cluster labels (the assignment matrix X in the paper — we
gather by label, which is X applied as an index map).

Two heads share this module (``head=`` on :class:`~repro.core.HSDAGConfig`):

``dense``   the paper's fixed ``Dense(num_devices)`` output layer — the
            default, bit-for-bit pinned by the golden suites.
``device``  a node-embedding × device-embedding compatibility head: slot
            embeddings and learned device embeddings (an MLP over the
            ``(D, F_dev)`` fleet feature table from
            :func:`repro.platforms.device_feature_table`) meet in a scaled
            dot product, so one set of weights scores fleets of any size —
            |D| is a *runtime* axis, not a parameter shape.  An optional
            per-(node, device) capacity mask (``SimArrays.fit_ok``) removes
            devices a node's resident bytes can never fit; the mask is
            lifted to cluster slots by an all-members-must-fit reduction
            over the labels, with an unmasked fallback for slots no single
            device can hold (the OOM reward still scores those).

Batch contract: everything here is written per-chain — (V,)-shaped slots, one
PRNG key, ``axis=-1`` reductions — and is lifted over a chain axis with
``jax.vmap`` by the batched rollout engine (hsdag ``batch_chains``), and over
a further *graph* axis by the multi-graph trainer.  Keep new ops vmap-safe:
no data-dependent shapes, no host callbacks, per-chain keys come from the
caller (never split a shared key inside).

Padded multi-graph batches need no masking here beyond ``active``: the GPN
already excludes clusters containing only pad nodes from ``active``, so their
slots contribute nothing to ``logp``/``entropy``; pad entries of
``fine_placement`` are valid device ids that the padded simulator ignores.
Pad *nodes* carry zero bytes, so their ``fit_ok`` rows are all-True and the
cluster reduction never tightens a mask on their account.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .gnn import mlp_apply, mlp_init

__all__ = ["policy_init", "policy_apply", "placement_logp", "PolicyOutput"]

#: Additive logit penalty for capacity-masked actions.  Large enough that a
#: masked device never samples (exp(-1e9) == 0 in f32) yet finite, so the
#: log-softmax stays NaN-free even when temperature scaling runs first.
_MASK_PENALTY = -1e9


class PolicyOutput(NamedTuple):
    coarse_placement: jnp.ndarray   # (V,) int32 — device per cluster slot
    fine_placement: jnp.ndarray     # (V,) int32 — device per original node
    logp: jnp.ndarray               # () — Σ over active slots of log π(p'|slot)
    entropy: jnp.ndarray            # () — Σ entropy over active slots
    logits: jnp.ndarray             # (V, |D|)


def policy_init(rng, hidden: int, num_devices: int, *,
                layers: int = 2, head: str = "dense",
                dev_feat_dim: Optional[int] = None) -> Dict:
    """Head parameters.

    ``dense`` reproduces the original single-MLP head exactly (same sizes,
    same RNG consumption — the bit-for-bit pin).  ``device`` emits a
    ``hidden``-wide slot projection plus a device-embedding MLP over
    ``dev_feat_dim`` fleet features; ``num_devices`` is irrelevant to its
    shapes (the whole point — one parameter set serves any fleet).
    """
    if head == "dense":
        sizes = [hidden] * layers + [num_devices]
        return {"mlp": mlp_init(rng, sizes)}
    if head != "device":
        raise ValueError(f"unknown policy head {head!r}; "
                         f"expected 'dense' or 'device'")
    if dev_feat_dim is None:
        raise ValueError("head='device' needs dev_feat_dim "
                         "(the device feature table width)")
    k_node, k_dev = jax.random.split(rng)
    return {"mlp": mlp_init(k_node, [hidden] * layers + [hidden]),
            "dev": mlp_init(k_dev, [dev_feat_dim, hidden, hidden])}


def _log_softmax(logits):
    return logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)


def _head_logits(params: Dict, pooled_z, dev_feats):
    """(V, |D|) scores — dense MLP, or slot × device compatibility."""
    if dev_feats is None:
        return mlp_apply(params["mlp"], pooled_z)
    node_proj = mlp_apply(params["mlp"], pooled_z)          # (V, H)
    dev_emb = mlp_apply(params["dev"], dev_feats)           # (D, H)
    scale = 1.0 / jnp.sqrt(jnp.asarray(node_proj.shape[-1], node_proj.dtype))
    return (node_proj @ dev_emb.T) * scale


def _cluster_mask(action_mask, labels, num_slots):
    """Lift a per-node (V, D) feasibility mask to cluster slots.

    A slot may only use devices every member node fits on (min over
    members); slots with no member keep all devices, and slots where *no*
    device fits every member fall back to unmasked — the placement is
    doomed to OOM either way, and an all-masked row would make the
    categorical ill-defined.
    """
    node_ok = action_mask.astype(jnp.float32)
    slot_ok = jnp.ones((num_slots, node_ok.shape[-1]), jnp.float32)
    slot_ok = slot_ok.at[labels].min(node_ok)
    ok = slot_ok > 0.5
    any_ok = jnp.any(ok, axis=-1, keepdims=True)
    return jnp.where(any_ok, ok, True)


def policy_apply(params: Dict, pooled_z: jnp.ndarray, active: jnp.ndarray,
                 labels: jnp.ndarray, rng, *,
                 greedy: bool = False, temperature=None,
                 dev_feats=None, action_mask=None) -> PolicyOutput:
    """Sample a placement for every active cluster slot and map it to nodes.

    ``temperature`` (a per-chain scalar; population search threads it)
    scales the categorical distribution to softmax(logits/T) — logp and
    entropy follow the tempered distribution, so the Eq.-14 replay stays
    the exact gradient of what was sampled.  ``None`` skips the division at
    trace time: the jaxpr is unchanged from the temperature-free build.

    ``dev_feats`` (``(D, F_dev)``) switches to the device-compatibility
    head; ``action_mask`` (``(V, D)`` per-node feasibility, e.g.
    ``SimArrays.fit_ok``) masks capacity-infeasible devices out of the
    sampled (and replayed) distribution.  Both default to ``None`` — the
    trace-time-dropped branches that keep the dense head's jaxpr
    byte-identical to the pre-knob build.

    Its operations carry the ``repro.head`` scope in their ``op_name``.
    """
    with jax.named_scope("repro.head"):
        logits = _head_logits(params, pooled_z, dev_feats)
        if temperature is not None:
            logits = logits / temperature
        if action_mask is not None:
            ok = _cluster_mask(action_mask, labels, logits.shape[0])
            logits = jnp.where(ok, logits, logits + _MASK_PENALTY)
        logp_full = _log_softmax(logits)
        if greedy:
            coarse = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            coarse = jax.random.categorical(rng, logits,
                                            axis=-1).astype(jnp.int32)
        chosen_logp = jnp.take_along_axis(logp_full, coarse[:, None],
                                          axis=-1)[:, 0]
        act = active.astype(logits.dtype)
        logp = jnp.sum(chosen_logp * act)
        entropy = jnp.sum(-jnp.sum(jnp.exp(logp_full) * logp_full, -1) * act)
        fine = coarse[labels]
        return PolicyOutput(coarse, fine, logp, entropy, logits)


def placement_logp(params: Dict, pooled_z: jnp.ndarray, active: jnp.ndarray,
                   coarse_placement: jnp.ndarray, *,
                   dev_feats=None) -> jnp.ndarray:
    """log π(P'|G'; θ) of a *stored* coarse placement (replay / K-epoch use)."""
    logits = _head_logits(params, pooled_z, dev_feats)
    logp_full = _log_softmax(logits)
    chosen = jnp.take_along_axis(logp_full, coarse_placement[:, None], -1)[:, 0]
    return jnp.sum(chosen * active.astype(logits.dtype))
