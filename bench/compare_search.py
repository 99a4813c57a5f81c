"""The comparison that decides ``correct`` in a search cell.

The reference walks every check window of the run on its own: it
teacher-forces the program's placements (``bench/ref_policy.py``), scores
them with its own scheduler, carries its own baseline, chain state and
parameters (float64 Adam over its own gradients) from window to window.
Each window is checked from the program's own state before it (its
parameters and carried chain state), so a near-tie decided one way in an
earlier window cannot fork a later one; each state the program hands on is
itself checked against the reference's step from the state before.  Five
numbers, each against the limit in the cell's file, and one reading that is
reported but not compared:

``makespan_gap``  the widest relative gap between a latency the program's
                  simulator scored and the reference scheduler's latency of
                  the same placement, over placements drawn from the seed in
                  every window, and the best placement tracked;
``fork_share``    the share of the check windows' chain steps that the
                  reference could not follow (it stops following a chain at
                  the first step of a window whose parse it cannot confirm);
``action_flip``   (details only) on the steps it follows, the widest relative
                  margin by which the reference's own draw preferred another
                  device than the one the program placed; it read 0 on every
                  seed, and neither the control nor a fault moves it (both
                  fork at the first step), so it has no upper reading;
``grad_gap``      the widest over the windows of the Eq.-14 gradient the
                  optimizer got (the program's replay, on the window's
                  parameters and carried chain state): by the worst leaf, the
                  gap between the norms of the program's gradient and the
                  reference's; where the reference lost a chain, the
                  program's replay runs again with those steps' weights 0;
``state_gap``     the widest over the windows and the chains followed
                  through them of the relative gap between the chain state
                  the program carries out of the window and the reference's;
``step_gap``      the widest over the windows of the parameters' change so
                  far: by the worst leaf, the gap between its norm and that
                  of a float64 Adam over the gradients the program's
                  optimizer got.

Leaves whose reference gradient is under a thousandth of the median leaf's
move under Adam by round-off alone and are left out of both gaps.
"""
from __future__ import annotations

import functools

import numpy as np

from . import ref_policy
from .ref_sim import RefScheduler

EXCLUDE_BELOW = 1e-3


def leaves(tree):
    import jax
    return [np.asarray(x, np.float64) for x in jax.tree.leaves(tree)]


def worst_leaf_gap(prog, ref, basis) -> float:
    """max over kept leaves of | |p| - |r| | / max(|r|, median |r|)."""
    pn = np.array([np.linalg.norm(x) for x in leaves(prog)])
    rn = np.array([np.linalg.norm(x) for x in leaves(ref)])
    bn = np.array([np.linalg.norm(x) for x in leaves(basis)])
    keep = bn >= EXCLUDE_BELOW * np.median(bn)
    if not np.any(bn > 0):
        return float("inf")
    med = np.median(rn[keep])
    return float(np.max(np.abs(pn - rn)[keep] / np.maximum(rn[keep], med)))


def ref_weights(rewards, cfg: dict, baseline=None):
    """Eq.-14 step weights (T, B) from rewards (T, B): a moving-average
    baseline over chain steps in window order, γ^t discount, per-chain
    standardisation.  → (weights, baseline after the window)."""
    r = np.asarray(rewards, np.float64)
    if cfg["use_baseline"]:
        for x in r.reshape(-1):
            baseline = x if baseline is None else 0.9 * baseline + 0.1 * x
        r = r - baseline
    if cfg["reward_to_go"] or cfg["entropy_coef"] or cfg["k_epochs"] != 1:
        raise ValueError("the reference covers reward_to_go=false, "
                         "entropy_coef=0, k_epochs=1")
    w = (cfg["gamma"] ** np.arange(r.shape[0]))[:, None] * r
    if cfg["normalize_weights"] and r.shape[0] > 1:
        std = w.std(axis=0, keepdims=True)
        w = np.where(std > 1e-12, (w - w.mean(axis=0, keepdims=True))
                     / np.where(std > 1e-12, std, 1.0), w)
    return w.astype(np.float32), baseline


def ref_adam(grads_list, opt: dict, lr: float):
    """Σ of Adam's updates over successive gradients, in float64."""
    total = m = v = None
    for k, g in enumerate(grads_list, start=1):
        g = leaves(g)
        if m is None:
            m = [np.zeros_like(x) for x in g]
            v = [np.zeros_like(x) for x in g]
            total = [np.zeros_like(x) for x in g]
        for i, gi in enumerate(g):
            m[i] = opt["b1"] * m[i] + (1 - opt["b1"]) * gi
            v[i] = opt["b2"] * v[i] + (1 - opt["b2"]) * gi * gi
            mhat = m[i] / (1 - opt["b1"] ** k)
            vhat = v[i] / (1 - opt["b2"] ** k)
            total[i] = total[i] - lr * mhat / (np.sqrt(vhat) + opt["eps"])
    return total


@functools.lru_cache(maxsize=4)
def _window_fn(cfg_items, dtype_name):
    """One program for every window: whether a window opens with a first
    step is an argument, so a run traces the reference once."""
    import jax
    import jax.numpy as jnp
    cfg = dict(cfg_items)
    dtype = jnp.dtype(dtype_name)

    def one(params, x0, adj, src, dst, keys, fines, ngroups, weights, z0,
            first):
        return ref_policy.chain_window(
            params, x0, adj, src, dst, keys, fines, ngroups, weights, z0,
            first, cfg=cfg, dtype=dtype)

    per_chain = jax.value_and_grad(one, has_aux=True)

    def block(params, x0, adj, src, dst, keys, fines, ngroups, weights, z0,
              first):
        (_, (diag, z)), grads = jax.vmap(
            per_chain, in_axes=(None,) * 5 + (1,) * 4 + (0, None))(
            params, x0, adj, src, dst, keys, fines, ngroups, weights, z0,
            first)
        return (jax.tree.map(lambda g: g.sum(0).astype(jnp.float32), grads),
                jax.tree.map(lambda d: jnp.swapaxes(d, 0, 1), diag),
                z.astype(jnp.float32))

    return jax.jit(block)


def reference_window(params, arrays, rec, weights, cfg: dict, *, dtype,
                     block: int, precision: str = "highest", z0=None):
    """Teacher-forced reference over every chain of one recorded window →
    (gradient summed over chains / B, diagnostics (T, B), chains' state
    after the window (B, V, H)).  ``z0`` (B, V, H) is the chains' state
    before a window that does not start afresh.  ``precision`` is the matmul
    precision the configuration states."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    fn = _window_fn(tuple(sorted(cfg.items())), dt.name)
    # Through the host, so the reference runs on one device even where the
    # program's arrays are laid out over a mesh.
    cast = lambda a: jnp.asarray(np.asarray(a, np.float32)).astype(dt)
    p = jax.tree.map(cast, params)
    x0, adj = cast(arrays.x), cast(arrays.adj)
    src = jnp.asarray(arrays.edges[:, 0], jnp.int32)
    dst = jnp.asarray(arrays.edges[:, 1], jnp.int32)
    nchains = rec["fines"].shape[1]
    if z0 is None:
        hidden = cfg["hidden_channel"]
        z0 = jnp.zeros((nchains, x0.shape[0], hidden), dt)
    total, diags, states = None, [], []
    with jax.default_matmul_precision(precision):
        for lo in range(0, nchains, block):
            sl = slice(lo, lo + block)
            g, d, z = fn(p, x0, adj, src, dst,
                         jnp.asarray(np.asarray(rec["keys"])[:, sl]),
                         jnp.asarray(np.asarray(rec["fines"])[:, sl],
                                     jnp.int32),
                         jnp.asarray(np.asarray(rec["ngroups"])[:, sl],
                                     jnp.int32),
                         jnp.asarray(np.asarray(weights)[:, sl], jnp.float32),
                         cast(z0[sl]), jnp.asarray(bool(rec["first"])))
            g = [np.asarray(x, np.float64) for x in jax.tree.leaves(g)]
            total = g if total is None else [a + b for a, b in zip(total, g)]
            diags.append(jax.tree.map(np.asarray, d))
            states.append(np.asarray(z))
    treedef = jax.tree.structure(params)
    grads = jax.tree.unflatten(treedef, [t / nchains for t in total])
    diag = {k: np.concatenate([d[k] for d in diags], axis=1)
            for k in diags[0]}
    return grads, diag, np.concatenate(states)


def state_gap(prog, ref, chains) -> float:
    """Widest relative gap ‖z_prog − z_ref‖ / ‖z_ref‖ over the chains the
    reference followed through the window (inf if it followed none)."""
    if not np.any(chains):
        return float("inf")
    p = np.asarray(prog, np.float64)[chains]
    r = np.asarray(ref, np.float64)[chains]
    axes = tuple(range(1, r.ndim))
    return float(np.max(np.sqrt(np.sum((p - r) ** 2, axis=axes))
                        / np.sqrt(np.sum(r ** 2, axis=axes))))


def compare(graph, platform, arrays, records, samples, best, params0,
            params_after, z_after, program_grads, config: dict, *,
            block: int = 16):
    """→ (numbers, details).

    ``records`` are the check windows, each with the program's parameters
    and chain state before it; ``params_after`` and ``z_after`` are those
    after the last.  ``program_grads(rec, weights)`` runs the program's
    compiled replay on a recorded window with other weights."""
    policy = config["policy"]
    if (policy["gnn_model"], policy["dropout_parsing"], policy["head"]) != (
            "gcn", 0.0, "dense"):
        raise ValueError("the reference covers gnn_model='gcn', "
                         "dropout_parsing=0 and head='dense'")
    sched = RefScheduler(graph, platform)
    placed = np.stack([p for p, _ in samples] + [best[0]])
    scored = np.asarray([lat for _, lat in samples] + [best[1]])
    ref_lat = sched.latency(placed)
    makespan_gap = float(np.max(np.abs(scored - ref_lat) / ref_lat))

    B, V = records[0]["fines"].shape[1:]
    base = leaves(params0)
    baseline = None
    grad_gaps, state_gaps, step_gaps, followed, ref_grads = [], [], [], [], []
    flips, margins, nonfinite_prog = [], [], []
    for k, rec in enumerate(records):
        last = k + 1 == len(records)
        T = rec["fines"].shape[0]
        rewards, _ = sched.reward(rec["fines"].reshape(T * B, V))
        weights, baseline = ref_weights(rewards.reshape(T, B), policy,
                                        baseline)
        z0 = None if rec["first"] else np.asarray(rec["z"])[0]
        ref_g, diag, z_ref = reference_window(
            rec["params_before"], arrays, rec, weights, policy,
            dtype=np.float32, block=block,
            precision=config["matmul_precision"], z0=z0)
        ref_grads.append(ref_g)
        fol = diag["followed"]
        followed.append(fol)
        # What the optimizer got or, where the reference lost a chain, the
        # program's own replay over the steps it followed.
        prog_g = (rec["grads"] if fol.all()
                  else program_grads(rec, rec["weights"] * fol))
        grad_gaps.append(worst_leaf_gap(prog_g, ref_g, ref_g))
        # The chain state the program carries into the next window.
        z_next = z_after if last else records[k + 1]["z"]
        state_gaps.append(state_gap(np.asarray(z_next)[0], z_ref,
                                    fol.all(axis=0)))
        # The update: the parameters' change so far against a float64 Adam
        # over the gradients the program's optimizer got.
        after = params_after if last else records[k + 1]["params_before"]
        delta_ref = ref_adam([r["grads"] for r in records[:k + 1]],
                             config["optimizer"], policy["learning_rate"])
        delta_prog = [a - b for a, b in zip(leaves(after), base)]
        step_gaps.append(worst_leaf_gap(delta_prog, delta_ref, ref_grads[0]))
        flips.append(float(diag["flip"].max()))
        margins.append(float(np.min(diag["parse_margin"])))
        nonfinite_prog.append(nonfinite(prog_g))

    numbers = dict(makespan_gap=makespan_gap,
                   fork_share=float(1.0 - np.mean(followed)),
                   # np.max, not max: a NaN in any window has to show.
                   grad_gap=float(np.max(grad_gaps)),
                   state_gap=float(np.max(state_gaps)),
                   step_gap=float(np.max(step_gaps)))
    details = dict(
        grad_gap_by_window=grad_gaps, state_gap_by_window=state_gaps,
        step_gap_by_window=step_gaps,
        fork_share_by_window=[float(1.0 - f.mean()) for f in followed],
        action_flip=max(flips),
        placements_simulated=int(len(placed)),
        nonfinite=dict(program_grads=nonfinite_prog,
                       reference_grads=[nonfinite(g) for g in ref_grads],
                       params_after=nonfinite(params_after)),
        min_parse_margin=min(margins))
    return numbers, details


def nonfinite(tree):
    return [int(np.sum(~np.isfinite(x))) for x in leaves(tree)]
