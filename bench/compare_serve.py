"""The comparison that decides ``correct`` in a serve cell.

Every request due in the window is checked once the window has closed:

``unanswered``  requests whose placement never came (limit 0);
``logit_gap``   over every answered request and every node, how far the
                served device's logit lies below the best logit of the
                node's group in the reference's greedy decode, over 1 + the
                group's largest |logit|.  Served placements go through the
                server's queues, bucketing (padding to the bucket's shape)
                and batching, so a placement that reaches the wrong request
                or a wrongly padded decode shows here.
"""
from __future__ import annotations

import functools

import numpy as np

from . import ref_policy


@functools.lru_cache(maxsize=4)
def _logits_fn(cfg_items, dtype_name):
    import jax
    import jax.numpy as jnp
    cfg = dict(cfg_items)
    return jax.jit(functools.partial(ref_policy.greedy_logits, cfg=cfg,
                                     dtype=jnp.dtype(dtype_name)))


def reference_logits(params, arrays, policy: dict, *, dtype, precision):
    """(V, D) greedy logits per node of one graph."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    cast = lambda a: jnp.asarray(a, jnp.float32).astype(dt)
    fn = _logits_fn(tuple(sorted(policy.items())), dt.name)
    with jax.default_matmul_precision(precision):
        out = fn(jax.tree.map(cast, params), cast(arrays.x), cast(arrays.adj),
                 jnp.asarray(arrays.edges[:, 0], jnp.int32),
                 jnp.asarray(arrays.edges[:, 1], jnp.int32))
    return np.asarray(out, np.float64)


def gaps(logits, placement):
    """Per node: (best - served) / (1 + max |logit|)."""
    served = np.take_along_axis(logits, placement[:, None], -1)[:, 0]
    return (logits.max(-1) - served) / (1.0 + np.abs(logits).max(-1))


def per_variant_logits(serve, params, *, dtype):
    from repro.core.features import extract_features
    policy = serve.config["policy"]
    return [reference_logits(params, extract_features(g, serve.fc), policy,
                             dtype=dtype,
                             precision=serve.config["matmul_precision"])
            for g in serve.variants]


def compare(serve, win, seed):
    ref = per_variant_logits(serve, serve.params, dtype=np.float32)
    widest, unanswered = 0.0, 0
    for v, placement in zip(win["picks"], win["placements"]):
        if placement is None:
            unanswered += 1
            continue
        p = np.asarray(placement, np.int64)
        if p.shape != (ref[v].shape[0],):
            widest = float("inf")
            continue
        widest = max(widest, float(gaps(ref[v], p).max()))
    return (dict(unanswered=float(unanswered), logit_gap=widest),
            dict(checked=int(len(win["picks"]) - unanswered)))


def control_gap(serve) -> float:
    """The control: the reference in bfloat16 decodes each variant; the
    float32 reference reads the gap of what it puts first."""
    ref = per_variant_logits(serve, serve.params, dtype=np.float32)
    low = per_variant_logits(serve, serve.params, dtype="bfloat16")
    return max(float(gaps(r, l.argmax(-1)).max()) for r, l in zip(ref, low))
