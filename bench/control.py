"""The control of a search cell: the reference put in the program's place,
computed one precision below the configuration's (bfloat16 for float32 at
the default matmul precision).

It samples its own windows with the same keys, scores them with the
reference scheduler in bfloat16, takes its Eq.-14 gradient and an Adam step
in bfloat16, and records its windows in the program's format, so the same
comparison judges it.  It has to come out as not correct.
"""
from __future__ import annotations

import functools

import numpy as np

from . import common, ref_policy
from .compare_search import ref_weights, reference_window
from .ref_sim import RefScheduler


@functools.lru_cache(maxsize=2)
def _rollout_fn(cfg_items):
    import jax
    import jax.numpy as jnp
    cfg = dict(cfg_items)

    def one(params, x0, adj, src, dst, keys, z0, first):
        return ref_policy.chain_rollout(params, x0, adj, src, dst, keys, z0,
                                        first, cfg=cfg, dtype=jnp.bfloat16)
    return jax.jit(jax.vmap(one, in_axes=(None,) * 5 + (1, 0, None),
                            out_axes=(1, 1, 0)))


def _split_steps(rngs, steps):
    """(B, 2) chain keys → (T, B, 2) step keys and the keys after."""
    import jax
    keys = []
    for _ in range(steps):
        both = jax.vmap(jax.random.split)(rngs)
        rngs, k = both[:, 0], both[:, 1]
        keys.append(k)
    return np.stack([np.asarray(k) for k in keys]), rngs


class ControlRun:
    """Mirrors :class:`~bench.drive_search.SearchRun`'s record interface."""

    def __init__(self, search):
        import jax.numpy as jnp
        self.search = search
        self.graph, self.platform = search.graph, search.platform
        self.arrays, self.config = search.arrays, search.config
        self.policy = search.config["policy"]
        self.B, self.T = search.B, search.T
        self.sched = RefScheduler(self.graph, self.platform,
                                  dtype=_bf16_numpy())
        bf = lambda a: jnp.asarray(a, jnp.bfloat16)
        self.x0, self.adj = bf(self.arrays.x), bf(self.arrays.adj)
        self.src = jnp.asarray(self.arrays.edges[:, 0], jnp.int32)
        self.dst = jnp.asarray(self.arrays.edges[:, 1], jnp.int32)

    def reset(self, seed: int):
        import jax
        import jax.numpy as jnp
        from .drive_search import chain_keys, to_host
        key, self.rng = common.seed_key(seed)
        k_params, k_chains = jax.random.split(key)
        self.params0 = to_host(self.search._init(k_params))
        self.params = jax.tree.map(lambda a: jnp.asarray(a, jnp.bfloat16),
                                   self.params0)
        zeros = lambda: jax.tree.map(jnp.zeros_like, self.params)
        self.m, self.v, self.k = zeros(), zeros(), 0
        self.rngs = chain_keys(k_chains, self.B)[0]
        self.z = jnp.zeros((self.B,) + self.arrays.x.shape[:1]
                           + (self.policy["hidden_channel"],), jnp.bfloat16)
        self.first, self.baseline = True, None
        self.best_latency, self.best_placement = float("inf"), None
        self.records, self.samples = [], []

    def window(self, *, sample: int):
        import sys
        import time
        import jax
        import jax.numpy as jnp
        t0 = time.perf_counter()
        keys, self.rngs = _split_steps(self.rngs, self.T)
        fn = _rollout_fn(tuple(sorted(self.policy.items())))
        block = self.search.traffic["reference_block"]
        parts = [fn(self.params, self.x0, self.adj, self.src, self.dst,
                    jnp.asarray(keys[:, lo:lo + block]),
                    self.z[lo:lo + block], jnp.asarray(self.first))
                 for lo in range(0, self.B, block)]
        fines = np.concatenate([np.asarray(p[0]) for p in parts], axis=1)
        ngroups = np.concatenate([np.asarray(p[1]) for p in parts], axis=1)
        z_next = jnp.concatenate([p[2] for p in parts])
        t1 = time.perf_counter()
        rewards, lat = self.sched.reward(fines.reshape(self.T * self.B, -1))
        t2 = time.perf_counter()
        rewards = rewards.reshape(self.T, self.B)
        lat = lat.reshape(self.T, self.B)
        i = np.unravel_index(np.argmin(lat), lat.shape)
        if lat[i] < self.best_latency:
            self.best_latency, self.best_placement = float(lat[i]), fines[i]
        weights, self.baseline = ref_weights(rewards, self.policy,
                                             self.baseline)
        rec = dict(first=self.first, keys=keys, fines=fines, ngroups=ngroups,
                   latencies=lat, weights=weights, z=self.z[None],
                   params_before=self.params)
        grads = self.program_grads(rec, weights)
        rec["grads"] = grads
        self.records.append(rec)
        self._adam(grads)
        for _ in range(sample):
            t, b = self.rng.integers(self.T), self.rng.integers(self.B)
            self.samples.append((fines[t, b].copy(), lat[t, b]))
        self.z, self.first = z_next, False
        jax.block_until_ready(self.params)
        print(f"control window: rollout {t1 - t0:.1f} s, scheduler "
              f"{t2 - t1:.1f} s, gradient and update "
              f"{time.perf_counter() - t2:.1f} s", file=sys.stderr, flush=True)

    def _adam(self, grads):
        import jax
        import jax.numpy as jnp
        opt, lr = self.config["optimizer"], self.policy["learning_rate"]
        bf = jnp.bfloat16
        self.k += 1
        g = jax.tree.map(lambda a: jnp.asarray(a, bf), grads)
        self.m = jax.tree.map(lambda m, g: (opt["b1"] * m
                                            + (1 - opt["b1"]) * g).astype(bf),
                              self.m, g)
        self.v = jax.tree.map(lambda v, g: (opt["b2"] * v
                                            + (1 - opt["b2"]) * g * g
                                            ).astype(bf), self.v, g)
        c1, c2 = 1 - opt["b1"] ** self.k, 1 - opt["b2"] ** self.k
        self.params = jax.tree.map(
            lambda p, m, v: (p - lr * (m / c1) / (jnp.sqrt(v / c2)
                                                  + opt["eps"])).astype(bf),
            self.params, self.m, self.v)

    def setup(self):
        from .drive_search import to_host
        for _ in range(self.search.traffic["check_windows"]):
            self.window(sample=self.search.traffic["sample_per_window"])
        self.params_checked = to_host(self.params)
        self.z_checked = self.z[None]

    def program_grads(self, rec, weights):
        """The control's replay of a recorded window, from the parameters
        and chain state it had before the window."""
        import jax.numpy as jnp
        grads, _, _ = reference_window(
            rec["params_before"], self.arrays, rec, weights, self.policy,
            dtype=jnp.bfloat16, block=self.search.traffic["reference_block"],
            z0=rec["z"][0])
        return grads


def _bf16_numpy():
    import ml_dtypes
    return ml_dtypes.bfloat16
