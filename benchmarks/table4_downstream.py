"""Paper Table 4: downstream model performance is unaffected by placement.

Two checks (mirroring §3.5):
  1. Real execution: the BERT benchmark graph is *actually executed* via
     MeasuredExecutor under CPU-only vs the HSDAG placement; final-op outputs
     are compared (MSE / cosine similarity / L2, the paper's metrics).
  2. Real model: a reduced LM runs unsharded vs GSPMD-sharded over every
     device the process sees; logits are compared — placement/sharding
     must not change numerics, and a mismatch fails the run.
"""
from __future__ import annotations

import numpy as np

from repro.core import extract_features, FeatureConfig
from repro.core.executor import MeasuredExecutor
from repro.graphs import bert_base

from common import emit, run_hsdag


def _final_outputs(executor: MeasuredExecutor, placement) -> np.ndarray:
    # run once and grab the terminal node's activation
    executor._run_once(np.asarray(placement))  # warm cache of weights
    outs = [None] * executor.graph.num_nodes
    import jax.numpy as jnp
    import jax
    for v in executor.order:
        v = int(v)
        dev_idx = int(placement[v]) % len(executor.devices)
        dev = executor.devices[dev_idx]
        m, k = executor._dims[v]
        w = executor._weight_on(m, k, dev_idx)
        acc = jnp.zeros((k,), jnp.float32, device=dev)
        for u in executor.preds[v]:
            x = outs[u]
            if x.devices() != {dev}:
                x = jax.device_put(x, dev)
            n = min(x.shape[0], k)
            acc = acc.at[:n].add(x[:n])
        outs[v] = executor._node_fn(w, acc)
    return np.asarray(outs[int(executor.order[-1])])


def main() -> None:
    g = bert_base()
    placement, lat, _ = run_hsdag(g, episodes=4)
    ex = MeasuredExecutor(g, warmup=1, timed=1)
    out_cpu = _final_outputs(ex, np.zeros(g.num_nodes, int))
    out_hsdag = _final_outputs(ex, placement)
    mse = float(np.mean((out_cpu - out_hsdag) ** 2))
    na, nb = np.linalg.norm(out_cpu), np.linalg.norm(out_hsdag)
    # identical zero vectors are perfectly similar (0/0 guard)
    cs = 1.0 if (na < 1e-12 and nb < 1e-12) else         float(np.dot(out_cpu, out_hsdag) / (na * nb + 1e-12))
    l2 = float(np.linalg.norm(out_cpu - out_hsdag))
    emit("table4_bert_cpu_vs_hsdag_exec", lat * 1e6,
         f"MSE={mse:.3e};CS={cs:.6f};L2={l2:.3e};paper:MSE=6.8e-07 CS=0.999")

    # sharded-vs-unsharded logits equivalence, on the devices this process
    # sees (a ("data", "model") mesh of all of them)
    import jax
    from jax.sharding import Mesh
    from repro.configs import get
    from repro.distributed.sharding import use_rules
    from repro.models import forward, init_params
    cfg = get("qwen1.5-0.5b").smoke_config
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                              cfg.vocab_size)
    base = np.asarray(forward(params, cfg, toks))
    devices = np.array(jax.devices())
    model = 2 if devices.size % 2 == 0 else 1
    mesh = Mesh(devices.reshape(-1, model), ("data", "model"))
    with use_rules(mesh, {}):
        sharded = jax.jit(lambda p, t: forward(p, cfg, t))(params, toks)
    err = float(np.max(np.abs(base - np.asarray(sharded))))
    if not err < 5e-4:
        raise RuntimeError(f"sharded logits differ from unsharded by {err}")
    emit("table4_sharded_vs_unsharded_logits", 0.0,
         f"max_abs_err={err};mesh={devices.size // model}x{model};"
         f"placement-invariant=True")


if __name__ == "__main__":
    main()
