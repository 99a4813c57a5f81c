"""Distribution tests needing >1 device run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count (set before jax init;
the main test process keeps 1 device, per DESIGN.md §8)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8, timeout: int = 360) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_sharding_rules_resolve():
    from repro.distributed.sharding import logical_spec, with_rules
    from jax.sharding import PartitionSpec
    rules = with_rules({"kv_heads": None})
    spec = logical_spec(("batch", "act_seq", "heads", "head_dim"), rules)
    assert spec == PartitionSpec(("pod", "data"), None, "model", None)
    spec2 = logical_spec(("batch", None, "kv_heads", None), rules)
    assert spec2 == PartitionSpec(("pod", "data"), None, None, None)


def test_use_rules_restores_on_exception():
    """The context restores mesh+rules even when the body raises."""
    from repro.distributed.sharding import current_mesh, use_rules
    from repro.distributed import sharding

    assert current_mesh() is None
    with pytest.raises(RuntimeError):
        with use_rules("outer-mesh", {"batch": "data"}):
            assert current_mesh() == "outer-mesh"
            with pytest.raises(RuntimeError):
                with use_rules("inner-mesh", {"batch": None}):
                    assert current_mesh() == "inner-mesh"
                    raise RuntimeError("inner boom")
            # inner context unwound cleanly, outer still active
            assert current_mesh() == "outer-mesh"
            assert sharding._CTX.rules["batch"] == "data"
            raise RuntimeError("outer boom")
    assert current_mesh() is None
    assert sharding._CTX.rules is None


def test_use_rules_bad_rules_leave_context_intact():
    """A rules mapping that explodes during merge must not half-activate."""
    from repro.distributed.sharding import current_mesh, use_rules

    from collections.abc import Mapping

    class BoomMapping(Mapping):
        def __getitem__(self, k):
            raise RuntimeError("bad rules")

        def __iter__(self):
            return iter(["batch"])

        def __len__(self):
            return 1

        def keys(self):
            raise RuntimeError("bad rules")

    with use_rules("outer-mesh"):
        with pytest.raises(RuntimeError, match="bad rules"):
            with use_rules("inner-mesh", BoomMapping()):
                pass                             # pragma: no cover
        assert current_mesh() == "outer-mesh"
    assert current_mesh() is None


def test_rollout_rules_resolve():
    from jax.sharding import PartitionSpec
    from repro.distributed.sharding import ROLLOUT_RULES, logical_spec
    spec = logical_spec(("time", "graphs", "chains"), ROLLOUT_RULES)
    assert spec == PartitionSpec(None, "graphs", "chains")


def test_mesh_axis_filtering():
    """'pod' is dropped when the mesh lacks that axis (single-pod mode)."""
    out = run_with_devices("""
        import jax, numpy as np
        from jax.sharding import Mesh, PartitionSpec
        from repro.distributed.sharding import logical_spec, with_rules
        mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
        spec = logical_spec(("batch", "act_embed"), with_rules({}), mesh)
        assert spec == PartitionSpec(("data",), None), spec
        print("OK")
    """, n=8)
    assert "OK" in out


def test_quantize_roundtrip_error_bound():
    import jax.numpy as jnp
    from repro.distributed.compression import dequantize_int8, quantize_int8
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(4096).astype(np.float32) * 3.0)
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s)) - np.asarray(x))
    # per-block max error ≤ scale/2 = amax/254
    blocks = np.asarray(x).reshape(-1, 256)
    bound = np.abs(blocks).max(1) / 254.0 + 1e-7
    assert np.all(err.reshape(-1, 256).max(1) <= bound * 1.01)


def test_compressed_psum_matches_mean():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.distributed.compression import compressed_psum_mean
        n_dev = 8
        mesh = Mesh(np.array(jax.devices()), ("data",))
        rng = np.random.default_rng(0)
        xs = rng.standard_normal((n_dev, 8 * 256 * n_dev)).astype(np.float32)

        def f(x):
            x = x.reshape(-1)
            return compressed_psum_mean(x, "data", n_dev)

        g = shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P(),
                      check_vma=False)
        got = np.asarray(g(jnp.asarray(xs)))
        want = xs.mean(0)
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        assert rel < 2e-2, rel         # int8 wire error
        print("OK", rel)
    """, n=8)
    assert "OK" in out


def test_compressed_allreduce_error_feedback_converges():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.distributed.compression import compressed_allreduce_tree
        n_dev = 4
        mesh = Mesh(np.array(jax.devices())[:4], ("data",))
        rng = np.random.default_rng(1)
        g_global = rng.standard_normal((4, 2048)).astype(np.float32)

        def f(x, err):
            grads = {"w": x.reshape(-1)}
            red, new_err = compressed_allreduce_tree(
                grads, "data", n_dev, err.reshape(-1))
            return red["w"], new_err.reshape(1, -1)

        fn = shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                       out_specs=(P(), P("data")), check_vma=False)
        err = jnp.zeros((4, 2048), jnp.float32)
        # accumulated mean over steps approaches the exact mean (EF property)
        acc = np.zeros(2048, np.float32)
        for step in range(8):
            red, err = fn(jnp.asarray(g_global), err)
            acc += np.asarray(red)
        want = g_global.mean(0) * 8
        rel = np.abs(acc - want).max() / np.abs(want).max()
        assert rel < 5e-3, rel        # EF drives accumulated error down
        print("OK", rel)
    """, n=4)
    assert "OK" in out


def test_pipeline_matches_sequential():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.distributed.pipeline import pipeline_apply
        S = 4
        mesh = Mesh(np.array(jax.devices())[:S], ("pod",))
        rng = jax.random.PRNGKey(0)
        d = 16
        # per-stage params: a dense layer each
        w = jax.random.normal(rng, (S, d, d)) / np.sqrt(d)

        def stage_fn(p, x):
            return jnp.tanh(x @ p)

        M = 6
        xs = jax.random.normal(jax.random.fold_in(rng, 1), (M, 3, d))
        out = pipeline_apply(stage_fn, w, xs, mesh=mesh, axis="pod")
        # sequential reference
        ref = xs
        for s in range(S):
            ref = jnp.tanh(ref @ w[s])
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 1e-5, err
        # gradients flow through the pipeline (backward = GPipe)
        def loss(w_):
            return jnp.sum(pipeline_apply(stage_fn, w_, xs, mesh=mesh,
                                          axis="pod") ** 2)
        def loss_ref(w_):
            r = xs
            for s in range(S):
                r = jnp.tanh(r @ w_[s])
            return jnp.sum(r ** 2)
        g1 = jax.grad(loss)(w)
        g2 = jax.grad(loss_ref)(w)
        gerr = float(jnp.max(jnp.abs(g1 - g2)))
        assert gerr < 1e-4, gerr
        print("OK", err, gerr)
    """, n=4)
    assert "OK" in out


def test_sharded_matmul_matches_dense():
    """shard_map TP matmul with psum == dense reference."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        mesh = Mesh(np.array(jax.devices()), ("model",))
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
        w = jax.random.normal(jax.random.PRNGKey(1), (64, 32))

        def f(x_l, w_l):     # x (4, 64/8), w (64/8, 32): contract + psum
            return jax.lax.psum(x_l @ w_l, "model")

        g = shard_map(f, mesh=mesh, in_specs=(P(None, "model"),
                                              P("model", None)),
                      out_specs=P())
        got = g(x, w)
        err = float(jnp.max(jnp.abs(got - x @ w)))
        assert err < 1e-4, err
        print("OK")
    """, n=8)
    assert "OK" in out


def test_straggler_watchdog():
    from repro.distributed import StragglerWatchdog
    wd = StragglerWatchdog(threshold=2.0, warmup_steps=2)
    for i in range(6):
        assert not wd.record(i, 1.0)
    assert wd.record(6, 5.0)          # 5× the EMA → flagged
    assert wd.flagged[0][0] == 6
    assert not wd.record(7, 1.0)      # baseline not poisoned


def test_choose_mesh_shape_shrinks_data_axis():
    from repro.distributed import choose_mesh_shape
    assert choose_mesh_shape(256, 16) == (16, 16)
    assert choose_mesh_shape(240, 16) == (15, 16)
    assert choose_mesh_shape(250, 16) == (125, 2)   # degrade model parallel
    assert choose_mesh_shape(7, 16) == (7, 1)
