"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape × mesh) cell:

    with mesh:
        lowered  = jax.jit(step, in_shardings=…, out_shardings=…) \
                       .lower(**input_specs(arch))
        compiled = lowered.compile()
        compiled.memory_analysis()   # proves it fits
        compiled.cost_analysis()     # FLOPs/bytes for §Roofline

must succeed on the 16×16 (256-chip) single-pod mesh AND the 2×16×16
(512-chip) multi-pod mesh.  Inputs and parameters are ShapeDtypeStructs —
no allocation happens for the 398B-parameter configs.

Each cell is lowered twice with identical math:
  * scanned layers  — the production program; its memory_analysis is the
    "fits on chip" evidence (scan reuses one block's buffers);
  * unrolled layers — for cost_analysis + collective bytes: XLA costs a
    scan body ONCE (not × trip count), so totals need the unrolled module.

Collective bytes (not in cost_analysis) are extracted from the optimized
HLO text by launch/roofline.py.

Usage:
    python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k
    python -m repro.launch.dryrun --arch … --shape … --multi-pod
    python -m repro.launch.dryrun --all --out results/dryrun [--jobs 2]
"""
import argparse
import os
import dataclasses
import json
import sys
import time
from typing import Dict, Optional

__all__ = ["run_cell", "main"]


def _rules_for(arch: str, shape_name: str, multi_pod: bool) -> Dict:
    """Per-arch overrides + per-shape adjustments (see configs/<arch>.py)."""
    from ..configs import SHAPES, get
    spec = get(arch)
    cfg = spec.config
    rules = dict(spec.rules)
    shp = SHAPES[shape_name]
    if cfg.fsdp and shp.kind == "train":
        # ZeRO-3 over the data axis — training only: gathering params every
        # serve step costs ~params/model_shards of wire per token
        # (EXPERIMENTS.md §Perf B1); serving keeps params model-sharded
        # and resident.
        rules.setdefault("embed", "data")
    if shp.global_batch == 1:
        rules["batch"] = None                    # long_500k: nothing to split
    if shp.kind == "decode" and rules.get("kv_heads", "model") is None:
        # KV heads are replicated (e.g. kv=8 < model=16): shard the cache's
        # sequence dim over "model" instead (flash-decoding style) — or the
        # 32k/500k caches exceed per-chip HBM.
        rules["cache_seq"] = "model"
    return rules


def _lower_cell(cfg, shp, cell, mesh, rules, in_sharding_for):
    """Build + lower the right step function for this shape kind."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from ..distributed.sharding import param_specs, use_rules
    from ..models import abstract_params, make_train_step, param_axes
    from ..models import lm as lm_mod
    from ..optim import adamw
    from ..optim.adamw import OptState

    params_abs = abstract_params(cfg)
    p_specs = param_specs(param_axes(cfg), mesh, rules)
    if cfg.quantize_weights and shp.kind != "train":
        from ..models.quantize import quantize_params, quantize_spec_tree
        p_specs = quantize_spec_tree(params_abs, p_specs, mesh)
        params_abs = jax.eval_shape(
            lambda p: quantize_params(p, cfg), params_abs)
    repl = NamedSharding(mesh, PartitionSpec())

    with use_rules(mesh, rules):
        if shp.kind == "train":
            opt = adamw(1e-4, state_dtype=(
                jnp.bfloat16 if cfg.optimizer_state_dtype == "bfloat16"
                else jnp.float32))
            train_step = make_train_step(cfg, opt)
            opt_abs = jax.eval_shape(opt.init, params_abs)
            opt_specs = OptState(step=repl, mu=p_specs, nu=p_specs)
            state_abs = lm_mod.TrainState(
                params_abs, opt_abs, jax.ShapeDtypeStruct((), jnp.int32))
            state_specs = lm_mod.TrainState(p_specs, opt_specs, repl)
            batch_abs = dict(cell["specs"])
            batch_specs = {k: in_sharding_for(cell["axes"][k])
                           for k in batch_abs}
            metric_specs = {"loss": repl, "grad_norm": repl, "step": repl}
            return jax.jit(
                train_step,
                in_shardings=(state_specs, batch_specs),
                out_shardings=(state_specs, metric_specs),
            ).lower(state_abs, batch_abs)

        if shp.kind == "prefill":
            def prefill_step(params, tokens, vision_embeds=None):
                return lm_mod.prefill(params, cfg, tokens,
                                      vision_embeds=vision_embeds)
            specs_in = [p_specs, in_sharding_for(cell["axes"]["tokens"])]
            args = [params_abs, cell["specs"]["tokens"]]
            if "vision_embeds" in cell["specs"]:
                specs_in.append(
                    in_sharding_for(cell["axes"]["vision_embeds"]))
                args.append(cell["specs"]["vision_embeds"])
            return jax.jit(prefill_step,
                           in_shardings=tuple(specs_in)).lower(*args)

        # decode
        def serve_step(params, tokens, caches, index):
            return lm_mod.decode_step(params, cfg, tokens, caches, index)

        is_axes_leaf = lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x)
        cache_specs = jax.tree.map(in_sharding_for, cell["axes"]["caches"],
                                   is_leaf=is_axes_leaf)
        return jax.jit(
            serve_step,
            in_shardings=(p_specs,
                          in_sharding_for(cell["axes"]["tokens"]),
                          cache_specs, repl),
        ).lower(params_abs, cell["specs"]["tokens"],
                cell["specs"]["caches"], cell["specs"]["index"])


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             extra_rules: Optional[Dict] = None,
             save_hlo: Optional[str] = None,
             skip_unrolled: bool = False,
             config_overrides: Optional[Dict] = None) -> Dict:
    from ..configs import get
    from ..configs.registry import input_specs_for
    from ..distributed.sharding import logical_spec, with_rules
    from .mesh import make_production_mesh
    from .roofline import collective_bytes_from_hlo
    from jax.sharding import NamedSharding
    import jax

    t0 = time.time()
    spec = get(arch)
    if config_overrides:
        spec = dataclasses.replace(
            spec, config=dataclasses.replace(spec.config, **config_overrides))
    if shape_name in spec.skip:
        return {"arch": arch, "shape": shape_name,
                "skipped": spec.skip[shape_name]}
    cell = input_specs_for(spec.config, shape_name)
    shp = cell["shape"]
    rules = _rules_for(arch, shape_name, multi_pod)
    if extra_rules:
        rules.update(extra_rules)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules_full = with_rules(rules)

    def in_sharding_for(axes):
        return NamedSharding(mesh, logical_spec(axes, rules_full, mesh))

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "num_devices": 512 if multi_pod else 256,
        "params": spec.config.num_params(),
        "active_params": spec.config.active_params(),
        "rules": {k: (list(v) if isinstance(v, tuple) else v)
                  for k, v in rules.items()},
    }

    with mesh:
        # 1. scanned (production) program → memory analysis
        t = time.time()
        lowered = _lower_cell(spec.config, shp, cell, mesh, rules,
                              in_sharding_for)
        compiled = lowered.compile()
        result["compile_scanned_s"] = round(time.time() - t, 2)
        mem = compiled.memory_analysis()
        result["memory"] = {
            k: getattr(mem, k, None) for k in (
                "temp_size_in_bytes", "argument_size_in_bytes",
                "output_size_in_bytes", "alias_size_in_bytes")} \
            if mem is not None else None

        # 2. cost accounting.  XLA costs a scan body once (not × trip count)
        # and fully unrolling 56–80 layers is compile-prohibitive, so lower
        # *unrolled* variants at 1 and 2 pattern-repeats and extrapolate
        # linearly: X(R) = X(1) + (R−1)·(X(2)−X(1)).  Exact for the
        # layer-homogeneous stacks used here (per-repeat cost is constant);
        # the R=1 program carries all boundary costs (embedding, loss,
        # optimizer, gradient collectives on non-block params).
        if skip_unrolled:
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
            result["flops"] = cost.get("flops") if cost else None
            result["bytes_accessed"] = (cost.get("bytes accessed")
                                        if cost else None)
            result["collectives"] = collective_bytes_from_hlo(hlo)
            result["cost_source"] = "scanned (loop bodies counted once)"
        else:
            from ..configs.registry import input_specs_for
            pat = len(spec.config.block_pattern)
            reps = spec.config.pattern_repeats
            samples = {}
            for r in (1, 2):
                t = time.time()
                cfg_r = dataclasses.replace(
                    spec.config, n_layers=r * pat, scan_layers=False)
                cell_r = input_specs_for(cfg_r, shape_name)
                lowered_r = _lower_cell(cfg_r, shp, cell_r, mesh, rules,
                                        in_sharding_for)
                compiled_r = lowered_r.compile()
                cost_r = compiled_r.cost_analysis()
                hlo_r = compiled_r.as_text()
                samples[r] = {
                    "flops": cost_r.get("flops", 0.0),
                    "bytes_accessed": cost_r.get("bytes accessed", 0.0),
                    "collectives": collective_bytes_from_hlo(hlo_r),
                    "compile_s": round(time.time() - t, 2),
                }
                if save_hlo and r == 2:
                    with open(save_hlo, "w") as f:
                        f.write(hlo_r)

            def extrap(key):
                x1, x2 = samples[1][key], samples[2][key]
                return x1 + (reps - 1) * (x2 - x1)

            result["flops"] = extrap("flops")
            result["bytes_accessed"] = extrap("bytes_accessed")
            c1 = samples[1]["collectives"]
            c2 = samples[2]["collectives"]
            coll = {}
            for k in c1:
                if k == "counts":
                    coll[k] = {op: int(c1[k][op] +
                                       (reps - 1) * (c2[k][op] - c1[k][op]))
                               for op in c1[k]}
                else:
                    coll[k] = c1[k] + (reps - 1) * (c2[k] - c1[k])
            result["collectives"] = coll
            result["cost_source"] = f"extrapolated R1/R2 → R={reps}"
            result["cost_samples"] = {
                str(r): {k: v for k, v in s.items() if k != "collectives"}
                for r, s in samples.items()}

    result["total_s"] = round(time.time() - t0, 2)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--jobs", type=int, default=1)
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--skip-unrolled", action="store_true",
                    help="fast mode: cost from the scanned program")
    ap.add_argument("--rules", default=None,
                    help="JSON dict of extra logical→mesh rule overrides "
                         "(§Perf iterations), e.g. '{\"res_seq\": \"model\"}'")
    ap.add_argument("--tag", default=None,
                    help="suffix for the output JSON (perf variants)")
    args = ap.parse_args(argv)

    if args.all:
        return _run_all(args.out, jobs=args.jobs,
                        skip_unrolled=args.skip_unrolled)

    extra = json.loads(args.rules) if args.rules else None
    res = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   save_hlo=args.save_hlo, skip_unrolled=args.skip_unrolled,
                   extra_rules=extra)
    print(json.dumps(res, indent=2, default=str))
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        tag = f"__{args.tag}" if args.tag else ""
        name = f"{args.arch}__{args.shape}__{res.get('mesh', 'skip')}{tag}.json"
        with open(os.path.join(args.out, name), "w") as f:
            json.dump(res, f, indent=2, default=str)
    return 0


def _run_all(out_dir: str, jobs: int = 1, skip_unrolled: bool = False) -> int:
    """Drive every (arch × shape × mesh) cell in worker subprocesses."""
    import subprocess
    from ..configs import SHAPES, all_archs, get

    os.makedirs(out_dir, exist_ok=True)
    cells = []
    for arch in all_archs():
        spec = get(arch)
        for shape in SHAPES:
            if shape in spec.skip:
                path = os.path.join(out_dir, f"{arch}__{shape}__skip.json")
                with open(path, "w") as f:
                    json.dump({"arch": arch, "shape": shape,
                               "skipped": spec.skip[shape]}, f, indent=2)
                continue
            for multi in (False, True):
                mesh = "2x16x16" if multi else "16x16"
                path = os.path.join(out_dir, f"{arch}__{shape}__{mesh}.json")
                if os.path.exists(path):
                    continue
                cells.append((arch, shape, multi, path))

    running = []
    failures = []

    def _drain(block_all=False):
        while running and (block_all or len(running) >= jobs):
            done = None
            for i, (proc, meta, log) in enumerate(running):
                if proc.poll() is not None:
                    done = i
                    break
            if done is None:
                time.sleep(2.0)
                continue
            proc, meta, log = running.pop(done)
            log.close()
            if proc.returncode != 0:
                failures.append(meta)
                print(f"FAIL {meta}", flush=True)
            else:
                print(f"ok   {meta}", flush=True)

    for arch, shape, multi, path in cells:
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape, "--out", out_dir]
        if multi:
            cmd.append("--multi-pod")
        if skip_unrolled or multi:
            # the multi-pod pass proves the pod axis shards; flop accounting
            # (single-pod only per §Roofline) doesn't need its unrolled build
            cmd.append("--skip-unrolled")
        logf = open(path.replace(".json", ".log"), "w")
        proc = subprocess.Popen(cmd, stdout=logf, stderr=subprocess.STDOUT)
        running.append((proc, (arch, shape, multi), logf))
        _drain()
    _drain(block_all=True)
    print(f"done; {len(failures)} failures")
    for f in failures:
        print("  FAIL:", f)
    return 1 if failures else 0


if __name__ == "__main__":
    # 512 virtual host devices stand in for the pod meshes; set before
    # jax initializes its backend, and only when run as a script.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    sys.exit(main())
