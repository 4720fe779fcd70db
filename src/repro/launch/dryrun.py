import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape) cell this lowers + compiles the
real train_step / prefill_step / serve_step on the production mesh
(single-pod 16x16 and multi-pod 2x16x16) using ShapeDtypeStruct inputs
(zero allocation), prints memory_analysis() and cost_analysis(), and
runs the loop-aware HLO roofline accounting (hlo_analysis.py).

Usage:
    python -m repro.launch.dryrun --arch internlm2-1.8b --shape train_4k
    python -m repro.launch.dryrun --arch all --multi-pod both \
        --out results/dryrun.json
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import LM_SHAPES, applicable_shapes
from repro.configs.registry import all_archs, get_config
from repro.core.platform import TARGET_DEVICE_KIND, device_peaks
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import transformer
from repro.models.schema import abstract_params, param_specs
from repro.sharding.partition import MeshContext, cache_spec_for, spec_for
from repro.training.step import (abstract_opt_state, batch_specs, input_specs,
                                 make_train_step, opt_state_specs)

# the modelled pod's chips (the host devices stand in for them)
_PEAKS = device_peaks(TARGET_DEVICE_KIND)
PEAK_FLOPS = _PEAKS["bf16_flops"]
HBM_BW = _PEAKS["hbm_bytes_per_s"]
ICI_BW = 50e9            # bytes/s per link (~3 links usable per axis hop)


def _named(tree, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def build_cell(arch: str, shape_name: str, mesh, cfg_overrides: dict | None = None):
    """-> (jitted_fn, example_abstract_args) for one cell."""
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    shape = LM_SHAPES[shape_name]
    ctx = MeshContext(mesh, profile=cfg.parallelism_profile)
    params_abs = abstract_params(cfg)
    pspecs = param_specs(cfg, mesh)
    batch_abs = input_specs(cfg, shape)
    bspecs = batch_specs(cfg, shape, mesh)

    meta = {"params_abs": params_abs, "pspecs": pspecs,
            "opt_abs": None, "ospecs": None}
    if shape.kind == "train":
        step_fn, opt = make_train_step(cfg, ctx)
        opt_abs = abstract_opt_state(cfg, opt)
        ospecs = opt_state_specs(cfg, opt, mesh)
        meta.update(opt_abs=opt_abs, ospecs=ospecs)
        jitted = jax.jit(
            step_fn,
            in_shardings=(_named(pspecs, mesh), _named(ospecs, mesh),
                          _named(bspecs, mesh)),
            out_shardings=(_named(pspecs, mesh), _named(ospecs, mesh), None),
            donate_argnums=(0, 1),
        )
        args = (params_abs, opt_abs, batch_abs)
    elif shape.kind == "prefill":
        def prefill_step(params, batch):
            return transformer.prefill(cfg, params, batch, ctx, max_len=shape.seq_len)
        jitted = jax.jit(prefill_step,
                         in_shardings=(_named(pspecs, mesh), _named(bspecs, mesh)))
        args = (params_abs, batch_abs)
    else:  # decode
        B = shape.global_batch
        cache_abs = transformer.init_cache(cfg, B, shape.seq_len, abstract=True)
        cspecs = _zip_tree(cache_abs, transformer.cache_logical_axes(cfg),
                           lambda leaf, ax: cache_spec_for(ax, leaf.shape, mesh))

        def serve_step(params, cache, tokens, pos):
            return transformer.decode_step(cfg, params, cache, tokens, pos, ctx)

        jitted = jax.jit(
            serve_step,
            in_shardings=(_named(pspecs, mesh), _named(cspecs, mesh),
                          _named(bspecs["tokens"], mesh), None),
            donate_argnums=(1,),
        )
        args = (params_abs, cache_abs, batch_abs["tokens"],
                jax.ShapeDtypeStruct((), jnp.int32))
        meta["cache_bytes"] = _local_bytes(cache_abs, cspecs, mesh)
    return jitted, args, cfg, shape, meta


def _zip_tree(a, b, f):
    """Zip two same-structured dict trees where b's leaves are tuples."""
    if isinstance(a, dict):
        return {k: _zip_tree(a[k], b[k], f) for k in a}
    return f(a, b)


def _local_bytes(abs_tree, spec_tree, mesh) -> float:
    """Exact per-device bytes of a sharded pytree."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    total = 0.0
    flat_a = jax.tree.leaves(abs_tree)
    flat_s = jax.tree.leaves(spec_tree, is_leaf=lambda x: isinstance(x, P))
    for a, s in zip(flat_a, flat_s):
        shards = 1
        for dim_spec in tuple(s):
            if dim_spec is None:
                continue
            for ax in (dim_spec if isinstance(dim_spec, tuple) else (dim_spec,)):
                shards *= sizes.get(ax, 1)
        total += a.size * a.dtype.itemsize / shards
    return total


def memory_estimate(cfg, shape, mesh, params_abs, pspecs, opt_abs=None,
                    ospecs=None) -> dict:
    """Analytic per-device HBM estimate for the TPU target (the CPU
    backend's temp_size is an upper bound: its buffer assignment does not
    alias checkpointed-scan buffers the way the TPU backend does)."""
    from repro.models.schema import decoder_period, slot_plan
    est = {"params": _local_bytes(params_abs, pspecs, mesh)}
    est["grads"] = est["params"]
    if opt_abs is not None:
        est["opt_state"] = _local_bytes(opt_abs, ospecs, mesh)
    if shape.kind == "train":
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dshards = sizes.get("data", 1) * sizes.get("pod", 1)
        b_loc = max(1, shape.global_batch // dshards)
        act = b_loc * shape.seq_len * cfg.d_model * 2  # bf16 layer input
        periods = cfg.num_layers // decoder_period(cfg)
        plan_len = len(slot_plan(cfg))
        # saved x per period + slot boundaries + ~4 live layer transients
        est["activations"] = act * (periods + plan_len + 4)
        # CE logits chunk (f32), vocab TP-sharded when divisible
        vshard = sizes.get("model", 1) if cfg.vocab_size % sizes.get("model", 1) == 0 else 1
        ls = cfg.loss_chunk or shape.seq_len
        est["logits"] = b_loc * ls * cfg.vocab_size * 4 / vshard
    est["total"] = float(sum(v for k, v in est.items() if k != "total"))
    return est


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             cfg_overrides: dict | None = None, verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    jitted, args, cfg, shape, meta = build_cell(arch, shape_name, mesh, cfg_overrides)
    lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    hlo = hlo_analysis.analyze(compiled.as_text())
    nchips = mesh.devices.size

    # roofline terms (per-device quantities; hlo shapes are post-SPMD)
    compute_s = hlo.flops / PEAK_FLOPS
    memory_s = 2.0 * hlo.hbm_bytes / HBM_BW    # x2: write traffic ~ read traffic
    collective_s = hlo.collective_wire_bytes / ICI_BW

    pc = cfg.param_count()
    model_flops_global = 6.0 * (pc["active"] - cfg.vocab_size * cfg.d_model) \
        * shape.tokens if shape.kind == "train" else \
        2.0 * (pc["active"] - cfg.vocab_size * cfg.d_model) * \
        (shape.tokens if shape.kind == "prefill" else shape.global_batch)
    model_flops_dev = model_flops_global / nchips

    rec = {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": nchips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "args_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "total_per_dev": mem.argument_size_in_bytes + mem.temp_size_in_bytes,
        },
        "memory_estimate": memory_estimate(
            cfg, shape, mesh, meta["params_abs"], meta["pspecs"],
            meta["opt_abs"], meta["ospecs"])
        | ({"cache": meta["cache_bytes"]} if "cache_bytes" in meta else {}),
        "xla_cost": {k: cost.get(k) for k in ("flops", "bytes accessed")}
        if isinstance(cost, dict) else {},
        "hlo": hlo.to_json(),
        "roofline": {
            "compute_s": compute_s, "memory_s": memory_s,
            "collective_s": collective_s,
            "dominant": max(
                [("compute", compute_s), ("memory", memory_s),
                 ("collective", collective_s)], key=lambda kv: kv[1])[0],
            "model_flops_per_dev": model_flops_dev,
            "useful_flops_ratio": model_flops_dev / hlo.flops if hlo.flops else 0.0,
            "roofline_fraction": model_flops_dev / PEAK_FLOPS
            / max(compute_s, memory_s, collective_s)
            if max(compute_s, memory_s, collective_s) > 0 else 0.0,
        },
        "params": pc,
        "ok": True,
    }
    if verbose:
        est = rec["memory_estimate"]
        print(f"[{arch} x {shape_name} @ {rec['mesh']}] "
              f"compile={t_compile:.0f}s "
              f"mem/dev={(rec['memory']['total_per_dev'])/2**30:.2f}GiB "
              f"(est {sum(v for k, v in est.items() if k != 'total')/2**30:.2f}GiB) "
              f"flops/dev={hlo.flops:.3e} "
              f"terms: C={compute_s*1e3:.1f}ms M={memory_s*1e3:.1f}ms "
              f"X={collective_s*1e3:.1f}ms -> {rec['roofline']['dominant']}"
              f" frac={rec['roofline']['roofline_fraction']:.2f}")
        print(f"  memory_analysis: {mem}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", choices=("no", "yes", "both"), default="no")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--override", default="", help="k=v,... ModelConfig overrides")
    args = ap.parse_args()

    archs = all_archs() if args.arch == "all" else args.arch.split(",")
    overrides = {}
    for kv in filter(None, args.override.split(",")):
        k, v = kv.split("=")
        overrides[k] = (int(v) if v.lstrip("-").isdigit() else
                        (v == "True" if v in ("True", "False") else v))

    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    results = []
    if out_path.exists():
        results = json.loads(out_path.read_text())

    def have(a, s, m):
        return any(r["arch"] == a and r["shape"] == s and r["mesh"] == m
                   and r.get("ok") for r in results)

    pods = {"no": [False], "yes": [True], "both": [False, True]}[args.multi_pod]
    for arch in archs:
        cfg = get_config(arch)
        shapes = applicable_shapes(cfg) if args.shape == "all" else args.shape.split(",")
        for shape_name in shapes:
            for mp in pods:
                mesh_name = "2x16x16" if mp else "16x16"
                if have(arch, shape_name, mesh_name) and not overrides:
                    print(f"skip cached {arch} x {shape_name} @ {mesh_name}")
                    continue
                try:
                    rec = run_cell(arch, shape_name, multi_pod=mp,
                                   cfg_overrides=overrides or None)
                except Exception as e:
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "ok": False,
                           "error": f"{type(e).__name__}: {e}"[:500]}
                results = [r for r in results
                           if not (r["arch"] == arch and r["shape"] == shape_name
                                   and r["mesh"] == mesh_name)]
                results.append(rec)
                out_path.write_text(json.dumps(results, indent=1))
    n_ok = sum(1 for r in results if r.get("ok"))
    print(f"\n{n_ok}/{len(results)} cells OK -> {out_path}")


if __name__ == "__main__":
    main()
