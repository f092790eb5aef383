import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x shape) cell on
the production mesh and extract roofline inputs from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]

Per cell this produces experiments/dryrun/<arch>__<shape>__<mesh>.json with
  * memory_analysis (bytes per device: argument/output/temp/peak) — fits?
  * cost_analysis   (per-device HLO FLOPs + bytes accessed)
  * collective_bytes by op kind, parsed from the optimized HLO
  * MODEL_FLOPS and useful-FLOPs ratio
which benchmarks/roofline.py turns into the three roofline terms.

The two os.environ lines above MUST precede any jax import: jax locks the
device count at first backend initialization.
"""

import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (ARCH_IDS, SHAPES, get_arch, shape_applicable)
from repro.distributed import sharding as shd
from repro.launch import hlo_analysis
from repro.launch import mesh as mesh_lib
from repro.models import api
from repro.train import optimizer as opt_lib

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")


# ---------------------------------------------------------------------------
# Cell construction: the function to lower + its input shardings
# ---------------------------------------------------------------------------

def _dp_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _batch_shardings(cfg, shape, mesh, recipe):
    """NamedShardings for the input_specs pytree."""
    dp = _dp_axes(mesh)
    dp = dp if len(dp) > 1 else (dp[0] if dp else None)
    seq_ax = "model" if recipe == "train" else None

    def spec_for(path, leaf):
        name = path[-1] if path else ""
        nd = len(leaf.shape)
        B = shape.global_batch
        bdim = dp if (B % _prod(mesh, dp) == 0) else None
        if name == "tokens":
            return P(bdim, None) if nd == 2 else P(bdim)
        if name == "positions":
            return P(None, bdim, seq_ax)
        if name in ("vision_embeds", "src_embeds"):
            return P(bdim, seq_ax, None)
        if name == "vision_mask":
            return P(bdim, seq_ax)
        if name == "cache_len":
            return P()
        return P(*([None] * nd))

    def rec(tree, path=()):
        if isinstance(tree, dict):
            return {k: rec(v, path + (k,)) for k, v in tree.items()}
        return NamedSharding(mesh, spec_for(path, tree))

    return rec


def _prod(mesh, axes):
    if axes is None:
        return 1
    axes = (axes,) if isinstance(axes, str) else axes
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in axes:
        n *= shape[a]
    return n


def _cache_shardings(cfg, shape, mesh, recipe):
    """Shardings for the decode cache pytree by family."""
    rules = shd.ACTIVATION_RULES[recipe]
    dp = _dp_axes(mesh)

    def resolve(logical, dim):
        axes = tuple(a for a in rules.get(logical, ()) if a in mesh.axis_names)
        if not axes or dim % _prod(mesh, axes) != 0:
            return None
        return axes if len(axes) > 1 else axes[0]

    def leaf_spec(key, leaf):
        nd = len(leaf.shape)
        if key in ("k_scale", "v_scale"):   # (L, B, T, KH)
            return P(None, resolve("batch", leaf.shape[1]),
                     resolve("cache_seq", leaf.shape[2]), None)
        if key in ("k", "v", "cross_k", "cross_v"):
            # (L, B, T, KH, Dh)
            return P(None, resolve("batch", leaf.shape[1]),
                     resolve("cache_seq", leaf.shape[2]), None, None)
        if key == "wkv":      # (L, B, H, N, N)
            return P(None, resolve("batch", leaf.shape[1]),
                     resolve("heads", leaf.shape[2]), None, None)
        if key in ("x_tm", "x_cm"):   # (L, B, D)
            return P(None, resolve("batch", leaf.shape[1]),
                     resolve("ffn", leaf.shape[2]))
        if key == "ssm":      # (n_sup, ae, B, H, P, N)
            return P(None, None, resolve("batch", leaf.shape[2]),
                     resolve("heads", leaf.shape[3]), None, None)
        if key == "conv":     # (n_sup, ae, B, W-1, conv_dim)
            return P(None, None, resolve("batch", leaf.shape[2]), None,
                     resolve("ffn", leaf.shape[4]))
        return P(*([None] * nd))

    return {k: NamedSharding(mesh, leaf_spec(k, v))
            for k, v in cache_specs_of(cfg, shape).items()}


def cache_specs_of(cfg, shape):
    return api.cache_specs(cfg, shape.global_batch, shape.seq_len)


def build_cell(arch_id: str, shape_name: str, mesh, variant: str = ""):
    """Returns (fn, arg_specs, recipe).

    Variants (EXPERIMENTS.md §Perf):
      ``int8``      serve with pre-quantized int8 dense weights (W8A8,
                    BitParticle-exact numerics) — memory + compute terms.
      ``q8gather``  train with int8-quantized FSDP weight gathers (STE) —
                    collective term.
    """
    cfg = get_arch(arch_id)
    if variant == "q8gather":
        cfg = cfg.replace(matmul_mode=cfg.matmul_mode + "+q8gather")
    if variant == "int8kv":
        cfg = cfg.replace(kv_cache_int8=True)
    shape = SHAPES[shape_name]
    specs = api.input_specs(cfg, shape)
    key = jax.random.PRNGKey(0)
    param_specs = jax.eval_shape(partial(api.init, cfg=cfg), key)
    if variant in ("int8", "int8kv") and shape.kind != "train":
        from repro.models.layers import quantize_dense_params
        param_specs = quantize_dense_params(param_specs)
        cfg = cfg.replace(matmul_mode="bp_exact")

    if shape.kind == "train":
        recipe = "train"
        opt_specs = jax.eval_shape(opt_lib.init_state, param_specs)
        p_sh = shd.named_shardings(param_specs, "train", mesh)
        o_sh = shd.named_shardings(opt_specs, "train", mesh)
        b_sh = jax.tree.map(lambda *_: None, specs)   # placeholder
        b_sh = _batch_shardings(cfg, shape, mesh, recipe)(specs)
        opt_cfg = opt_lib.OptimizerConfig()

        def train_step(params, opt_state, batch):
            with shd.recipe("train"):
                (loss, metrics), grads = jax.value_and_grad(
                    lambda p: api.loss_fn(p, cfg, batch), has_aux=True)(params)
                params, opt_state, om = opt_lib.apply_updates(
                    opt_cfg, params, opt_state, grads)
                return params, opt_state, {"loss": loss, **om}

        args = (param_specs, opt_specs, specs)
        in_sh = (p_sh, o_sh, b_sh)
        out_sh = (p_sh, o_sh,
                  {"loss": NamedSharding(mesh, P()),
                   "lr": NamedSharding(mesh, P()),
                   "grad_norm": NamedSharding(mesh, P())})
        fn = jax.jit(train_step, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=(0, 1))
        return fn, args, recipe

    if shape.kind == "prefill":
        recipe = "train"  # prefill shares the sequence-parallel recipe
        p_sh = shd.named_shardings(param_specs, "serve", mesh)
        b_sh = _batch_shardings(cfg, shape, mesh, recipe)(specs)
        cache_sh = _cache_shardings(cfg, shape, mesh, "decode")

        def prefill_step(params, batch):
            with shd.recipe("train"):
                return api.prefill(params, cfg, batch, shape.seq_len)

        args = (param_specs, specs)
        fn = jax.jit(prefill_step, in_shardings=(p_sh, b_sh))
        return fn, args, recipe

    # decode
    recipe = "decode_long" if shape.global_batch == 1 else "decode"
    p_sh = shd.named_shardings(param_specs, "serve", mesh)
    b_sh = dict(_batch_shardings(cfg, shape, mesh, recipe)(
        {"tokens": specs["tokens"], "cache_len": specs["cache_len"]}))
    b_sh["cache"] = _cache_shardings(cfg, shape, mesh, recipe)

    def serve_step(params, batch):
        with shd.recipe(recipe):
            return api.decode_step(params, cfg, batch)

    args = (param_specs, specs)
    # donate the batch (i.e. the KV/state cache): the updated cache aliases
    # the input buffers instead of materializing a second full cache
    fn = jax.jit(serve_step, in_shardings=(p_sh, b_sh),
                 donate_argnums=(1,))
    return fn, args, recipe


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------

def run_cell(arch_id: str, shape_name: str, multi_pod: bool, out_dir: str,
             verbose: bool = True, variant: str = ""):
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    arch_tag = f"{arch_id}@{variant}" if variant else arch_id
    tag = f"{arch_tag}__{shape_name}__{mesh_name}"
    path = os.path.join(out_dir, tag + ".json")
    os.makedirs(out_dir, exist_ok=True)
    cfg = get_arch(arch_id)
    shape = SHAPES[shape_name]
    record = {"arch": arch_tag, "shape": shape_name, "mesh": mesh_name,
              "base_arch": arch_id, "variant": variant, "ok": False}
    t0 = time.time()
    try:
        mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
        with jax.set_mesh(mesh):
            fn, args, recipe = build_cell(arch_id, shape_name, mesh, variant)
            lowered = fn.lower(*args)
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = hlo_analysis.analyze(compiled.as_text())
        record.update({
            "ok": True,
            "recipe": recipe,
            "lower_s": round(t_lower - t0, 2),
            "compile_s": round(t_compile - t_lower, 2),
            "n_devices": 512 if multi_pod else 256,
            # cost_analysis counts while bodies once — kept for reference;
            # the roofline uses the trip-count-aware HLO-derived numbers
            "xla_cost_flops_per_device": cost.get("flops", -1.0),
            "xla_cost_bytes_per_device": cost.get("bytes accessed", -1.0),
            "dot_flops_per_device": hlo["dot_flops_per_device"],
            "dot_flops_int_per_device": hlo["dot_flops_int_per_device"],
            "while_loops": hlo["while_loops"],
            "memory_analysis": {
                "argument_size": getattr(mem, "argument_size_in_bytes", None),
                "output_size": getattr(mem, "output_size_in_bytes", None),
                "temp_size": getattr(mem, "temp_size_in_bytes", None),
                "generated_code_size": getattr(
                    mem, "generated_code_size_in_bytes", None),
                "peak_memory": getattr(mem, "peak_memory_in_bytes", None),
            },
            "collective_bytes": hlo["collective_bytes"],
            "collective_counts": hlo["collective_counts"],
            "top_collectives": hlo["top_collectives"],
            "model_flops_global": api.model_flops(cfg, shape),
        })
    except Exception as e:  # noqa: BLE001 — record failures as artifacts
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    record["total_s"] = round(time.time() - t0, 2)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    if verbose:
        status = "OK " if record["ok"] else "FAIL"
        print(f"[{status}] {tag}  ({record['total_s']}s)", flush=True)
        if record["ok"]:
            ma = record["memory_analysis"]
            peak = (ma.get("peak_memory") or 0) / 2**30
            print(f"       dot_flops/dev={record['dot_flops_per_device']:.3e}  "
                  f"peak_mem/dev={peak:.2f}GiB  "
                  f"coll_bytes={sum(record['collective_bytes'].values()):.3e}",
                  flush=True)
        else:
            print("       " + record["error"].splitlines()[0], flush=True)
    return record


def all_cells():
    for aid in ARCH_IDS:
        arch = get_arch(aid)
        for sname in SHAPES:
            if shape_applicable(arch, SHAPES[sname]):
                yield aid, sname


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--variant", default="")
    ap.add_argument("--out", default=os.path.abspath(DEFAULT_OUT))
    args = ap.parse_args()

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]
    cells = list(all_cells()) if args.all else [(args.arch, args.shape)]
    n_fail = 0
    for aid, sname in cells:
        for mp in meshes:
            mesh_name = "pod2x16x16" if mp else "pod16x16"
            path = os.path.join(args.out, f"{aid}__{sname}__{mesh_name}.json")
            if args.skip_existing and os.path.exists(path):
                with open(path) as f:
                    if json.load(f).get("ok"):
                        print(f"[SKIP] {aid}__{sname}__{mesh_name}", flush=True)
                        continue
            rec = run_cell(aid, sname, mp, args.out, variant=args.variant)
            n_fail += 0 if rec["ok"] else 1
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
