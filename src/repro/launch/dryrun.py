"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination with ShapeDtypeStruct inputs (no allocation), and extract the
memory / cost / collective statistics the roofline analysis consumes.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both \
        --out results/dryrun

The XLA_FLAGS line below MUST run before any other jax-touching import.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.base import INPUT_SHAPES, ArchConfig, get_config, list_archs  # noqa: E402
from repro.launch import init_compile_cache  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import model as model_lib  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.models.schema import shape_tree  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.serve import serve_step as serve_lib  # noqa: E402
from repro.sharding import partition  # noqa: E402
from repro.train import train_step as ts_lib  # noqa: E402

SLOTS = 8

COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^\n=]*=?\s*\(?([a-z0-9]+)\[([0-9,]*)\]"
)

_DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
    "pred": 1, "s64": 8, "u64": 8, "f64": 8, "s16": 2, "u16": 2,
}


def input_specs(cfg: ArchConfig, shape_name: str, dp_groups: int) -> dict:
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    info = INPUT_SHAPES[shape_name]
    s, gb, kind = info["seq_len"], info["global_batch"], info["kind"]
    i32 = jnp.int32
    act = cfg.activation_dtype

    if kind == "train":
        mb_seqs = max(1, gb // (dp_groups * SLOTS))
        gmb = dp_groups * mb_seqs
        if cfg.modality == "vision_embeds":
            return {
                "embeds": jax.ShapeDtypeStruct((SLOTS, gmb, s, cfg.d_model), act),
                "positions": jax.ShapeDtypeStruct((3, gmb, s), i32),
                "labels": jax.ShapeDtypeStruct((SLOTS, gmb, s), i32),
            }
        if cfg.modality == "audio_codes":
            return {
                "tokens": jax.ShapeDtypeStruct((SLOTS, gmb, s, cfg.num_codebooks), i32),
                "labels": jax.ShapeDtypeStruct((SLOTS, gmb, s, cfg.num_codebooks), i32),
            }
        return {
            "tokens": jax.ShapeDtypeStruct((SLOTS, gmb, s), i32),
            "labels": jax.ShapeDtypeStruct((SLOTS, gmb, s), i32),
        }

    if kind == "prefill":
        if cfg.modality == "vision_embeds":
            return {
                "embeds": jax.ShapeDtypeStruct((gb, s, cfg.d_model), act),
                "positions": jax.ShapeDtypeStruct((3, gb, s), i32),
            }
        if cfg.modality == "audio_codes":
            return {"tokens": jax.ShapeDtypeStruct((gb, s, cfg.num_codebooks), i32)}
        return {"tokens": jax.ShapeDtypeStruct((gb, s), i32)}

    # decode: one new token + caches of length s.
    if cfg.modality == "vision_embeds":
        tok = jax.ShapeDtypeStruct((gb, 1, cfg.d_model), act)
    elif cfg.modality == "audio_codes":
        tok = jax.ShapeDtypeStruct((gb, 1, cfg.num_codebooks), i32)
    else:
        tok = jax.ShapeDtypeStruct((gb, 1), i32)
    return {
        "tokens": tok,
        "caches": transformer.cache_shapes(cfg, gb, s),
        "pos": jax.ShapeDtypeStruct((), i32),
    }


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum result-operand sizes of collective ops in the (post-SPMD) HLO."""
    out: dict[str, float] = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        op, dtype, dims = m.group(1), m.group(2), m.group(3)
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[op] = out.get(op, 0.0) + n * _DTYPE_BYTES[dtype]
    return out


#: set False (--baseline-sharding) to reproduce the pre-optimization
#: replicated-KV-cache baseline recorded in EXPERIMENTS.md §Perf.
SEQ_SHARD_CACHES = True


#: serve-time FSDP threshold: if the model-axis param shard alone exceeds
#: this, weights are additionally sharded over the DP axes (gathered at use).
FSDP_SERVE_BYTES = 12 * 2**30


def lower_one(cfg: ArchConfig, shape_name: str, mesh) -> tuple:
    """Build the jitted step + abstract args for one combination."""
    kind = INPUT_SHAPES[shape_name]["kind"]
    dp = partition.mesh_axis_size(mesh, partition.batch_axes(mesh))
    pspecs = partition.param_specs(cfg, mesh)
    if SEQ_SHARD_CACHES:
        tp = partition.mesh_axis_size(mesh, "model")
        resident = cfg.total_params() * 2 / max(tp, 1)
        if resident > FSDP_SERVE_BYTES:
            # Serve: weights gathered per period. Train: full FSDP — params,
            # grads and (via zero1) moments shard over the DP axes too;
            # jamba-398B's 72 GiB/dev train footprint is infeasible otherwise.
            pspecs = partition.fsdp_param_specs(cfg, mesh)
    pshapes = model_lib.param_shapes(cfg)
    nshard = lambda t: jax.tree.map(  # noqa: E731
        lambda s: NamedSharding(mesh, s), t, is_leaf=lambda x: isinstance(x, P)
    )

    if kind == "train":
        opt_cfg = adamw.AdamWConfig()
        step = ts_lib.make_train_step(cfg, opt_cfg)
        batch = input_specs(cfg, shape_name, dp)
        ospecs = adamw.opt_state_specs(pspecs, pshapes, mesh)
        opt_shapes = adamw.AdamWState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            mu=jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), pshapes
            ),
            nu=jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), pshapes
            ),
        )
        in_shardings = (
            nshard(pspecs),
            nshard(ospecs),
            nshard(partition.train_batch_specs(cfg, mesh)),
        )
        jitted = jax.jit(step, in_shardings=in_shardings)
        args = (pshapes, opt_shapes, batch)
    elif kind == "prefill":
        gb = INPUT_SHAPES[shape_name]["global_batch"]
        step = serve_lib.make_prefill_step(cfg, INPUT_SHAPES[shape_name]["seq_len"])
        batch = input_specs(cfg, shape_name, dp)
        in_shardings = (
            nshard(pspecs),
            nshard(partition.serve_batch_specs(cfg, mesh, gb)),
        )
        jitted = jax.jit(step, in_shardings=in_shardings)
        args = (pshapes, batch)
    else:  # decode
        gb = INPUT_SHAPES[shape_name]["global_batch"]
        s = INPUT_SHAPES[shape_name]["seq_len"]
        step = serve_lib.make_decode_step(cfg, s)
        spec = input_specs(cfg, shape_name, dp)
        in_shardings = (
            nshard(pspecs),
            NamedSharding(mesh, partition.decode_token_specs(cfg, mesh, gb)),
            nshard(partition.cache_specs(cfg, mesh, gb, seq_shard=SEQ_SHARD_CACHES)),
            NamedSharding(mesh, P()),
        )
        # Donate the KV caches: the functional cache update would otherwise
        # hold old + new cache simultaneously (§Perf iteration 2).
        jitted = jax.jit(step, in_shardings=in_shardings, donate_argnums=(2,))
        args = (pshapes, spec["tokens"], spec["caches"], spec["pos"])
    return jitted, args


def dryrun(arch: str, shape_name: str, multi_pod: bool) -> dict:
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.monotonic()
    # jax.set_mesh (not the bare `with mesh:`) so the abstract mesh is
    # visible at trace time — the expert-parallel MoE path reads it.
    with jax.set_mesh(mesh):
        jitted, args = lower_one(cfg, shape_name, mesh)
        lowered = jitted.lower(*args)
        t_lower = time.monotonic() - t0
        t0 = time.monotonic()
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    n_dev = mesh.devices.size
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(n_dev),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "bytes_per_device": {
            "argument": getattr(mem, "argument_size_in_bytes", 0),
            "output": getattr(mem, "output_size_in_bytes", 0),
            "temp": getattr(mem, "temp_size_in_bytes", 0),
            "peak": getattr(mem, "peak_memory_in_bytes", 0),
        },
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    }
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["off", "on", "both"], default="off")
    ap.add_argument("--out", default=None, help="directory for JSON results")
    ap.add_argument(
        "--baseline-sharding", action="store_true",
        help="disable beyond-paper sharding optimizations (EXPERIMENTS §Perf)",
    )
    args = ap.parse_args()
    init_compile_cache()
    if args.baseline_sharding:
        global SEQ_SHARD_CACHES
        SEQ_SHARD_CACHES = False

    archs = list_archs() if args.all else [args.arch]
    archs = [a for a in archs if a and a != "falcon-demo-100m"]
    shapes = list(INPUT_SHAPES) if args.all or not args.shape else [args.shape]
    pods = {"off": [False], "on": [True], "both": [False, True]}[args.multi_pod]

    failures = []
    for arch in archs:
        for shape_name in shapes:
            for mp in pods:
                tag = f"{arch} x {shape_name} x {'2x16x16' if mp else '16x16'}"
                try:
                    res = dryrun(arch, shape_name, mp)
                except Exception as e:  # noqa: BLE001
                    print(f"FAIL {tag}: {e}")
                    traceback.print_exc()
                    failures.append(tag)
                    continue
                print(
                    f"OK {tag}: flops={res['flops']:.3e} "
                    f"peak/dev={res['bytes_per_device']['peak']/2**30:.2f}GiB "
                    f"compile={res['compile_s']}s"
                )
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    fn = f"{arch}__{shape_name}__{res['mesh'].replace('x','_')}.json"
                    with open(os.path.join(args.out, fn), "w") as f:
                        json.dump(res, f, indent=1)
    if failures:
        print(f"\n{len(failures)} FAILURES:\n" + "\n".join(failures))
        sys.exit(1)
    print("\nALL DRY-RUNS PASSED")


if __name__ == "__main__":
    main()
