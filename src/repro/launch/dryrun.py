import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST precede any jax import: jax locks the device count on first init.
# This module is the ONLY place the 512-device placeholder topology exists;
# tests/benches import repro.* normally and see the real 1-CPU container.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the full production step — sharded train_step for
``train_*`` shapes, single-token ``serve``/decode step (with its KV/SSM cache)
for ``decode_*``/``long_*`` shapes, last-token-logits forward for
``prefill_*`` — entirely from ShapeDtypeStructs (no allocation), then:

    lowered  = jax.jit(step, in_shardings=..., out_shardings=..., donate...)
                  .lower(*input_specs(arch, shape))
    compiled = lowered.compile()
    print(compiled.memory_analysis())   # proves the cell fits
    print(compiled.cost_analysis())     # FLOPs/bytes for the roofline

and writes a JSON artifact with cost/memory/collective stats + the three-term
roofline (see ``roofline.py``).  Failures here are bugs in the system.

Usage:
    python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k
    python -m repro.launch.dryrun --arch gemma2-9b --shape decode_32k --multi-pod
    python -m repro.launch.dryrun --all [--multi-pod] [--force]   # sweep (subprocess per cell)
"""
import argparse
import functools
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Dict, Optional

ARTIFACT_DIR = os.environ.get("REPRO_DRYRUN_DIR", "artifacts/dryrun")


# --------------------------------------------------------------------------------------
def input_specs(arch: str, shape_name: str) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as SDS

    from ..configs import get_config
    from ..configs.base import SHAPES

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    gb, S = shape.global_batch, shape.seq_len
    if shape.kind in ("train", "prefill"):
        if cfg.frontend != "none":
            batch = {"embeds": SDS((gb, S, cfg.d_model), jnp.bfloat16)}
        else:
            batch = {"tokens": SDS((gb, S), jnp.int32)}
        if shape.kind == "train":
            batch["targets"] = SDS((gb, S), jnp.int32)
            batch["mask"] = SDS((gb, S), jnp.float32)
        return batch
    # decode: one new token against a seq_len-deep cache
    return {
        "tokens": SDS((gb, 1), jnp.int32),
        "pos": SDS((), jnp.int32),
    }


def _batch_sharding(batch_specs, mesh, rules):
    """Batch tensors: leading dim over the data axes (when divisible)."""
    import jax
    from jax.sharding import NamedSharding

    from ..distributed.sharding import build_pspec

    def one(sds):
        logical = ["batch"] + [None] * (len(sds.shape) - 1)
        return NamedSharding(mesh, build_pspec(sds.shape, logical, rules, mesh))

    return jax.tree.map(one, batch_specs)


# --------------------------------------------------------------------------------------
def _apply_overrides(cfg, pc, overrides):
    """--set key=value overrides: model fields go to ModelConfig, run-policy
    fields to ParallelConfig.  Values parse as int/float/str."""
    import dataclasses

    def parse(v):
        for cast in (int, float):
            try:
                return cast(v)
            except ValueError:
                continue
        return v

    mfields = {f.name for f in dataclasses.fields(cfg)}
    pfields = {f.name for f in dataclasses.fields(pc)}
    for kv in overrides or []:
        k, _, v = kv.partition("=")
        v = parse(v)
        if k in mfields:
            cfg = dataclasses.replace(cfg, **{k: v})
        elif k in pfields:
            pc = dataclasses.replace(pc, **{k: v})
        else:
            raise KeyError(f"--set {k}: not a ModelConfig or ParallelConfig field")
    return cfg, pc


def lower_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    save_hlo: Optional[str] = None,
    overrides=None,
) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from ..configs import get_config, memory_policy
    from ..configs.base import SHAPES, TrainConfig
    from ..distributed.sharding import build_sharding, make_rules, sharding_context
    from ..models import transformer as T
    from ..train.train_step import init_train_state, make_train_step, train_state_specs
    from . import hlo_cost, roofline
    from .mesh import make_production_mesh

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    pc = memory_policy(arch, shape, multi_pod=multi_pod)
    tp = None
    rest = []
    for kv in overrides or []:
        if kv.startswith("tp="):
            tp = int(kv.split("=")[1])
        else:
            rest.append(kv)
    cfg, pc = _apply_overrides(cfg, pc, rest)
    if tp is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    else:
        # perf-iteration lever: same chip count, different (data, model) split
        # (e.g. model=8 when an arch's head count doesn't divide 16)
        import dataclasses as _dc

        n = 512 if multi_pod else 256
        shp = (2, (n // 2) // tp, tp) if multi_pod else (n // tp, tp)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        mesh = jax.make_mesh(shp, axes, devices=jax.devices()[:n],
                             axis_types=(AxisType.Auto,) * len(axes))
        pc = _dc.replace(pc, mesh_shape=shp, mesh_axes=axes)
    n_chips = mesh.size
    rules = make_rules(pc.mesh_axes, shard_cache_seq=pc.shard_cache_seq)
    dp_axes = tuple(a for a in ("pod", "data") if a in pc.mesh_axes)
    tc = TrainConfig(model=cfg, parallel=pc)
    rep = NamedSharding(mesh, P())

    record: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "kind": shape.kind,
        "mesh": {"shape": list(mesh.devices.shape), "axes": list(mesh.axis_names)},
        "overrides": list(overrides or []),
        "parallel": {
            "zero_stage": pc.zero_stage,
            "microbatch": pc.microbatch,
            "remat": pc.remat,
            "mu_dtype": pc.mu_dtype,
            "nu_dtype": pc.nu_dtype,
            "grad_allreduce_dtype": pc.grad_allreduce_dtype,
            "shard_cache_seq": pc.shard_cache_seq,
        },
    }

    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
    t0 = time.time()

    if shape.kind == "train":
        state_shapes = jax.eval_shape(functools.partial(init_train_state, tc=tc), key_sds)
        specs = train_state_specs(tc)
        if pc.zero_stage == "zero1":
            # params replicated over the data axes (TP/model sharding kept);
            # optimizer moments stay data-sharded -> grads reduce-scatter once
            # per step and params all-gather once after the update, instead of
            # per-microbatch FSDP regathers.
            rules_params = dict(rules, embed=())
            state_sh = {
                "params": build_sharding(state_shapes["params"], specs["params"], rules_params, mesh),
                "opt": build_sharding(state_shapes["opt"], specs["opt"], rules, mesh),
            }
        else:
            state_sh = build_sharding(state_shapes, specs, rules, mesh)
        batch_specs = input_specs(arch, shape_name)
        batch_sh = _batch_sharding(batch_specs, mesh, rules)
        step = make_train_step(tc)

        def fn(state, batch):
            with sharding_context(mesh, rules):
                return step(state, batch)

        jitted = jax.jit(
            fn, in_shardings=(state_sh, batch_sh), out_shardings=(state_sh, rep),
            donate_argnums=(0,),
        )
        lowered = jitted.lower(state_shapes, batch_specs)

    elif shape.kind == "prefill":
        params_shapes = jax.eval_shape(
            functools.partial(T.init_params, cfg=cfg), key_sds
        )
        params_sh = build_sharding(params_shapes, T.param_specs(cfg), rules, mesh)
        batch_specs = input_specs(arch, shape_name)
        batch_sh = _batch_sharding(batch_specs, mesh, rules)

        def fn(params, batch):
            with sharding_context(mesh, rules):
                logits, _ = T.forward(
                    params,
                    batch.get("tokens"),
                    cfg,
                    inputs_embeds=batch.get("embeds"),
                    remat="none",
                    last_only=not cfg.encoder_only,
                )
            return logits

        jitted = jax.jit(fn, in_shardings=(params_sh, batch_sh), out_shardings=None)
        lowered = jitted.lower(params_shapes, batch_specs)

    else:  # decode
        params_shapes = jax.eval_shape(
            functools.partial(T.init_params, cfg=cfg), key_sds
        )
        params_sh = build_sharding(params_shapes, T.param_specs(cfg), rules, mesh)
        cache_shapes = jax.eval_shape(
            lambda: T.init_cache(cfg, shape.global_batch, shape.seq_len, jnp.bfloat16)
        )
        cache_sh = build_sharding(cache_shapes, T.cache_specs(cfg), rules, mesh)
        tok_specs = input_specs(arch, shape_name)
        tok_sh = {
            "tokens": _batch_sharding({"t": tok_specs["tokens"]}, mesh, rules)["t"],
            "pos": rep,
        }

        def fn(params, cache, tokens, pos):
            with sharding_context(mesh, rules):
                logits, new_cache = T.decode_step(params, cache, tokens, pos, cfg)
            return logits, new_cache

        jitted = jax.jit(
            fn,
            in_shardings=(params_sh, cache_sh, tok_sh["tokens"], tok_sh["pos"]),
            out_shardings=(None, cache_sh),
            donate_argnums=(1,),
        )
        lowered = jitted.lower(
            params_shapes, cache_shapes, tok_specs["tokens"], tok_specs["pos"]
        )

    record["lower_s"] = round(time.time() - t0, 2)
    t1 = time.time()
    compiled = lowered.compile()
    record["compile_s"] = round(time.time() - t1, 2)

    # ---- memory analysis (proves the cell fits) -----------------------------------
    try:
        mem = compiled.memory_analysis()
        print(mem)
        record["memory_analysis"] = {
            k: int(getattr(mem, k))
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
                "alias_size_in_bytes",
            )
            if hasattr(mem, k)
        } or str(mem)
    except Exception as e:  # CPU backend may not implement it
        record["memory_analysis"] = f"unavailable: {e}"

    # ---- cost analysis (FLOPs / bytes for the roofline) ----------------------------
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    print({k: v for k, v in sorted(cost.items()) if "{" not in k})
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    record["cost_analysis"] = {
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "transcendentals": float(cost.get("transcendentals", 0.0)),
    }

    # ---- trip-count-aware walk of the post-SPMD HLO --------------------------------
    hlo = compiled.as_text()
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    totals = hlo_cost.analyse_hlo(hlo, default_group=n_chips)
    record["hlo_cost"] = {
        "flops": totals.flops,
        "bytes": totals.bytes,
        "bytes_bf16eq": totals.bytes_bf16eq,
        "kernel_flops": totals.kernel_flops,
        "kernel_bytes_bf16eq": totals.kernel_bytes_bf16eq,
        "coll_operand_bytes": totals.coll_operand,
        "coll_wire_bytes": totals.coll_wire,
        "coll_tpu_wire_bytes": totals.coll_tpu_wire,
        "per_collective": totals.per_op,
    }

    # ---- roofline -------------------------------------------------------------------
    counts = cfg.param_counts()
    tokens_global = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    rl = roofline.analyse(
        flops_dev=totals.flops,
        bytes_bf16eq_dev=totals.bytes_bf16eq,
        kernel_bytes_bf16eq_dev=totals.kernel_bytes_bf16eq,
        bytes_raw_dev=totals.bytes,
        wire_bytes_dev=totals.coll_tpu_wire,
        n_params_active=counts["active"],
        tokens_global=tokens_global,
        kind=shape.kind,
        n_chips=n_chips,
    )
    record["roofline"] = rl.to_json()
    record["param_counts"] = {k: float(v) for k, v in counts.items()}
    record["status"] = "ok"
    return record


# --------------------------------------------------------------------------------------
def cell_path(arch: str, shape_name: str, multi_pod: bool, out_dir: str, tag: str = "") -> str:
    mesh_tag = "multipod_2x16x16" if multi_pod else "pod_16x16"
    suffix = f"__{tag}" if tag else ""
    return os.path.join(out_dir, mesh_tag, f"{arch}__{shape_name}{suffix}.json")


def run_one(args) -> int:
    from ..configs import cells

    skip = dict((s.name, r) for s, r in cells(args.arch))[args.shape]
    path = cell_path(args.arch, args.shape, args.multi_pod, args.out, args.tag)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    if skip:
        record = {
            "arch": args.arch, "shape": args.shape, "status": "skipped", "reason": skip,
            "mesh": "multipod" if args.multi_pod else "pod",
        }
        print(f"SKIP {args.arch} x {args.shape}: {skip}")
    else:
        try:
            record = lower_cell(
                args.arch, args.shape, multi_pod=args.multi_pod,
                save_hlo=args.save_hlo, overrides=args.overrides,
            )
            rl = record["roofline"]
            mesh_str = "x".join(str(x) for x in record["mesh"]["shape"])
            print(
                f"OK {args.arch} x {args.shape} mesh={mesh_str} "
                f"compile={record['compile_s']}s bottleneck={rl['bottleneck']} "
                f"terms(c/m/coll)={rl['compute_s']:.3e}/{rl['memory_s']:.3e}/{rl['collective_s']:.3e}s "
                f"useful={rl['useful_ratio']:.2f} frac={rl['roofline_fraction']:.2f}"
            )
        except Exception:
            record = {
                "arch": args.arch, "shape": args.shape, "status": "failed",
                "error": traceback.format_exc(),
            }
            print(f"FAIL {args.arch} x {args.shape}", file=sys.stderr)
            traceback.print_exc()
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    return 0 if record["status"] in ("ok", "skipped") else 1


def run_all(args) -> int:
    """Sweep driver: one fresh subprocess per cell (isolates XLA memory and
    any single-cell failure), resumable via the per-cell JSON artifacts."""
    from ..configs import ARCH_IDS
    from ..configs.base import SHAPES

    meshes = [False, True] if args.mesh == "both" else [args.mesh == "multi"]
    failures, done, total = [], 0, 0
    for multi_pod in meshes:
        for arch in ARCH_IDS:
            for shape_name in SHAPES:
                total += 1
                path = cell_path(arch, shape_name, multi_pod, args.out)
                if os.path.exists(path) and not args.force:
                    with open(path) as f:
                        st = json.load(f).get("status")
                    if st in ("ok", "skipped"):
                        done += 1
                        continue
                cmd = [
                    sys.executable, "-m", "repro.launch.dryrun",
                    "--arch", arch, "--shape", shape_name, "--out", args.out,
                ]
                if multi_pod:
                    cmd.append("--multi-pod")
                print(f"[{total}] {arch} x {shape_name} multi_pod={multi_pod}", flush=True)
                try:
                    r = subprocess.run(cmd, timeout=args.timeout)
                    rc = r.returncode
                except subprocess.TimeoutExpired:
                    rc = -9
                    with open(path, "w") as f:
                        json.dump(
                            {"arch": arch, "shape": shape_name, "status": "failed",
                             "error": f"timeout after {args.timeout}s"}, f)
                if rc == 0:
                    done += 1
                else:
                    failures.append((arch, shape_name, multi_pod))
    print(f"\ndry-run sweep: {done}/{total} cells ok/skipped, {len(failures)} failed")
    for f in failures:
        print("  FAILED:", f)
    return 1 if failures else 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch")
    p.add_argument("--shape")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--all", action="store_true")
    p.add_argument("--mesh", choices=["single", "multi", "both"], default="both",
                   help="which mesh(es) --all sweeps")
    p.add_argument("--out", default=ARTIFACT_DIR)
    p.add_argument("--force", action="store_true", help="recompute existing artifacts")
    p.add_argument("--timeout", type=int, default=3000, help="per-cell seconds (--all)")
    p.add_argument("--save-hlo", default=None, help="dump post-SPMD HLO text to file")
    p.add_argument("--tag", default="", help="artifact filename suffix (perf iterations)")
    p.add_argument("--set", action="append", default=[], dest="overrides",
                   metavar="KEY=VALUE", help="override ModelConfig/ParallelConfig fields")
    args = p.parse_args(argv)
    if args.all:
        return run_all(args)
    if not args.arch or not args.shape:
        p.error("need --arch and --shape (or --all)")
    return run_one(args)


if __name__ == "__main__":
    sys.exit(main())
