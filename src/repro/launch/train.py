"""End-to-end training driver.

Runs a real (CPU-sized or pod-sized) training loop with the production code
path: sharded train_step under a mesh, synthetic deterministic data,
atomic async checkpointing, auto-resume, and optional fault injection to
exercise the restart path.

    PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b --smoke \\
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/run1
    # kill it mid-run; rerun the same command -> resumes from the last step.

``--smoke`` selects the reduced config (CPU-trainable); omit it on a real pod
to train the full architecture.  ``--fail-at N`` simulates a crash at step N
(exercises checkpoint/restart in tests and demos).
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time

import jax
import numpy as np


def build(args):
    import jax.numpy as jnp

    from ..configs import ARCH_IDS, get_config, get_smoke_config
    from ..configs.base import ParallelConfig, TrainConfig
    from ..data.pipeline import SyntheticLM
    from ..distributed.sharding import build_sharding, make_rules, sharding_context
    from ..train.train_step import init_train_state, make_train_step, train_state_specs
    from .mesh import make_trial_mesh

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.vocab:
        cfg = dataclasses.replace(cfg, vocab_size=args.vocab)
    n_dev = min(args.devices or len(jax.devices()), len(jax.devices()))
    mesh = make_trial_mesh(n_dev)
    pc = ParallelConfig(
        mesh_shape=tuple(mesh.devices.shape),
        mesh_axes=tuple(mesh.axis_names),
        microbatch=args.microbatch,
        remat=args.remat,
    )
    tc = TrainConfig(
        model=cfg,
        parallel=pc,
        learning_rate=args.lr,
        warmup_steps=max(args.steps // 10, 1),
        total_steps=args.steps,
        seed=args.seed,
    )
    rules = make_rules(pc.mesh_axes)
    data = SyntheticLM(cfg.vocab_size, args.seq, args.batch, seed=args.seed)
    step_fn = make_train_step(tc)

    def fn(state, batch):
        with sharding_context(mesh, rules):
            return step_fn(state, batch)

    state_shapes = jax.eval_shape(
        functools.partial(init_train_state, tc=tc), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    state_sh = build_sharding(state_shapes, train_state_specs(tc), rules, mesh)
    jitted = jax.jit(fn, in_shardings=(state_sh, None), out_shardings=(state_sh, None),
                     donate_argnums=(0,))
    return tc, mesh, data, jitted, state_sh


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="starcoder2-3b")
    p.add_argument("--smoke", action="store_true", help="reduced config (CPU-trainable)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--vocab", type=int, default=0, help="override vocab (0 = config)")
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--microbatch", type=int, default=0)
    p.add_argument("--remat", default="none", choices=["none", "full", "dots"])
    p.add_argument("--devices", type=int, default=0, help="devices for the trial mesh")
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--fail-at", type=int, default=0, help="simulate a crash at this step")
    args = p.parse_args(argv)

    from ..checkpoint.checkpointer import Checkpointer
    from .compile_cache import enable_compile_cache

    enable_compile_cache()

    tc, mesh, data, jitted, state_sh = build(args)

    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    state = None
    if ckpt is not None and ckpt.latest_step() is not None:
        restored, manifest = ckpt.restore()
        state = jax.device_put(restored, state_sh)
        start = int(manifest["step"])
        print(f"resumed from checkpoint at step {start}")
    if state is None:
        from ..train.train_step import init_train_state

        state = jax.device_put(
            init_train_state(jax.random.PRNGKey(args.seed), tc), state_sh
        )

    t0 = time.time()
    losses = []
    for step in range(start, args.steps):
        if args.fail_at and step == args.fail_at:
            print(f"simulated failure at step {step}", file=sys.stderr)
            return 17  # distinct exit code: "injected failure"
        batch = {k: np.asarray(v) for k, v in data.make_batch(step).items()}
        state, metrics = jitted(state, batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = time.time() - t0
            print(f"step {step:5d}  loss {loss:.4f}  lr {float(metrics['lr']):.2e}  "
                  f"acc {float(metrics.get('accuracy', 0.0)):.3f}  [{dt:.1f}s]", flush=True)
        if ckpt is not None and step > 0 and step % args.ckpt_every == 0:
            ckpt.save_async(step, state, {"loss": float(metrics["loss"])})
    if ckpt is not None:
        ckpt.wait()
        ckpt.save(args.steps, state)
    print(json.dumps({"final_loss": losses[-1] if losses else None,
                      "first_loss": losses[0] if losses else None,
                      "steps": args.steps, "seconds": round(time.time() - t0, 1)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
