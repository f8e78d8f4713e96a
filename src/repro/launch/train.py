"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --reduced --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Fault tolerance in practice:
  * checkpoint every --ckpt-every steps (atomic manifest, async write);
  * on start, resumes from the latest complete checkpoint automatically;
  * the data pipeline is a pure function of step, so a restarted run
    consumes exactly the batches it would have seen (kill -9 mid-run and
    relaunch — the loss curve continues; tests/test_train.py does this).
"""

from __future__ import annotations

import argparse
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, list_archs, reduced_config
from repro.launch.jax_cache import enable_compile_cache
from repro.launch.mesh import describe, make_host_mesh
from repro.models import init_params
from repro.parallel import sharding as shlib
from repro.parallel.sharding import param_shardings
from repro.train import (
    AdamWConfig, DataConfig, TrainConfig, adamw_init, build_train_step,
    checkpoint, cosine_schedule, make_source, augment_for_arch,
)


def train_config(microbatches: int = 1, remat: str = "none") -> TrainConfig:
    return TrainConfig(adamw=AdamWConfig(), microbatches=microbatches,
                       remat=remat, moe_strategy="dense")


def init_train_state(cfg, tc: TrainConfig, mesh, seed: int):
    """Params placed by ``param_shardings`` on ``mesh`` (FSDP over
    ``data``, tensor parallel over ``model``) and their AdamW state; the
    first step's outputs carry the moments' sharding from there."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    params = jax.device_put(params, param_shardings(params, mesh))
    return params, adamw_init(params, tc.adamw)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true",
                    help="family-preserving tiny config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none",
                    choices=["none", "full", "dots", "sqrt"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-async", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-path", default="",
                    help="memmapped token file (synthetic stream if unset)")
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--n-layers", type=int, default=2)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg, n_layers=args.n_layers,
                             d_model=args.d_model)
    enable_compile_cache()
    mesh = make_host_mesh()
    print(f"mesh: {describe(mesh)}  arch: {cfg.name}")

    tc = train_config(args.microbatches, args.remat)
    lr = cosine_schedule(args.lr, max(args.steps // 20, 1), args.steps)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch, seed=args.seed,
                          path=args.data_path or None)
    source = make_source(data_cfg)

    with shlib.activity(mesh, {}):
        params, opt_state = init_train_state(cfg, tc, mesh, args.seed)
        step_fn = jax.jit(build_train_step(cfg, tc, lr),
                          donate_argnums=(0, 1))

        start = 0
        if args.ckpt_dir:
            latest = checkpoint.latest_step(args.ckpt_dir)
            if latest is not None:
                params, opt_state = checkpoint.restore(
                    args.ckpt_dir, latest, (params, opt_state))
                start = latest
                print(f"resumed from step {latest}")

        # Preemption handling: on SIGTERM (maintenance events send this
        # before killing the VM) finish the current step, checkpoint, and
        # exit cleanly — the relaunch resumes with zero lost steps.
        preempted = {"flag": False}

        def _on_sigterm(signum, frame):
            preempted["flag"] = True

        prev_handler = signal.signal(signal.SIGTERM, _on_sigterm)

        losses = []
        t0 = time.time()
        pending = None
        for step in range(start, args.steps):
            batch = source.batch(step)
            batch = augment_for_arch(batch, cfg, args.seq, step)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt_state, metrics = step_fn(
                params, opt_state, batch, jnp.asarray(step))
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % args.log_every == 0 or step == args.steps - 1:
                dt = time.time() - t0
                print(f"step {step:5d} loss {loss:7.4f} "
                      f"grad_norm {float(metrics['grad_norm']):8.3f} "
                      f"lr {float(metrics['lr']):.2e} ({dt:5.1f}s)",
                      flush=True)
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                if pending is not None:
                    pending.join()
                pending = checkpoint.save(
                    args.ckpt_dir, step + 1, (params, opt_state),
                    blocking=not args.ckpt_async)
            if preempted["flag"]:
                if args.ckpt_dir:
                    if pending is not None:
                        pending.join()
                    checkpoint.save(args.ckpt_dir, step + 1,
                                    (params, opt_state))
                print(f"preempted at step {step + 1}: checkpointed, "
                      f"exiting cleanly", flush=True)
                signal.signal(signal.SIGTERM, prev_handler)
                return losses
        if pending is not None:
            pending.join()
        signal.signal(signal.SIGTERM, prev_handler)
        if args.ckpt_dir:
            checkpoint.save(args.ckpt_dir, args.steps, (params, opt_state))
        print(f"final loss {losses[-1]:.4f} "
              f"(first {losses[0]:.4f}, "
              f"best {min(losses):.4f})")
        return losses


if __name__ == "__main__":
    main()
