"""Training driver: data pipeline -> jit'd train step -> checkpoints.

Fault tolerance: atomic checkpoints every --ckpt-every steps, crash-safe
resume (--resume picks the latest commit), and a --supervise mode that
restarts the run after failures (simulate one with --fail-at).  The data
pipeline is keyed by global step, so a restarted run consumes the exact
batches the crashed run would have.

XLA collective/compute overlap on real TPU is enabled via
--xla_tpu_enable_async_collective_fusion and the latency-hiding scheduler
(--xla_latency_hiding_scheduler); they are no-ops on CPU so we only document
them here.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sharding as shd
from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import DataConfig, SyntheticTokenPipeline
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.models.common import set_batch_axes
from repro.train import OptConfig, TrainConfig, init_train_state, make_train_step
from repro.train.step import train_state_specs


def build(args):
    cfg = get_config(args.arch, smoke=args.smoke)
    api = build_model(cfg)
    mesh = make_host_mesh(model=args.tp)
    set_batch_axes(shd._batch_axes_for(mesh, args.batch), mesh=mesh)
    tcfg = TrainConfig(opt=OptConfig(lr=args.lr, warmup_steps=args.warmup,
                                     total_steps=args.steps),
                       accum_steps=args.accum)
    step_fn = make_train_step(api, tcfg)
    state_shape = train_state_specs(api)
    state_sh = shd.make_param_shardings(cfg, mesh, state_shape)
    repl = NamedSharding(mesh, P())
    with mesh:
        jit_step = jax.jit(step_fn, in_shardings=(state_sh, None),
                           out_shardings=(state_sh, repl),
                           donate_argnums=(0,))
    return cfg, api, mesh, jit_step, state_sh


def run(args) -> dict:
    cfg, api, mesh, jit_step, state_sh = build(args)
    ckpt = CheckpointManager(args.ckpt_dir, keep=3)
    data = SyntheticTokenPipeline(
        DataConfig(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
                   seed=args.seed))

    start = 0
    if args.resume:
        try:
            template = train_state_specs(api)
            state, meta = ckpt.restore_latest(template, state_sh)
            start = int(meta["step"])
            data.load_state_dict(meta["extra"].get("data", {"step": start}))
            print(f"resumed from step {start}")
        except FileNotFoundError:
            state = init_train_state(api, jax.random.PRNGKey(args.seed))
    else:
        state = init_train_state(api, jax.random.PRNGKey(args.seed))

    losses = []
    for step in range(start, args.steps):
        batch = data.batch_at(step)
        feed = {"tokens": jnp.asarray(batch["tokens"])}
        if cfg.is_encdec:
            feed["frames"] = jnp.zeros((args.batch, args.seq, cfg.d_model),
                                       cfg.compute_dtype)
        elif cfg.frontend == "patch":
            feed["prefix_embeds"] = jnp.zeros(
                (args.batch, cfg.frontend_len, cfg.d_model), cfg.compute_dtype)
        t0 = time.perf_counter()
        with mesh:  # constraint anchors need the mesh context at trace time
            state, metrics = jit_step(state, feed)
        loss = float(metrics["loss"])
        losses.append(loss)
        if args.fail_at is not None and step == args.fail_at:
            raise RuntimeError(f"injected failure at step {step}")
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {loss:8.4f} "
                  f"gnorm {float(metrics['grad_norm']):7.3f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"({time.perf_counter() - t0:5.2f}s)", flush=True)
        if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            ckpt.save(state, step + 1, extra={"data": data.state_dict(),
                                              "arch": cfg.name})
    if args.ckpt_every:
        ckpt.save(state, args.steps, extra={"data": data.state_dict(),
                                            "arch": cfg.name})
    return {"final_loss": losses[-1] if losses else None,
            "first_loss": losses[0] if losses else None}


def main() -> None:
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--supervise", action="store_true",
                    help="auto-restart from the latest checkpoint on failure")
    ap.add_argument("--max-restarts", type=int, default=2)
    args = ap.parse_args()

    if not args.supervise:
        out = run(args)
        print(out)
        return
    restarts = 0
    while True:
        try:
            out = run(args)
            print(out)
            return
        except RuntimeError as e:  # node failure — restart from checkpoint
            restarts += 1
            print(f"[supervisor] failure: {e}; restart {restarts}")
            if restarts > args.max_restarts:
                raise
            args.resume = True
            args.fail_at = None


if __name__ == "__main__":
    main()
