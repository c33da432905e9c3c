"""End-to-end trainer: --arch <id> --steps N, with fault-tolerant restart.

Runs on whatever devices exist (CPU smoke: 1 device; TPU pod: the production
mesh).  Features exercised here and tested in tests/test_train_driver.py:

  * deterministic data pipeline with host prefetch (train/data.py);
  * periodic async checkpointing, atomic rename, --resume auto picks up the
    latest step after a crash — and reshards onto a *different* mesh if the
    world changed (elastic restart);
  * straggler mitigation: data is a pure function of (seed, step), so a
    replaced host needs no coordination to rejoin at the right step.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

import repro.configs as C
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer
from repro.models.params import tree_abstract, tree_init, tree_shardings
from repro.train import checkpoint as ckpt
from repro.train import optimizer as opt
from repro.train.data import Prefetcher, batch_for_step
from repro.train.train_step import make_train_step


def reduced_shapes(cfg, batch: int, seq: int):
    i32 = jnp.int32
    if cfg.family == "encoder":
        return {"frames": jax.ShapeDtypeStruct((batch, seq, cfg.d_model),
                                               jnp.float32),
                "mask": jax.ShapeDtypeStruct((batch, seq), jnp.bool_),
                "labels": jax.ShapeDtypeStruct((batch, seq), i32)}
    out = {"tokens": jax.ShapeDtypeStruct((batch, seq), i32),
           "labels": jax.ShapeDtypeStruct((batch, seq), i32)}
    if cfg.family == "vlm":
        out["patches"] = jax.ShapeDtypeStruct(
            (batch, cfg.vlm_patches, cfg.vlm_patch_dim), jnp.float32)
    return out


def train(arch: str, *, steps: int = 100, batch: int = 8, seq: int = 128,
          reduced: bool = True, ckpt_dir: str | None = None,
          ckpt_every: int = 50, resume: str = "auto", seed: int = 0,
          n_data: int = 1, n_model: int = 1, lr: float = 3e-4,
          log_every: int = 10, schedule_steps: int | None = None):
    cfg = C.get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    mesh = make_host_mesh(n_data, n_model)
    cfg = cfg.with_mesh(mesh)
    horizon = schedule_steps or steps   # keep LR schedule invariant across
    ocfg = opt.OptConfig(lr=lr,          # crash-restart runs of one job
                         warmup=min(20, horizon // 10 + 1),
                         total_steps=horizon, schedule=cfg.schedule)

    pdefs = transformer.param_defs(cfg)
    odefs = opt.opt_state_defs(pdefs, data_size=cfg.mesh_dp)
    p_sh = tree_shardings(pdefs, mesh)
    o_sh = tree_shardings(odefs, mesh)

    start = 0
    if ckpt_dir and resume == "auto" and (s := ckpt.latest_step(ckpt_dir)):
        like = {"params": tree_abstract(pdefs, cfg.param_dtype),
                "opt": tree_abstract(odefs)}
        tree = ckpt.restore(ckpt_dir, s, like,
                            shardings={"params": p_sh, "opt": o_sh})
        params, state = tree["params"], tree["opt"]
        start = s
        print(f"[train] resumed step {s} from {ckpt_dir}", flush=True)
    else:
        key = jax.random.PRNGKey(seed)
        params = tree_init(pdefs, key, cfg.param_dtype)
        state = tree_init(odefs, key)

    # out_shardings pin the updated params back to their logical specs —
    # without them the ZeRO update leaks the moments' 'data' sharding into
    # params and step 2 violates in_shardings (multi-device only)
    step_fn = jax.jit(make_train_step(cfg, ocfg),
                      in_shardings=(p_sh, o_sh, None),
                      out_shardings=(p_sh, o_sh, None),
                      donate_argnums=(0, 1))
    shapes = reduced_shapes(cfg, batch, seq)
    pf = Prefetcher(cfg, "train_4k", start_step=start, seed=seed,
                    reduced_shapes=shapes)
    losses = []
    t0 = time.time()
    try:
        with mesh:
            for i in range(start, steps):
                step_idx, b = pf.next()
                assert step_idx == i
                params, state, metrics = step_fn(params, state, b)
                losses.append(float(metrics["loss"]))
                if i % log_every == 0 or i == steps - 1:
                    print(f"[train] step {i} loss {losses[-1]:.4f} "
                          f"lr {float(metrics['lr']):.2e} "
                          f"gnorm {float(metrics['grad_norm']):.3f} "
                          f"({(time.time()-t0):.1f}s)", flush=True)
                if ckpt_dir and (i + 1) % ckpt_every == 0:
                    ckpt.save(ckpt_dir, i + 1,
                              {"params": params, "opt": state})
    finally:
        pf.close()
    if ckpt_dir:
        ckpt.save(ckpt_dir, steps, {"params": params, "opt": state},
                  block=True)
    return params, state, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="use the full (non-reduced) config")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default="auto", choices=["auto", "none"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--n-data", type=int, default=1)
    ap.add_argument("--n-model", type=int, default=1)
    args = ap.parse_args()
    use_compile_cache()
    _, _, losses = train(
        args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
        reduced=not args.full, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, resume=args.resume, lr=args.lr,
        n_data=args.n_data, n_model=args.n_model)
    print(f"[train] done: first-loss {losses[0]:.4f} last-loss "
          f"{losses[-1]:.4f}")


if __name__ == "__main__":
    main()
