"""Serving driver: batched prefill + greedy decode for any decoder arch."""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

import repro.configs as C
from repro.api.attention import attention_cache_stats, attention_program_for
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer
from repro.models.params import tree_init, tree_shardings
from repro.serve import serve_step as serve


def run(arch: str, *, batch: int = 4, prompt_len: int = 32,
        max_new: int = 16, reduced: bool = True, n_data: int = 1,
        n_model: int = 1, seed: int = 0, repeats: int = 3):
    cfg = C.get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    assert cfg.family != "encoder", "encoder-only archs do not decode"
    mesh = make_host_mesh(n_data, n_model)
    cfg = cfg.with_mesh(mesh)
    key = jax.random.PRNGKey(seed)
    params = tree_init(transformer.param_defs(cfg), key, cfg.param_dtype)
    cache_len = prompt_len + max_new + (
        cfg.vlm_patches if cfg.family == "vlm" else 0) + 8

    prompt = {"tokens": jax.random.randint(key, (batch, prompt_len), 0,
                                           cfg.vocab)}
    if cfg.family == "vlm":
        prompt["patches"] = jax.random.normal(
            key, (batch, cfg.vlm_patches, cfg.vlm_patch_dim),
            cfg.activ_dtype)

    # Warm the attention program handle before tracing: prefill dispatches
    # through the compile-once AttentionProgram (repro.api.attention).
    if cfg.family != "ssm" and cfg.attention_impl != "boundary_stub":
        attention_program_for(cfg)
    prefill = jax.jit(serve.make_prefill(cfg, cache_len))
    decode = jax.jit(serve.make_decode_step(cfg), donate_argnums=(1,))
    pos = prompt_len + (cfg.vlm_patches if cfg.family == "vlm" else 0)
    with mesh:
        # warmup dispatch: compile prefill AND a decode step outside the
        # timed region — the first call pays jit, not the model
        tok, cache = prefill(params, prompt)
        tok, cache = decode(params, cache, tok[:, None], jnp.int32(pos))
        tok.block_until_ready()

        # min-of-N: shared-machine contamination is one-sided, so the
        # fastest pass is the least-contaminated one (bench protocol,
        # see benchmarks/common.time_fn).  Decode donates the cache, so
        # every pass re-prefills to get a fresh one.
        t_prefill = t_decode = float("inf")
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            tok, cache = prefill(params, prompt)
            tok.block_until_ready()
            t_prefill = min(t_prefill, time.perf_counter() - t0)
            toks = [tok]
            t0 = time.perf_counter()
            for i in range(max_new - 1):
                tok, cache = decode(params, cache, tok[:, None],
                                    jnp.int32(pos + i))
                toks.append(tok)
            tok.block_until_ready()
            t_decode = min(t_decode, time.perf_counter() - t0)
    out = jnp.stack(toks, axis=1)
    print(f"[serve] {arch}: prefill {batch}x{prompt_len} in "
          f"{t_prefill*1e3:.1f}ms; {max_new-1} decode steps in "
          f"{t_decode*1e3:.1f}ms "
          f"({(max_new-1)*batch/max(t_decode,1e-9):.1f} tok/s, "
          f"best of {max(1, repeats)})", flush=True)
    stats = attention_cache_stats()["attention_programs"]
    print(f"[serve] attention programs: {stats['size']} compiled, "
          f"{stats['hits']} cache hits", flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--n-data", type=int, default=1)
    ap.add_argument("--n-model", type=int, default=1)
    args = ap.parse_args()
    use_compile_cache()
    run(args.arch, batch=args.batch, prompt_len=args.prompt_len,
        max_new=args.max_new, reduced=not args.full, n_data=args.n_data,
        n_model=args.n_model)


if __name__ == "__main__":
    main()
