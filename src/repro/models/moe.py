"""Mixture-of-Experts: top-k router + capacity-bucketed expert compute.

Baseline implementation is pjit-level: tokens are sorted into per-expert
capacity buckets with static-shape scatter/gather, expert weights are sharded
over the 'model' axis, and XLA's SPMD partitioner inserts the dispatch
collectives.  An explicit two-hop all_to_all shard_map variant is the §Perf
hillclimb for the collective-bound MoE cells (see EXPERIMENTS.md).

Experts are padded to a multiple of the model-axis size (e.g. granite's 40
experts → 48 slots) — phantom experts get -inf router logits, so they receive
no tokens and contribute nothing; the padding cost is visible in the roofline
(documented waste, a hillclimb lever).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import shard
from repro.models.params import ParamDef


def moe_defs(d_model: int, d_ff: int, n_experts: int, pad_to: int = 16,
             act: str = "swiglu"):
    e = ((n_experts + pad_to - 1) // pad_to) * pad_to
    defs = {
        "router": ParamDef((d_model, e), P()),  # small, replicated
        # 2D-sharded expert weights (ZeRO-3 style): experts over 'model',
        # the d/f dim over 'data'; gathered per layer inside the EP shard.
        # (1D sharding left 27 GB/device of expert params for the 235B MoE —
        # caught by the dry-run memory analysis, §Perf iteration 6.)
        "w_up": ParamDef((e, d_model, d_ff), P("model", "data", None)),
        "w_down": ParamDef((e, d_ff, d_model), P("model", "data", None)),
    }
    if act in ("swiglu", "geglu"):
        defs["w_gate"] = ParamDef((e, d_model, d_ff),
                                  P("model", "data", None))
    return defs, e


def apply_moe(x, p, *, n_experts: int, n_padded: int, top_k: int,
              act: str = "swiglu", capacity_factor: float = 1.25,
              min_capacity: int = 4, dp_axes=("data",)):
    """x: (B, S, d) -> (B, S, d).

    Static-shape dispatch: (token, k) slots are bucketed per expert with a
    rank-within-expert cumsum; slots beyond capacity are dropped (standard
    Switch-style capacity truncation).
    """
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    if n_padded > n_experts:                       # mask phantom experts
        pad_mask = jnp.arange(n_padded) >= n_experts
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    gates, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    cap = max(min_capacity, int(capacity_factor * t * top_k / n_experts))
    cap = (cap + 255) // 256 * 256 if cap > 256 else cap  # DP-shardable
    # rank of each (token,k) slot within its expert, computed via one-hot
    # cumulative counts — O(t·k·E) bools, all static shapes.
    flat_ids = ids.reshape(-1)                                  # (t*k,)
    onehot = jax.nn.one_hot(flat_ids, n_padded, dtype=jnp.int32)
    rank = jnp.cumsum(onehot, axis=0) - onehot                  # before me
    my_rank = jnp.take_along_axis(rank, flat_ids[:, None], axis=1)[:, 0]
    keep = my_rank < cap

    # scatter tokens into (E, cap, d) buckets
    buckets = jnp.zeros((n_padded, cap, d), x.dtype)
    src = jnp.repeat(xt, top_k, axis=0)                         # (t*k, d)
    e_idx = jnp.where(keep, flat_ids, 0)
    c_idx = jnp.where(keep, my_rank, cap - 1)
    src = jnp.where(keep[:, None], src, 0)
    buckets = buckets.at[e_idx, c_idx].add(src, mode="drop")
    # experts over 'model', capacity over the DP axes: without the capacity
    # shard, every data replica computed ALL capacity slots (caught by the
    # dry-run roofline: useful_flops_ratio 0.04 for granite train_4k)
    buckets = shard(buckets, "model", dp_axes, None)

    # expert FFN: (E, cap, d) x (E, d, f) -> (E, cap, f) -> (E, cap, d)
    up = jnp.einsum("ecd,edf->ecf", buckets, p["w_up"])
    if act in ("swiglu", "geglu"):
        gate_act = jax.nn.silu if act == "swiglu" else jax.nn.gelu
        up = gate_act(jnp.einsum("ecd,edf->ecf", buckets, p["w_gate"])) * up
    else:
        up = jax.nn.silu(up)
    out_b = jnp.einsum("ecf,efd->ecd", up, p["w_down"])
    out_b = shard(out_b, "model", dp_axes, None)

    # gather back to (t*k, d), weight by gate, sum over k
    back = out_b[e_idx, c_idx]
    back = jnp.where(keep[:, None], back, 0)
    y = (back.reshape(t, top_k, d).astype(jnp.float32)
         * gates[..., None]).sum(axis=1)
    y = shard(y.reshape(b, s, d).astype(x.dtype), dp_axes, None, None)
    return y, _aux_loss(logits[:, :n_experts], ids, n_experts, top_k)


def _aux_loss(logits, ids, n_experts, top_k):
    """Switch-style load-balance auxiliary loss."""
    probs = jax.nn.softmax(logits, axis=-1)
    frac_tokens = jnp.mean(
        (jax.nn.one_hot(ids, n_experts).sum(axis=1) > 0).astype(jnp.float32),
        axis=0)
    frac_probs = probs.mean(axis=0)
    return n_experts * jnp.sum(frac_tokens * frac_probs)


# ----------------------------------------------------- shard_map EP path ---
def _current_mesh():
    try:
        from jax._src import mesh as mesh_lib
        m = mesh_lib.thread_resources.env.physical_mesh
        return None if m.empty else m
    except Exception:  # noqa: BLE001
        return None


def apply_moe_ep(x, p, *, n_experts: int, n_padded: int, top_k: int,
                 act: str = "swiglu", capacity_factor: float = 1.25,
                 min_capacity: int = 4, dp_axes=("data",), mesh=None):
    """Expert-parallel MoE via shard_map — the §Perf hillclimb for the
    collective-bound MoE cells.

    Key observation: activations are *replicated* over the 'model' axis
    (tensor-parallel layers psum back to replicated d_model), so every
    expert owner already holds every token of its data shard.  Dispatch is
    therefore purely LOCAL — each model column buckets tokens for its own
    E/model_size experts — and the only collective is one psum of the
    (tokens, d_model) output over 'model', identical in shape to a
    row-parallel matmul's reduction.  No all_to_all, no cross-shard scatter
    (the pjit-level scatter was measured at 240 s of collective time for
    granite train_4k; see EXPERIMENTS.md §Perf iteration 2).
    """
    mesh = mesh or _current_mesh()
    if mesh is None or "model" not in mesh.axis_names \
            or mesh.shape["model"] == 1 or n_padded % mesh.shape["model"]:
        return apply_moe(x, p, n_experts=n_experts, n_padded=n_padded,
                         top_k=top_k, act=act,
                         capacity_factor=capacity_factor,
                         min_capacity=min_capacity, dp_axes=dp_axes)
    mm = mesh.shape["model"]
    e_loc = n_padded // mm
    dp = tuple(a for a in (dp_axes if isinstance(dp_axes, tuple)
                           else (dp_axes,)) if a and a in mesh.axis_names)
    dp = dp if dp else None

    has_gate = "w_gate" in p

    def shard_fn(x, router, w_up, w_down, *maybe_gate):
        w_gate = maybe_gate[0] if maybe_gate else None
        if dp and "data" in dp:
            # ZeRO-3 gather of this layer's local experts (bwd: XLA turns
            # the transpose into a reduce-scatter of the expert grads)
            w_up = jax.lax.all_gather(w_up, "data", axis=1, tiled=True)
            w_down = jax.lax.all_gather(w_down, "data", axis=1, tiled=True)
            if w_gate is not None:
                w_gate = jax.lax.all_gather(w_gate, "data", axis=1,
                                            tiled=True)
        b, s, d = x.shape
        t = b * s
        xt = x.reshape(t, d)
        logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
        if n_padded > n_experts:
            pad_mask = jnp.arange(n_padded) >= n_experts
            logits = jnp.where(pad_mask[None, :], -1e30, logits)
        gates, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

        e0 = jax.lax.axis_index("model") * e_loc
        flat_ids = ids.reshape(-1)
        local = (flat_ids >= e0) & (flat_ids < e0 + e_loc)
        lids = jnp.where(local, flat_ids - e0, 0)

        cap = max(min_capacity, int(capacity_factor * t * top_k / n_experts))
        onehot = jax.nn.one_hot(lids, e_loc, dtype=jnp.int32) \
            * local[:, None].astype(jnp.int32)
        rank = jnp.cumsum(onehot, axis=0) - onehot
        my_rank = jnp.take_along_axis(rank, lids[:, None], axis=1)[:, 0]
        keep = local & (my_rank < cap)

        src = jnp.repeat(xt, top_k, axis=0)
        src = jnp.where(keep[:, None], src, 0)
        e_idx = jnp.where(keep, lids, 0)
        c_idx = jnp.where(keep, my_rank, cap - 1)
        buckets = jnp.zeros((e_loc, cap, d), x.dtype)
        buckets = buckets.at[e_idx, c_idx].add(src, mode="drop")

        up = jnp.einsum("ecd,edf->ecf", buckets, w_up)
        if w_gate is not None:
            gact = jax.nn.silu if act == "swiglu" else jax.nn.gelu
            up = gact(jnp.einsum("ecd,edf->ecf", buckets, w_gate)) * up
        else:
            up = jax.nn.silu(up)
        out_b = jnp.einsum("ecf,efd->ecd", up, w_down)

        back = out_b[e_idx, c_idx]
        back = jnp.where(keep[:, None], back, 0)
        y = (back.reshape(t, top_k, d).astype(jnp.float32)
             * gates[..., None]).sum(axis=1)
        y = jax.lax.psum(y, "model")           # the ONE collective
        aux = _aux_loss(logits[:, :n_experts], ids, n_experts, top_k)
        if dp:                                  # mean over data shards
            aux = jax.lax.pmean(aux, dp)
        return y.reshape(b, s, d).astype(x.dtype), aux

    wspec = P("model", "data" if (dp and "data" in dp) else None, None)
    in_specs = [P(dp, None, None), P(), wspec, wspec]
    args = [x, p["router"], p["w_up"], p["w_down"]]
    if has_gate:
        in_specs.append(wspec)
        args.append(p["w_gate"])
    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=(P(dp, None, None), P()), check_vma=False)
    return fn(*args)
