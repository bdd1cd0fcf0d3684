"""Mixture-of-Experts FFN: top-k routing with sort-based dispatch.

Production dispatch path (MegaBlocks/MaxText-style), chosen for TPU + pjit:

* routing/top-k in fp32;
* *per-sequence* dispatch: the argsort/scatter runs vmapped over the batch
  axis, so with batch sharded over ("pod","data") every device sorts and
  scatters only its local rows — no cross-device scatter, no (T, E, C)
  one-hot dispatch tensor;
* tokens are packed into (E, C, D) capacity buffers by a stable sort over
  expert ids (overflow dropped, standard capacity-factor semantics);
* expert weights are *tensor-parallel over the hidden dim F* ("model" axis),
  i.e. TP-in-expert + DP-over-tokens. Expert-parallelism (sharding E) is the
  alternative; the trade-off is recorded in DESIGN.md §5 and revisited in the
  §Perf hillclimb.
* shared experts (DeepSeekMoE) are a fused dense SwiGLU applied to every
  token.

Returns the load-balancing auxiliary loss (Switch-style) alongside outputs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array

from repro.configs.base import ModelConfig
from repro.models.layers import swiglu


def expert_capacity(seq_len: int, cfg: ModelConfig, capacity_factor: float) -> int:
    """Static per-sequence expert capacity C (multiple of 8, >= 1)."""
    raw = capacity_factor * seq_len * cfg.top_k / cfg.num_experts
    c = max(int(raw + 0.999), 1)
    return max((c + 7) // 8 * 8, 8) if seq_len >= 64 else c


def route_topk(x: Array, w_router: Array, top_k: int) -> tuple[Array, Array, Array]:
    """fp32 router: returns (gates (S,k), expert_idx (S,k), aux_loss scalar)."""
    logits = (x.astype(jnp.float32) @ w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                     # (S, E)
    gates, idx = jax.lax.top_k(probs, top_k)                    # (S, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch load-balance loss: E * sum_e fraction_e * mean_prob_e.
    e = probs.shape[-1]
    occupancy = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    frac = occupancy / jnp.maximum(occupancy.sum(), 1.0)
    aux = e * jnp.sum(frac * probs.mean(axis=0))
    return gates, idx, aux


def _dispatch_one_row(x: Array, gates: Array, idx: Array, num_experts: int, cap: int):
    """Pack one sequence's tokens into (E, C, D) buffers via stable sort.

    Returns (buffers, dest, token_src, weight) with dest/token_src/weight flat
    over (S * k,); ``dest`` is an index into the flattened (E*C) buffer and is
    out-of-bounds for capacity-dropped entries (scatter/gather use drop mode).
    """
    s, d = x.shape
    k = idx.shape[-1]
    flat_e = idx.reshape(-1)                                    # (S*k,)
    sort_i = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[sort_i]
    token_src = sort_i // k                                     # (S*k,)
    counts = jnp.bincount(flat_e, length=num_experts)
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(s * k) - offsets[sorted_e]
    keep = pos < cap
    dest = jnp.where(keep, sorted_e * cap + pos, s * k + num_experts * cap)
    buf = jnp.zeros((num_experts * cap, d), x.dtype)
    buf = buf.at[dest].set(x[token_src], mode="drop")
    weight = gates.reshape(-1)[sort_i]
    return buf.reshape(num_experts, cap, d), dest, token_src, weight


def _moe_local(x, w_router, we_gate, we_up, we_down, cfg, cap, psum_axis=None):
    """MoE over LOCAL rows (B_local, S, D) — sort/scatter stay on-device.

    ``psum_axis``: when expert weights arrive as local F-shards (manual TP
    inside shard_map), the down-projection partial sums reduce over it.
    """
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.top_k

    def per_row(xr):
        gates, idx, aux = route_topk(xr, w_router, k)
        buf, dest, token_src, weight = _dispatch_one_row(xr, gates, idx, e, cap)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, we_gate))
        h = h * jnp.einsum("ecd,edf->ecf", buf, we_up)
        out = jnp.einsum("ecf,efd->ecd", h, we_down)
        if psum_axis is not None:
            out = jax.lax.psum(out, psum_axis)
        out_buf = out.reshape(e * cap, d)
        gathered = jnp.take(out_buf, jnp.minimum(dest, e * cap - 1), axis=0)
        gathered = jnp.where((dest < e * cap)[:, None], gathered, 0.0)
        yr = jnp.zeros((s, d), x.dtype).at[token_src].add(
            (gathered * weight[:, None]).astype(x.dtype)
        )
        return yr, aux

    y, aux = jax.vmap(per_row)(x)
    return y, jnp.mean(aux)


def moe_ffn(
    x: Array,
    w_router: Array,
    we_gate: Array,
    we_up: Array,
    we_down: Array,
    cfg: ModelConfig,
    capacity_factor: float = 1.25,
) -> tuple[Array, Array]:
    """Top-k MoE over (B, S, D) activations.

    Expert weights: we_gate/we_up (E, D, F), we_down (E, F, D).
    Returns (output (B, S, D), aux_loss scalar).

    When an ambient mesh with batch axes exists, the token path runs under a
    FULLY-MANUAL ``shard_map`` over ("pod","data","model"): XLA's SPMD
    partitioner cannot prove the vmapped dispatch scatter parallel over the
    batch dim and falls back to replicating the (B, E·C, D) buffers —
    measured 172 GB/step of all-gathers on phi3.5-moe train_4k
    (EXPERIMENTS.md §Perf B1). Manual batch locality removes them by
    construction; expert weights enter as local F-shards (manual TP) and the
    down-projection partial sums psum over "model" explicitly. (A
    partial-auto shard_map would be lighter, but mixing manual batch axes
    with an auto model axis inside grad+remat trips an XLA crash on this
    backend — documented in §Perf B1.)
    """
    cap = expert_capacity(x.shape[1], cfg, capacity_factor)

    mesh = jax.sharding.get_abstract_mesh()
    f = cfg.moe_d_ff or cfg.d_ff
    batch_axes = tuple(
        a for a in ("pod", "data")
        if (not mesh.empty) and a in mesh.axis_names and x.shape[0] % mesh.shape[a] == 0
    )
    model_ok = (
        (not mesh.empty)
        and "model" in mesh.axis_names
        and f % mesh.shape["model"] == 0
    )
    if not batch_axes or not model_ok:
        return _moe_local(x, w_router, we_gate, we_up, we_down, cfg, cap)

    from jax.sharding import PartitionSpec as P

    bspec = batch_axes if len(batch_axes) > 1 else batch_axes[0]

    def local_fn(xl, wr, wg, wu, wd):
        y, aux = _moe_local(xl, wr, wg, wu, wd, cfg, cap, psum_axis="model")
        return y, jax.lax.pmean(aux, batch_axes)

    return jax.shard_map(
        local_fn,
        in_specs=(
            P(bspec),                      # x: rows local per batch shard
            P(),                           # router replicated
            P(None, None, "model"),        # we_gate: F-shard
            P(None, None, "model"),        # we_up:   F-shard
            P(None, "model", None),        # we_down: F-shard (row-parallel)
        ),
        out_specs=(P(bspec), P()),
        axis_names=set(batch_axes) | {"model"},
        check_vma=False,
    )(x, w_router, we_gate, we_up, we_down)


def shared_expert_ffn(x: Array, ws_gate: Array, ws_up: Array, ws_down: Array) -> Array:
    """DeepSeekMoE shared experts — a fused dense SwiGLU over all tokens."""
    return swiglu(x, ws_gate, ws_up, ws_down)
