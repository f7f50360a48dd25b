"""Mixture-of-Experts FFN with sort-based capacity dispatch and expert
parallelism (DeepSeek-V3 / Llama-4 style).

Parallelization: activations are batch-sharded (replicated across the
"model" mesh axis); expert weights are sharded over "model".  Inside
``shard_map`` every device selects the tokens routed to ITS experts from
its (replicated) local token block, runs a fixed-capacity gather -> grouped
GEMM -> scatter, and the partial outputs are ``psum``'d over the model axis
(the same single-collective pattern as a Megatron TP MLP, but with a
sort-based capacity dispatch instead of dense GShard one-hot tensors —
a (T, E, C) dispatch tensor would be ~4e13 elements for DeepSeek-V3's
train_4k cell, which is exactly why it is not used here).

Routing supports softmax-top-k (Switch/Mixtral style) and DeepSeek-V3's
sigmoid scoring with normalized top-k and routed scaling.  Shared experts
(always-on dense branch) are applied outside the dispatch.  Aux outputs:
load-balance loss and router z-loss.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.nn.basic import mlp_apply, mlp_init
from repro.nn.param import Param, fan_in_init
from repro.sharding import current_ctx

f32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    num_shared_experts: int = 0
    capacity_factor: float = 1.25
    routing: str = "softmax"  # "softmax" | "sigmoid" (deepseek-v3)
    routed_scaling: float = 1.0
    norm_topk: bool = False
    aux_loss_weight: float = 0.001
    z_loss_weight: float = 1e-4
    # Expert-parallel combine: "psum" all-reduces the full (T, d) partial
    # output (2x T*d ring bytes); "gather" all-gathers only the compact
    # per-expert outputs (k*cf*T*d bytes) and combines locally — cheaper
    # whenever top_k * capacity_factor < 2 (e.g. llama4's top-1).
    combine: str = "psum"


def moe_init(key, d_model: int, cfg: MoEConfig, mlp_kind: str = "swiglu"):
    ks = jax.random.split(key, 6)
    E, F = cfg.num_experts, cfg.d_ff_expert
    p = {
        "router": Param(fan_in_init(ks[0], (d_model, E), d_model), ("embed", None)),
        "wi": Param(fan_in_init(ks[1], (E, d_model, F), d_model), ("experts", "embed", "expert_mlp")),
        "wg": Param(fan_in_init(ks[2], (E, d_model, F), d_model), ("experts", "embed", "expert_mlp")),
        "wo": Param(fan_in_init(ks[3], (E, F, d_model), F), ("experts", "expert_mlp", "embed")),
    }
    if cfg.routing == "sigmoid":
        p["router_bias"] = Param(jnp.zeros((E,), f32), (None,))
    if cfg.num_shared_experts:
        p["shared"] = mlp_init(
            ks[4], d_model, F * cfg.num_shared_experts, mlp_kind
        )
    return p


def _route(p, x2d, cfg: MoEConfig):
    """Router scores -> (weights (T,k), ids (T,k), aux_losses)."""
    logits = jnp.einsum("td,de->te", x2d.astype(f32), p["router"].astype(f32))
    if cfg.routing == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["router_bias"].astype(f32)  # bias affects selection only
        w, ids = jax.lax.top_k(sel, cfg.top_k)
        w = jnp.take_along_axis(scores, ids, axis=-1)
        if cfg.norm_topk:
            w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
        w = w * cfg.routed_scaling
        probs = scores / jnp.maximum(jnp.sum(scores, -1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, ids = jax.lax.top_k(probs, cfg.top_k)
        if cfg.norm_topk:
            w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
    # Load-balance loss (Switch-style): E * sum_e f_e * P_e.
    T = x2d.shape[0]
    E = cfg.num_experts
    assign = jnp.zeros((T, E), f32).at[jnp.arange(T)[:, None], ids].set(1.0)
    f_e = jnp.mean(assign, axis=0)
    p_e = jnp.mean(probs, axis=0)
    lb_loss = E * jnp.sum(f_e * p_e) * cfg.aux_loss_weight
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * cfg.z_loss_weight
    return w, ids, lb_loss + z_loss


def _dispatch(x2d, w, ids, cfg: MoEConfig, e_start, e_local, dtype):
    """Sort-based fixed-capacity dispatch bookkeeping (identical on every
    shard — routing math uses the full E).  Returns (buf (e_local*C, d),
    st, sw, dest_local, C)."""
    T, d = x2d.shape
    k = cfg.top_k
    E = cfg.num_experts
    C = max(8, int(T * k * cfg.capacity_factor) // E)
    flat_e = ids.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_w = w.reshape(-1)
    order = jnp.argsort(flat_e)
    se = flat_e[order]
    st = flat_t[order]
    sw = flat_w[order]
    counts = jnp.bincount(flat_e, length=E)
    start = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k) - start[se]
    local = (se >= e_start) & (se < e_start + e_local) & (pos < C)
    dest = jnp.where(local, (se - e_start) * C + pos, e_local * C)
    buf = jnp.zeros((e_local * C + 1, d), dtype)
    buf = buf.at[dest].set(x2d.astype(dtype)[st])
    # Global dest (over ALL experts) for the gather-combine path.
    globally_valid = pos < C
    dest_global = jnp.where(globally_valid, se * C + pos, E * C)
    return buf[:-1], st, sw, dest, dest_global, C


def _expert_ffn(h, wi, wg, wo, e_local, C, dtype):
    """Grouped gated GEMM over the local experts."""
    d = h.shape[-1]
    h = h.reshape(e_local, C, d)
    g = jnp.einsum("ecd,edf->ecf", h, wg.astype(dtype))
    up = jnp.einsum("ecd,edf->ecf", h, wi.astype(dtype))
    act = jax.nn.silu(g) * up
    return jnp.einsum("ecf,efd->ecd", act, wo.astype(dtype)).reshape(e_local * C, d)


def _dispatch_compute_combine(x2d, w, ids, wi, wg, wo, cfg: MoEConfig, e_start, e_local, dtype):
    """Fixed-capacity gather -> grouped GEMM -> weighted scatter-add.

    Processes only experts [e_start, e_start + e_local).  x2d: (T, d).
    """
    T, d = x2d.shape
    buf, st, sw, dest, _, C = _dispatch(x2d, w, ids, cfg, e_start, e_local, dtype)
    out = _expert_ffn(buf, wi, wg, wo, e_local, C, dtype)
    out_flat = jnp.concatenate([out, jnp.zeros((1, d), dtype)])
    y = jnp.zeros((T, d), dtype)
    y = y.at[st].add(out_flat[dest] * sw[:, None].astype(dtype))
    return y


def moe_apply(
    p,
    x,  # (B, S, d)
    cfg: MoEConfig,
    *,
    mlp_kind: str = "swiglu",
    dtype=jnp.bfloat16,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y, aux_loss).  Runs expert-parallel when a mesh ctx with a
    'model' axis is active, single-device otherwise (same code path)."""
    B, S, d = x.shape
    ctx = current_ctx()
    E = cfg.num_experts

    def local_moe(router, router_bias, wi, wg, wo, xblk, e_start, e_local):
        x2d = xblk.reshape(-1, d)
        pp = {"router": router}
        if router_bias is not None:
            pp["router_bias"] = router_bias
        w, ids, aux = _route(pp, x2d, cfg)
        y = _dispatch_compute_combine(
            x2d, w, ids, wi, wg, wo, cfg, e_start, e_local, dtype
        )
        return y.reshape(xblk.shape), aux

    use_ep = (
        ctx is not None
        and "model" in ctx.mesh.shape
        and E % ctx.mesh.shape["model"] == 0
    )
    if use_ep:
        mesh = ctx.mesh
        ep = mesh.shape["model"]
        e_local = E // ep
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)

        def shard_fn(router, router_bias, wi, wg, wo, xblk):
            midx = jax.lax.axis_index("model")
            if cfg.combine == "gather":
                # all-gather compact expert outputs, combine locally:
                # payload k*cf*T*d vs psum's 2*T*d ring bytes.
                x2d = xblk.reshape(-1, d)
                pp = {"router": router}
                if router_bias is not None:
                    pp["router_bias"] = router_bias
                w, ids, aux = _route(pp, x2d, cfg)
                buf, st, sw, _, dest_global, C = _dispatch(
                    x2d, w, ids, cfg, midx * e_local, e_local, dtype
                )
                out_local = _expert_ffn(buf, wi, wg, wo, e_local, C, dtype)
                out_all = jax.lax.all_gather(out_local, "model", axis=0, tiled=True)
                out_all = jnp.concatenate([out_all, jnp.zeros((1, d), dtype)])
                y = jnp.zeros((x2d.shape[0], d), dtype)
                y = y.at[st].add(out_all[dest_global] * sw[:, None].astype(dtype))
                y = y.reshape(xblk.shape)
            else:
                y, aux = local_moe(
                    router, router_bias, wi, wg, wo, xblk, midx * e_local, e_local
                )
                y = jax.lax.psum(y, "model")
            aux = jax.lax.pmean(aux, batch_axes + ("model",))
            return y, aux

        rb = p.get("router_bias")
        in_specs = (
            P(None, None),
            None if rb is None else P(None),
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
            P(batch_axes, None, None),
        )
        y, aux = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(P(batch_axes, None, None), P()),
            check_vma=False,
        )(p["router"], rb, p["wi"], p["wg"], p["wo"], x)
    else:
        y, aux = local_moe(
            p["router"], p.get("router_bias"), p["wi"], p["wg"], p["wo"], x, 0, E
        )

    if "shared" in p:
        y = y + mlp_apply(p["shared"], x, mlp_kind, dtype)
    return y, aux
