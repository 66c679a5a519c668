"""Mixture-of-Experts FFN with expert parallelism over the ``model`` axis.

Dispatch strategy (see DESIGN.md): activations enter the MoE block already
replicated over the model axis (they are the psum output of the TP attention
block), so expert dispatch needs *no* communication — each model-rank gathers
the tokens routed to its local experts (capacity-bounded top-C selection),
runs the expert FFNs as one batched einsum, and scatter-adds gate-weighted
results.  The only collective is the combine ``psum`` over the model axis,
which coincides with the TP all-reduce the block needs anyway.

The cross-pod/EP traffic this generates is exactly the All2All-class pattern
whose fabric cost the paper optimizes (MRLS +50% vs FT at 100K endpoints) —
see ``repro.fabric`` for the planner that consumes the dry-run byte counts.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import ParamSpec, GATED_ACTS


__all__ = ["MoECfg", "moe_specs", "moe_apply"]


@dataclasses.dataclass(frozen=True)
class MoECfg:
    n_experts: int
    top_k: int
    d_expert: int
    n_shared: int = 0
    capacity_factor: float = 1.25
    router_scale_bias: bool = False    # DeepSeek aux-loss-free bias


def moe_specs(cfg) -> dict:
    m, d = cfg.moe, cfg.d_model
    scale_out = 0.02 / math.sqrt(2 * cfg.total_layers)
    out = {
        "router": ParamSpec((d, m.n_experts), (None, None), "float32"),
        "wi": ParamSpec((m.n_experts, d, 2, m.d_expert),
                        ("tp", "fsdp", None, None)),
        "wo": ParamSpec((m.n_experts, m.d_expert, d),
                        ("tp", None, "fsdp"), scale=scale_out),
    }
    if m.router_scale_bias:
        out["router_bias"] = ParamSpec((m.n_experts,), (None,), "float32", "zeros")
    if m.n_shared:
        out["shared_wi"] = ParamSpec((d, 2, m.n_shared * m.d_expert),
                                     ("fsdp", None, "tp"))
        out["shared_wo"] = ParamSpec((m.n_shared * m.d_expert, d),
                                     ("tp", "fsdp"), scale=scale_out)
    return out


def _local_expert_ffn(wi, wo, xs):
    """xs: [E_loc, C, d] -> [E_loc, C, d]; gated (SwiGLU) experts."""
    gu = jnp.einsum("ecd,edgf->ecgf", xs, wi,
                    preferred_element_type=jnp.bfloat16)
    h = jax.nn.silu(gu[:, :, 0].astype(jnp.float32)).astype(xs.dtype) * gu[:, :, 1]
    return jnp.einsum("ecf,efd->ecd", h, wo,
                      preferred_element_type=jnp.bfloat16)


def moe_apply(p: dict, x, cfg, sh):
    """x: [B,S,d] (replicated over the model axis).  Returns [B,S,d]."""
    m: MoECfg = cfg.moe
    B, S, d = x.shape
    mesh = sh.mesh
    tp_ax = sh.rules.tp
    dp_axes = tuple(sh.rules.dp)
    n_tp = mesh.shape[tp_ax] if tp_ax else 1
    assert m.n_experts % n_tp == 0
    e_loc = m.n_experts // n_tp

    # per-device token count and capacity (static)
    n_dp = 1
    for a in dp_axes:
        n_dp *= mesh.shape[a]
    t_loc = (B * S) // n_dp
    cap = max(4, int(t_loc * m.top_k * m.capacity_factor / m.n_experts))

    def local(x_loc, router_w, router_b, wi_loc, wo_loc):
        T = x_loc.shape[0] * x_loc.shape[1]
        xt = x_loc.reshape(T, d)
        logits = (xt.astype(jnp.float32) @ router_w).astype(jnp.float32)
        if router_b is not None:                  # aux-loss-free load balance
            sel_scores = jax.nn.sigmoid(logits) + router_b
        else:
            sel_scores = logits
        top_vals, top_idx = jax.lax.top_k(sel_scores, m.top_k)     # [T,k]
        gates = jax.nn.softmax(
            jnp.take_along_axis(logits, top_idx, 1), axis=-1)      # [T,k]

        tp_rank = jax.lax.axis_index(tp_ax) if tp_ax else 0
        e0 = tp_rank * e_loc
        # match[e, T*k] for my experts; pick first `cap` per expert
        flat_e = top_idx.reshape(-1)                               # [T*k]
        flat_g = gates.reshape(-1)
        eids = e0 + jnp.arange(e_loc, dtype=jnp.int32)
        match = flat_e[None, :] == eids[:, None]                   # [E_loc,T*k]
        prio = jnp.where(match, -jnp.arange(T * m.top_k, dtype=jnp.int32),
                         jnp.int32(-(1 << 30)))
        sel_p, sel_i = jax.lax.top_k(prio, cap)                    # [E_loc,cap]
        sel_ok = sel_p > -(1 << 30)
        tok = jnp.where(sel_ok, sel_i // m.top_k, 0)
        gate = jnp.where(sel_ok, flat_g[sel_i], 0.0)

        xs = xt[tok.reshape(-1)].reshape(e_loc, cap, d)
        ys = _local_expert_ffn(wi_loc, wo_loc, xs)
        ys = ys * gate[..., None].astype(ys.dtype)
        out = jnp.zeros((T, d), ys.dtype).at[tok.reshape(-1)].add(
            ys.reshape(-1, d), mode="drop")
        if tp_ax:
            out = jax.lax.psum(out, tp_ax)
        return out.reshape(x_loc.shape)

    router_b = p.get("router_bias")
    in_specs = (P(dp_axes, None, None), P(None, None),
                (P(None) if router_b is not None else None),
                P(tp_ax, None, None, None), P(tp_ax, None, None))
    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=in_specs,
        out_specs=P(dp_axes, None, None),
        check_vma=False,
    )(x, p["router"].astype(jnp.float32), router_b, p["wi"], p["wo"])

    if m.n_shared:
        gu = jnp.einsum("bsd,dgf->bsgf", x, p["shared_wi"],
                        preferred_element_type=jnp.bfloat16)
        h = jax.nn.silu(gu[:, :, 0].astype(jnp.float32)).astype(x.dtype) * gu[:, :, 1]
        out = out + jnp.einsum("bsf,fd->bsd", h, p["shared_wo"],
                               preferred_element_type=jnp.bfloat16)
    return out
