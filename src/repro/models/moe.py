"""Fine-grained Mixture-of-Experts (DeepSeekMoE-style: shared + routed top-k).

Routing (router matmul, softmax, top-k, aux loss) runs in plain pjit with
global semantics.  Dispatch + expert compute + combine run under shard_map
over ("data","model"): tokens are sharded over the data axes, experts over
"model".  The residual stream is replicated over "model" at entry, so every
model shard sees its data shard's full token set — dispatch is a purely
local sort/gather into per-expert capacity buffers (C = ceil(k*T_loc*cf/E)),
followed by grouped einsums over the shard's E/TP local experts, a local
combine-scatter, and ONE psum over "model" (the same output all-reduce a
tensor-parallel MLP needs).  No token all-to-all, no redundant compute along
the data axis — the pjit-global formulation would replicate the capacity
dimension per data shard (16x waste; see EXPERIMENTS.md #Perf).

Dispatch index math is memory traffic, not matmul FLOPs, keeping HLO_FLOPs
~= active-param FLOPs.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers


def moe_init(key, cfg):
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_expert, m.n_routed
    dt = cfg.param_dtype
    ks = layers.split(key, 5)
    params, axes = {}, {}
    # experts take the "model" axis (EP); within-expert dims use FSDP ("embed")
    # only — mapping ff to "model" too would double-book the mesh axis.
    params["router"], axes["router"] = layers.dense_init(
        ks[0], (d, e), ("embed", "experts"), jnp.float32, scale=0.02)
    params["wg"], axes["wg"] = layers.dense_init(ks[1], (e, d, f), ("experts", "embed", None), dt)
    params["wu"], axes["wu"] = layers.dense_init(ks[2], (e, d, f), ("experts", "embed", None), dt)
    params["wd"], axes["wd"] = layers.dense_init(ks[3], (e, f, d), ("experts", None, "embed"), dt)
    if m.n_shared:
        sp, sa = layers.mlp_init(ks[4], cfg, d_ff=m.d_expert * m.n_shared)
        params["shared"], axes["shared"] = sp, sa
    return params, axes


def _capacity(m, n_tokens):
    return max(1, int(math.ceil(m.top_k * n_tokens * m.capacity_factor
                                / m.n_routed)))


def _dispatch_compute_combine(xt, gate, ids, wg, wu, wd, *, e0, n_experts,
                              capacity, compute_dtype):
    """Local-shard MoE core.  xt: (T,D); gate/ids: (T,k); expert weights are
    this shard's slice (E_loc, D, F).  e0 = first global expert id owned.
    Returns (T,D) partial output (zero rows for tokens routed elsewhere)."""
    t, d = xt.shape
    k = ids.shape[1]
    c = capacity
    cd = compute_dtype

    flat_e = ids.reshape(-1)                              # (t*k,) global ids
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank = jnp.arange(t * k) - first                      # slot within expert
    local_e = sorted_e - e0
    keep = (rank < c) & (local_e >= 0) & (local_e < n_experts)
    dest = jnp.where(keep, local_e * c + rank, n_experts * c)
    slot_src = jnp.full((n_experts * c + 1,), t * k, jnp.int32).at[dest].set(
        order.astype(jnp.int32))[: n_experts * c]
    src_token = jnp.where(slot_src < t * k, slot_src // k, t)

    xpad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    buf = jnp.take(xpad, src_token, axis=0).reshape(n_experts, c, d)

    g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(cd))
    u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(cd))
    h = jax.nn.silu(g) * u
    yb = jnp.einsum("ecf,efd->ecd", h, wd.astype(cd))     # (E_loc,C,D)

    flat_gate = gate.reshape(-1)[order]
    slot_gate = jnp.zeros((n_experts * c + 1,), jnp.float32).at[dest].set(
        jnp.where(keep, flat_gate, 0.0))[: n_experts * c]
    yw = yb.reshape(n_experts * c, d).astype(jnp.float32) * slot_gate[:, None]
    out = jnp.zeros((t + 1, d), jnp.float32).at[src_token].add(yw)[:t]
    return out.astype(cd)


def moe_apply(p, x, cfg, env):
    """x: (B,S,D) -> (B,S,D).  Aux loss returned separately."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = m.n_routed, m.top_k
    cd = cfg.compute_dtype
    xt = x.reshape(t, d)

    # ---- routing (fp32, global semantics) -------------------------------- #
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate, ids = jax.lax.top_k(probs, k)                   # (t,k)
    if m.norm_topk:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch-style)
    me = probs.mean(axis=0)
    load = jnp.zeros((e,), jnp.float32).at[ids.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * load)

    # ---- expert compute --------------------------------------------------- #
    tp = env.tp
    if env.mesh is None or tp == 1 or (e % max(tp, 1) != 0):
        out = _dispatch_compute_combine(
            xt, gate, ids, p["wg"], p["wu"], p["wd"], e0=0, n_experts=e,
            capacity=_capacity(m, t), compute_dtype=cd)
        if env.mesh is not None and tp > 1:
            out = env.constrain(out.reshape(b, s, d), ("batch", None, None))
            out = out.reshape(t, d)
    else:
        dp_total = env.dp
        t_loc = t // dp_total if t % dp_total == 0 else t
        cap = _capacity(m, t_loc)
        e_loc = e // tp
        axis = env.model_axis
        dspec = env.data_axes if len(env.data_axes) > 1 else env.data_axes[0]
        tok_spec = P(dspec) if t % dp_total == 0 else P()

        def body(xt, gate, ids, wg, wu, wd):
            j = jax.lax.axis_index(axis)
            out = _dispatch_compute_combine(
                xt, gate, ids, wg, wu, wd, e0=j * e_loc, n_experts=e_loc,
                capacity=cap, compute_dtype=cd)
            return jax.lax.psum(out, axis)

        out = jax.shard_map(
            body, mesh=env.mesh,
            in_specs=(P(*tok_spec, None), P(*tok_spec, None), P(*tok_spec, None),
                      P(axis, None, None), P(axis, None, None),
                      P(axis, None, None)),
            out_specs=P(*tok_spec, None),
            check_vma=False,
        )(xt, gate, ids, p["wg"], p["wu"], p["wd"])

    out = out.reshape(b, s, d)
    if m.n_shared:
        out = out + layers.mlp_apply(p["shared"], x, cfg)
    return out, aux
