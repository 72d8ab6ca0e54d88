"""Mixture-of-Experts layer: top-k routing with static-capacity dispatch.

Dispatch is sort-free and static-shaped: per-expert slot positions come from
a one-hot cumulative sum, tokens beyond an expert's capacity are dropped
(standard Switch/GShard semantics; capacity_factor sizes the buffers).  The
(E, C, d) expert buffers are sharded over the "model" (and optionally
"data") mesh axes -> XLA SPMD inserts the all_to_all token exchange, the
exact expert-parallel communication pattern of DeepSeek-style training.

Routers: 'softmax' (classic, with jitter-free argmax top-k) and 'sigmoid'
(DeepSeek-V3 aux-loss-free: sigmoid affinities, top-k, weights normalized
over the selected experts).  A load-balance auxiliary loss is returned for
the softmax router.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import normal


def init_moe(cfg, key, dtype=jnp.float32):
    m = cfg.moe
    d, f = cfg.d_model, m.d_ff_expert
    ks = jax.random.split(key, 7)
    s_in, s_out = (2.0 / d) ** 0.5, (2.0 / f) ** 0.5
    e = m.n_experts
    p = {
        "router": normal(ks[0], (d, e), 0.02, jnp.float32),
        "wi": normal(ks[1], (e, d, f), s_in, dtype),
        "wo": normal(ks[2], (e, f, d), s_out, dtype),
    }
    if cfg.act == "swiglu":
        p["wg"] = normal(ks[3], (e, d, f), s_in, dtype)
    if m.n_shared:
        fs = f * m.n_shared
        p["sh_wi"] = normal(ks[4], (d, fs), s_in, dtype)
        p["sh_wo"] = normal(ks[5], (fs, d), s_out, dtype)
        if cfg.act == "swiglu":
            p["sh_wg"] = normal(ks[6], (d, fs), s_in, dtype)
    return p


def _route(cfg, p, x2):
    """x2: (T, d) -> (weights (T,k), experts (T,k), aux_loss)."""
    m = cfg.moe
    logits = (x2.astype(jnp.float32) @ p["router"])        # (T, E)
    if m.router == "sigmoid":
        aff = jax.nn.sigmoid(logits)
        w, idx = jax.lax.top_k(aff, m.top_k)
        w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
        aux = jnp.zeros((), jnp.float32)                   # aux-free routing
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, m.top_k)
        w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
        # Switch-style load-balance loss
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(
            jnp.sum(jax.nn.one_hot(idx[:, 0], m.n_experts), axis=0)
            / x2.shape[0])
        aux = m.n_experts * jnp.sum(me * ce)
    return w, idx, aux


def apply_moe(cfg, p, x):
    """x: (B, S, d) -> (y, aux_loss). Dispatch:
    'dense' one-hot scatter (single-device / baseline), or the shard_map
    expert-parallel path when a production mesh is active."""
    impl = getattr(cfg, "moe_impl", "auto")
    if impl != "dense":
        mesh = jax.sharding.get_abstract_mesh()
        if not mesh.empty and "model" in mesh.axis_names:
            t = x.shape[0] * x.shape[1]
            n_all = 1
            for a in mesh.axis_names:
                n_all *= mesh.shape[a]
            if t % n_all == 0 and t >= n_all:
                return apply_moe_ep(cfg, p, x, mesh)
    return apply_moe_dense(cfg, p, x)


def apply_moe_dense(cfg, p, x):
    """Reference dense dispatch (used on CPU and as the perf baseline)."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    x2 = x.reshape(t, d)
    w, idx, aux = _route(cfg, p, x2)                       # (T,k)

    e = m.n_experts
    cap = max(int(t * m.top_k / e * m.capacity_factor), 4)

    # slot assignment: position of each (token, choice) within its expert
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)       # (T, k, E)
    flat = onehot.reshape(t * m.top_k, e)
    pos_in_e = jnp.cumsum(flat, axis=0) - flat             # (T*k, E)
    slot = jnp.sum(pos_in_e * flat, axis=-1)               # (T*k,)
    eid = idx.reshape(-1)
    keep = slot < cap
    # scatter tokens into (E, C, d) buffers (dropped tokens vanish)
    buf = jnp.zeros((e, cap, d), x.dtype)
    tok = jnp.repeat(jnp.arange(t), m.top_k)
    buf = buf.at[eid, jnp.minimum(slot, cap - 1)].add(
        jnp.where(keep[:, None], x2[tok], 0))

    # expert computation: batched matmuls sharded over the expert axis (EP)
    if cfg.act == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"])) * \
            jnp.einsum("ecd,edf->ecf", buf, p["wi"])
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, p["wi"]))
    out = jnp.einsum("ecf,efd->ecd", h, p["wo"])           # (E, C, d)

    # combine: gather each kept (token, choice) result, weight, and sum
    gathered = out[eid, jnp.minimum(slot, cap - 1)]        # (T*k, d)
    gathered = jnp.where(keep[:, None], gathered, 0)
    wk = w.reshape(-1)[:, None].astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[tok].add(gathered * wk)

    if m.n_shared:
        if cfg.act == "swiglu":
            hs = jax.nn.silu(x2 @ p["sh_wg"]) * (x2 @ p["sh_wi"])
        else:
            hs = jax.nn.gelu(x2 @ p["sh_wi"])
        y = y + hs @ p["sh_wo"]
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel path: shard_map + all_to_all (the DeepSeek EP pattern)
# ---------------------------------------------------------------------------
#
# GSPMD cannot partition the data-dependent scatter of the dense dispatch
# across the expert axis; it falls back to REPLICATING the (E, C, d) expert
# buffers (multi-GB all-gathers per layer - measured in the baseline
# dry-run, EXPERIMENTS.md SPerf). Inside shard_map every index is local, so
# the dispatch is a cheap local scatter and the only communication is the
# unavoidable token all_to_all - the paper-era (GShard/DeepSeek) EP design.
#
# Layout: tokens sharded over ALL mesh axes (the model axis joins DP for
# the MoE block - sequence-parallel style); experts sharded over
# ("data","model") when divisible, else ("model",). Each device scatters
# its local tokens into per-destination-device send buffers, all_to_all
# exchanges them, experts run locally, and the inverse all_to_all returns
# outputs for a weighted local combine.

def _ep_axes(mesh, n_experts):
    for axes in (("data", "model"), ("model",)):
        if all(a in mesh.axis_names for a in axes):
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            if n_experts % n == 0 and n_experts >= n:
                return axes, n
    return None, 1


def apply_moe_ep(cfg, p, x, mesh):
    """shard_map boundary kept at the surrounding activation sharding
    P(('pod','data')); the model-axis token split happens INSIDE the body
    (dynamic_slice by axis_index + tiled all_gather on the way out), so
    forward activations and backward cotangents share one sharding and
    GSPMD never invents hybrid layouts (which measurably fall back to
    multi-GB replicating all-gathers in the dense-layer backward)."""
    from jax.sharding import PartitionSpec as P
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    exp_axes, n_exp_dev = _ep_axes(mesh, m.n_experts)
    if exp_axes is None:
        return apply_moe_dense(cfg, p, x)
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_dp = 1
    for a in dp_axes:
        n_dp *= mesh.shape[a]
    n_tp = mesh.shape.get("model", 1)
    if t % (n_dp * n_tp):
        return apply_moe_dense(cfg, p, x)
    t_dp = t // n_dp                    # tokens per dp shard
    t_me = t_dp // n_tp                 # tokens this model-rank works on
    e_per_dev = m.n_experts // n_exp_dev
    cap = max(int(t_me * m.top_k / m.n_experts * m.capacity_factor), 1)

    x2 = x.reshape(t, d)

    def body(x_loc, router, wi, wg, wo, sh):
        """x_loc: (t_dp, d) - replicated over 'model'; each model-rank
        processes its slice. wi/wg/wo: (e_per_dev, ...)."""
        mi = jax.lax.axis_index("model")
        x_me = jax.lax.dynamic_slice(x_loc, (mi * t_me, jnp.zeros((),
                                                                  mi.dtype)),
                                     (t_me, d))
        w, idx, aux = _route_local(cfg, router, x_me)
        aux = jax.lax.pmean(aux, dp_axes + ("model",))
        # local scatter into per-destination send buffers
        eid = idx.reshape(-1)                              # (t_me*k,)
        dev = eid // e_per_dev
        sub = eid % e_per_dev
        onehot = jax.nn.one_hot(eid, m.n_experts, dtype=jnp.int32)
        pos_in_e = jnp.cumsum(onehot, axis=0) - onehot
        slot = jnp.sum(pos_in_e * onehot, axis=-1)         # per-expert slot
        keep = slot < cap
        addr = sub * cap + jnp.minimum(slot, cap - 1)      # within dest dev
        tok = jnp.repeat(jnp.arange(t_me), m.top_k)
        send = jnp.zeros((n_exp_dev, e_per_dev * cap, d), x_loc.dtype)
        send = send.at[dev, addr].add(
            jnp.where(keep[:, None], x_me[tok], 0))

        # token exchange: one all_to_all there...
        recv = jax.lax.all_to_all(send, exp_axes, split_axis=0,
                                  concat_axis=0, tiled=True)
        # recv[j] = tokens from device j for MY experts
        toks = recv.reshape(n_exp_dev, e_per_dev, cap, d) \
                   .transpose(1, 0, 2, 3).reshape(e_per_dev,
                                                  n_exp_dev * cap, d)
        if cfg.act == "swiglu":
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", toks, wg)) * \
                jnp.einsum("ecd,edf->ecf", toks, wi)
        else:
            h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", toks, wi))
        out = jnp.einsum("ecf,efd->ecd", h, wo)
        # ... and one back
        back = out.reshape(e_per_dev, n_exp_dev, cap, d) \
                  .transpose(1, 0, 2, 3).reshape(n_exp_dev,
                                                 e_per_dev * cap, d)
        got = jax.lax.all_to_all(back, exp_axes, split_axis=0,
                                 concat_axis=0, tiled=True)
        # local combine for this model-rank's tokens
        gathered = got[dev, addr]
        gathered = jnp.where(keep[:, None], gathered, 0)
        wk = w.reshape(-1)[:, None].astype(x_loc.dtype)
        y_me = jnp.zeros((t_me, d), x_loc.dtype).at[tok].add(gathered * wk)

        if m.n_shared:  # shared experts: ffn-sharded over 'model' instead
            if cfg.act == "swiglu":
                hs = jax.nn.silu(x_me @ sh["sh_wg"]) * (x_me @ sh["sh_wi"])
            else:
                hs = jax.nn.gelu(x_me @ sh["sh_wi"])
            y_me = y_me + hs @ sh["sh_wo"]
        # reassemble the dp-shard from the 16 model-rank slices
        return jax.lax.all_gather(y_me, "model", axis=0, tiled=True), aux

    sh_params = {k: v for k, v in p.items() if k.startswith("sh_")}
    wg = p.get("wg", p["wi"])
    exp_spec = P(exp_axes if len(exp_axes) > 1 else exp_axes[0], None, None)
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_axes, None), P(), exp_spec, exp_spec, exp_spec,
                  P()),
        out_specs=(P(dp_axes, None), P()),
        check_vma=False,
    )(x2, p["router"], p["wi"], wg, p["wo"], sh_params)
    return y.reshape(b, s, d), aux


def _route_local(cfg, router_w, x2):
    m = cfg.moe
    logits = x2.astype(jnp.float32) @ router_w
    if m.router == "sigmoid":
        aff = jax.nn.sigmoid(logits)
        w, idx = jax.lax.top_k(aff, m.top_k)
        w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
        aux = jnp.zeros((), jnp.float32)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, m.top_k)
        w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(idx[:, 0], m.n_experts),
                              axis=0) / x2.shape[0])
        aux = m.n_experts * jnp.sum(me * ce)
    return w, idx, aux
