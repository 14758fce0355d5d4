"""Mixture-of-Experts FFN with sort-based (dropping) dispatch.

Design: tokens are routed top-k, flattened to (T·k) assignments, sorted by
expert id, ranked within each expert's run, and scattered into a dense
``(E, C, D)`` buffer (C = capacity).  Expert FFNs run as batched einsums over
the expert axis; results are gathered back with routing weights.  Assignments
beyond capacity are dropped (standard capacity-factor semantics).

Expert parallelism: the (E, C, D) buffer and all expert weights carry the
``experts`` logical axis → the `model` mesh axis; GSPMD turns the scatter /
gather into all-to-alls across the model axis.  Experts are padded to a
multiple of 16 (``cfg.experts_padded``) with −inf router logits so padded
experts never receive tokens.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np


from .layers import dense_init, pdtype


def moe_init(key, cfg):
    d, f = cfg.d_model, cfg.moe_d_ff
    e = cfg.experts_padded
    dt = pdtype(cfg)
    ks = jax.random.split(key, 4)
    params, axes = {}, {}
    # router is replicated: every device routes its local tokens over all experts
    params["router"], axes["router"] = dense_init(
        ks[0], (d, e), (None, None), dtype=jnp.float32)
    params["w_gate"], axes["w_gate"] = dense_init(
        ks[1], (e, d, f), ("experts", "embed", "expert_mlp"),
        scale=1.0 / np.sqrt(d), dtype=dt)
    params["w_up"], axes["w_up"] = dense_init(
        ks[2], (e, d, f), ("experts", "embed", "expert_mlp"),
        scale=1.0 / np.sqrt(d), dtype=dt)
    params["w_down"], axes["w_down"] = dense_init(
        ks[3], (e, f, d), ("experts", "expert_mlp", "embed"),
        scale=1.0 / np.sqrt(f), dtype=dt)
    return params, axes


def moe_apply_dense(p, x, cfg, ctx):
    """No-drop MoE for decode steps: every expert runs on every token, outputs
    are combined with (renormalized) top-k gates.  Exact (capacity-free)
    routing semantics; compute is E/k× the routed path, which is the right
    trade at decode batch sizes — it avoids the dispatch all-to-alls entirely
    and keeps decode causal/deterministic.

    x: (B, S, D) with small B·S. Returns (out, aux=0).
    """
    B, S, D = x.shape
    T = B * S
    E = cfg.experts_padded
    k = cfg.top_k
    xf = x.reshape(T, D)
    logits = xf.astype(jnp.float32) @ p["router"]
    if E > cfg.n_experts:
        logits = jnp.where(jnp.arange(E)[None, :] >= cfg.n_experts, -jnp.inf, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    gates = jnp.zeros((T, E), jnp.float32).at[
        jnp.repeat(jnp.arange(T), k), idx.reshape(-1)].add(w.reshape(-1))

    h = jax.nn.silu(jnp.einsum("td,edf->tef", xf, p["w_gate"]))
    h = h * jnp.einsum("td,edf->tef", xf, p["w_up"])
    y_all = jnp.einsum("tef,efd->ted", h, p["w_down"])     # (T, E, D)
    y = jnp.einsum("ted,te->td", y_all.astype(jnp.float32), gates)
    return y.astype(x.dtype).reshape(B, S, D), jnp.zeros((), jnp.float32)


def moe_apply(p, x, cfg, ctx):
    """Routed MoE FFN. x: (B, S, D) → (out (B, S, D), aux_loss scalar f32).

    With a mesh in ctx (and divisible shapes) this uses the expert-parallel
    shard_map path (explicit all-to-alls); otherwise the single-device global
    formulation.
    """
    if ctx is not None and getattr(ctx, "mesh", None) is not None:
        mesh = ctx.mesh
        B, S, D = x.shape
        data_ax = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
        dsize = 1
        for a in data_ax:
            dsize *= mesh.shape[a]
        msize = mesh.shape["model"]
        if (B % dsize == 0 and S % msize == 0
                and cfg.experts_padded % msize == 0):
            return _moe_apply_ep(p, x, cfg, ctx, data_ax, msize)
    return _moe_apply_global(p, x, cfg, ctx)


def _moe_apply_ep(p, x, cfg, ctx, data_ax, msize):
    """Expert-parallel dispatch inside shard_map (GShard-style).

    Tokens are sharded (batch → data axes, sequence → model axis); each device
    routes its local tokens, builds a per-(device, expert) capacity buffer,
    exchanges it with two ``all_to_all``s over the model axis around the
    expert FFN, and combines locally.  Capacity is per source device —
    standard EP semantics.
    """
    import numpy as np
    mesh = ctx.mesh
    E = cfg.experts_padded
    k = cfg.top_k
    from jax.sharding import PartitionSpec as P

    x_spec = P(data_ax, "model", None)
    w_spec = P("model", None, None)
    all_axes = tuple(mesh.axis_names)

    def local(xl, router, w_gate, w_up, w_down):
        Bl, Sl, D = xl.shape
        T = Bl * Sl
        xf = xl.reshape(T, D)
        logits = xf.astype(jnp.float32) @ router
        if E > cfg.n_experts:
            logits = jnp.where(jnp.arange(E)[None, :] >= cfg.n_experts,
                               -jnp.inf, logits)
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, k)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)

        me = probs.mean(axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (T * k)
        aux_local = (me * ce).sum() * cfg.n_experts
        aux = jax.lax.pmean(aux_local, all_axes)

        fe = idx.reshape(-1)
        fw = w.reshape(-1)
        ftok = jnp.repeat(jnp.arange(T), k)
        order = jnp.argsort(fe, stable=True)
        fe_s, fw_s, ftok_s = fe[order], fw[order], ftok[order]
        seg_start = jnp.searchsorted(fe_s, jnp.arange(E))
        rank = jnp.arange(T * k) - seg_start[fe_s]
        cap = int(np.ceil(cfg.capacity_factor * T * k / E))
        cap = max(4, ((cap + 3) // 4) * 4)
        keep = rank < cap
        rank_c = jnp.where(keep, rank, 0)

        buf = jnp.zeros((E, cap, D), xl.dtype)
        buf = buf.at[fe_s, rank_c].add(jnp.where(keep[:, None], xf[ftok_s], 0))

        # route to expert owners: (E, cap, D) -> (E/m, m·cap, D)
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                                 tiled=True)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate))
        h = h * jnp.einsum("ecd,edf->ecf", buf, w_up)
        out = jnp.einsum("ecf,efd->ecd", h, w_down)
        # route back: (E/m, m·cap, D) -> (E, cap, D)
        out = jax.lax.all_to_all(out, "model", split_axis=1, concat_axis=0,
                                 tiled=True)

        gathered = out[fe_s, rank_c]
        contrib = gathered * (fw_s * keep).astype(gathered.dtype)[:, None]
        y = jnp.zeros((T, D), xl.dtype).at[ftok_s].add(contrib)
        return y.reshape(Bl, Sl, D), aux

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, w_spec),
        out_specs=(x_spec, P()),
        check_vma=False)
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def _moe_apply_global(p, x, cfg, ctx):
    """Single-device / no-mesh fallback (same math, global capacity)."""
    B, S, D = x.shape
    T = B * S
    E = cfg.experts_padded
    k = cfg.top_k
    xf = x.reshape(T, D)

    logits = (xf.astype(jnp.float32) @ p["router"])  # (T, E) f32
    if E > cfg.n_experts:  # mask padded experts
        pad_mask = jnp.arange(E) >= cfg.n_experts
        logits = jnp.where(pad_mask[None, :], -jnp.inf, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)                 # (T, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch-style)
    me = probs.mean(axis=0)                                   # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (T * k)
    aux = (me * ce).sum() * (cfg.n_experts ** 2) / cfg.n_experts

    # flatten assignments and rank within expert
    fe = idx.reshape(-1)                                       # (T*k,)
    fw = w.reshape(-1)
    ftok = jnp.repeat(jnp.arange(T), k)
    order = jnp.argsort(fe, stable=True)
    fe_s, fw_s, ftok_s = fe[order], fw[order], ftok[order]
    seg_start = jnp.searchsorted(fe_s, jnp.arange(E))          # (E,)
    rank = jnp.arange(T * k) - seg_start[fe_s]

    cap = int(np.ceil(cfg.capacity_factor * T * k / E))
    cap = max(4, ((cap + 3) // 4) * 4)
    keep = rank < cap
    rank_c = jnp.where(keep, rank, 0)

    buf = jnp.zeros((E, cap, D), x.dtype)
    vals = jnp.where(keep[:, None], xf[ftok_s], 0)
    buf = buf.at[fe_s, rank_c].add(vals)
    if ctx is not None:
        buf = ctx.constrain(buf, ("experts", None, None))

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    if ctx is not None:
        out_buf = ctx.constrain(out_buf, ("experts", None, None))

    gathered = out_buf[fe_s, rank_c]                           # (T*k, D)
    contrib = gathered * (fw_s * keep).astype(gathered.dtype)[:, None]
    y = jnp.zeros((T, D), x.dtype).at[ftok_s].add(contrib)
    return y.reshape(B, S, D), aux
