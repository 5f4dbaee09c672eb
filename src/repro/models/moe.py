"""Mixture-of-Experts layer — GShard/Switch-style dense dispatch (TPU/GSPMD).

Token-choice top-k routing with capacity, einsum dispatch/combine (the
MaxText/GShard lowering that XLA SPMD partitions cleanly over the expert
axis), optional shared experts (DeepSeek-V3: 1 shared + 256 routed top-8;
Llama-4 Scout: 1 shared + 16 routed top-1), and the standard load-balancing
auxiliary loss.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from .layers import init_swiglu, swiglu


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    m = cfg.moe
    ks = jax.random.split(key, 5)
    e = m.n_experts
    d, f = cfg.d_model, m.d_ff_expert
    s_in, s_out = float(1.0 / np.sqrt(d)), float(1.0 / np.sqrt(f))
    p = {
        "router": jax.random.normal(ks[0], (d, e), dtype) * s_in,
        "wg": jax.random.normal(ks[1], (e, d, f), dtype) * s_in,
        "wu": jax.random.normal(ks[2], (e, d, f), dtype) * s_in,
        "wd": jax.random.normal(ks[3], (e, f, d), dtype) * s_out,
    }
    if m.n_shared:
        p["shared"] = init_swiglu(ks[4], d, m.d_ff_expert * m.n_shared, dtype)
    return p


def _top_k_gating(logits: jax.Array, k: int):
    """Top-k gates normalised over the selected experts (DeepSeek-V3 style)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    return probs, gate_vals, idx


def moe_layer(
    x: jax.Array,  # (B, S, D)
    p: dict,
    cfg: ModelConfig,
    capacity_factor: float = 1.25,
) -> tuple[jax.Array, jax.Array]:
    """Returns (output, aux_loss).  Dense dispatch: FLOPs ∝ top_k·T·d·f + dispatch."""
    if cfg.moe_impl == "a2a":
        out = moe_layer_a2a(x, p, cfg, capacity_factor)
        if out is not None:
            return out
    if cfg.moe_group_size > 0:
        return moe_layer_grouped(x, p, cfg, capacity_factor)
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = m.n_experts, m.top_k
    xt = x.reshape(t, d)

    logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs, gates, idx = _top_k_gating(logits, k)  # (T,E), (T,k), (T,k)

    capacity = max(1, int(math.ceil(t * k / e * capacity_factor)))
    # slot-major positions: slot 0 choices get priority (GShard ordering)
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)          # (T, k, E)
    slot_major = jnp.swapaxes(onehot, 0, 1)                   # (k, T, E)
    pos_in_expert = jnp.cumsum(slot_major.reshape(k * t, e), axis=0).reshape(
        k, t, e
    ) - slot_major
    pos = jnp.sum(pos_in_expert * slot_major, axis=-1)        # (k, T)
    expert_of_slot = jnp.swapaxes(idx, 0, 1)                  # (k, T)
    keep = pos < capacity
    gates_km = jnp.swapaxes(gates, 0, 1) * keep.astype(jnp.float32)  # (k, T)

    # dispatch/combine tensors (T, E, C)
    pos_onehot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32) * keep[..., None]
    disp = jnp.einsum(
        "kte,ktc->tec", slot_major.astype(jnp.float32), pos_onehot
    )
    comb = jnp.einsum(
        "kte,ktc,kt->tec", slot_major.astype(jnp.float32), pos_onehot, gates_km
    )

    xin = jnp.einsum("tec,td->ecd", disp.astype(x.dtype), xt)        # (E, C, D)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, p["wg"].astype(x.dtype)))
    u = jnp.einsum("ecd,edf->ecf", xin, p["wu"].astype(x.dtype))
    hexp = jnp.einsum("ecf,efd->ecd", g * u, p["wd"].astype(x.dtype))  # (E, C, D)
    y = jnp.einsum("tec,ecd->td", comb.astype(x.dtype), hexp)

    if m.n_shared:
        y = y + swiglu(xt, p["shared"])

    # load-balance aux loss (Switch): E · Σ_e fraction_e · router_prob_e
    frac = jnp.mean(
        jnp.sum(onehot.astype(jnp.float32), axis=1), axis=0
    )  # (E,) fraction of tokens routed
    prob_mean = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac * prob_mean) * m.aux_loss_coef

    del expert_of_slot
    return y.reshape(b, s, d), aux


def moe_layer_grouped(
    x: jax.Array,  # (B, S, D)
    p: dict,
    cfg: ModelConfig,
    capacity_factor: float = 1.25,
) -> tuple[jax.Array, jax.Array]:
    """§Perf hillclimb variant: GShard *group-blocked* dispatch.

    The naive dispatch materialises a (T, E, C) tensor with C ∝ T — at
    train_4k/deepseek-v3 scale that is the 10 TB temp / 489 TB all-reduce
    pathology in the baseline dry-run.  Blocking tokens into groups of
    ``Tg = cfg.moe_group_size`` makes per-group capacity Cg ∝ Tg (constant),
    so dispatch tensors are (G, Tg, E, Cg) — G·Tg·E·Cg = T·E·Cg elements,
    ~T/Tg× smaller — and shard cleanly: G on the DP axes, E on "model" (EP);
    one-hots are bf16 so the dispatch einsums run on the MXU.
    """
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = m.n_experts, m.top_k
    tg = min(cfg.moe_group_size, t)
    assert t % tg == 0, (t, tg)
    g = t // tg
    cap = max(1, int(math.ceil(tg * k / e * capacity_factor)))
    dt = x.dtype

    from jax.sharding import PartitionSpec as _P

    def wsc(v, spec):
        try:
            return jax.lax.with_sharding_constraint(v, _P(*spec))
        except Exception:  # no ambient mesh (CPU smoke tests): no-op
            return v

    def _mesh_axes_for(dim: int, include_model: bool = True):
        """Largest axis prefix whose product divides ``dim``."""
        try:
            mesh = jax.sharding.get_abstract_mesh()
            names = tuple(mesh.axis_names) if mesh is not None else ()
        except Exception:
            return None
        pool = ("pod", "data", "model") if include_model else ("pod", "data")
        avail = [n for n in pool if n in names]
        best = None
        for kk in range(1, len(avail) + 1):
            prod = 1
            for a in avail[:kk]:
                prod *= mesh.shape[a]
            if dim % prod == 0:
                best = tuple(avail[:kk])
        return best

    # Full-mesh expert parallelism: experts spread over every mesh axis
    # (256 experts / 256 chips ⇒ 1 expert per chip) — expert weights need no
    # inner-dim sharding, so no partial-sum all-reduces and no FSDP
    # regathers; the groups→experts hop is the classic MoE all-to-all of
    # (E, G, Cg, D) activations (small).  Groups stay on the DP axes —
    # pinned explicitly: GSPMD loses the batch sharding through the
    # (B,S,D)→(G,Tg,D) reshape and falls back to full replication otherwise.
    eax = _mesh_axes_for(e)
    gax = _mesh_axes_for(g, include_model=False)  # groups ride the DP axes
    xg = x.reshape(g, tg, d)
    if gax:
        xg = wsc(xg, (gax, None, None))
    logits = xg.astype(jnp.float32) @ p["router"].astype(jnp.float32)  # (G,Tg,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                               # (G,Tg,k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)                   # (G,Tg,k,E)
    slot_major = jnp.moveaxis(onehot, 2, 1)                            # (G,k,Tg,E)
    flat = slot_major.reshape(g, k * tg, e)
    pos = jnp.cumsum(flat, axis=1) - flat                              # pos within (g,e)
    pos = jnp.sum(pos.reshape(g, k, tg, e) * slot_major, axis=-1)      # (G,k,Tg)
    keep = pos < cap
    gates_km = jnp.moveaxis(gates, 2, 1) * keep.astype(jnp.float32)    # (G,k,Tg)
    pos_oh = jax.nn.one_hot(pos, cap, dtype=dt) * keep[..., None].astype(dt)

    disp = jnp.einsum("gkte,gktc->gtec", slot_major.astype(dt), pos_oh)
    comb = jnp.einsum(
        "gkte,gktc,gkt->gtec", slot_major.astype(dt), pos_oh, gates_km.astype(dt)
    )
    if gax:
        disp = wsc(disp, (gax, None, None, None))
        comb = wsc(comb, (gax, None, None, None))

    xin = jnp.einsum("gtec,gtd->egcd", disp, xg)                       # (E,G,Cg,D)
    if eax:
        xin = wsc(xin, (eax, None, None, None))  # → a2a onto expert shards
    gact = jax.nn.silu(jnp.einsum("egcd,edf->egcf", xin, p["wg"].astype(dt)))
    uact = jnp.einsum("egcd,edf->egcf", xin, p["wu"].astype(dt))
    hexp = jnp.einsum("egcf,efd->egcd", gact * uact, p["wd"].astype(dt))
    if eax:
        hexp = wsc(hexp, (eax, None, None, None))
    y = jnp.einsum("gtec,egcd->gtd", comb, hexp)

    if m.n_shared:
        y = y + swiglu(xg.reshape(t, d), p["shared"]).reshape(g, tg, d)

    frac = jnp.mean(jnp.sum(onehot.astype(jnp.float32), axis=2), axis=(0, 1))
    prob_mean = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac * prob_mean) * m.aux_loss_coef
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# shard_map expert-parallel all-to-all MoE (§Perf — the production routing)
# ---------------------------------------------------------------------------


def moe_layer_a2a(
    x: jax.Array,  # (B, S, D)
    p: dict,
    cfg: ModelConfig,
    capacity_factor: float = 1.25,
):
    """Explicit expert-parallel MoE: local dispatch → all_to_all → local
    expert FFN → all_to_all → local combine (DeepSeek-V3's own EP layout).

    GSPMD cannot synthesise token-routing all-to-all from one-hot dispatch
    einsums — every auto-partitioning of them all-gathers activations (§Perf
    iteration log).  ``shard_map`` makes the routing explicit: per-device
    payloads are (E, C, D) send buffers (≈ top_k·T_loc·D·cf bytes), so the
    collective cost scales with *routed tokens*, not with tokens × experts.

    Requires E divisible over the ("data","model") mesh axes and T divisible
    by the device count; returns None to fall back to the einsum path
    otherwise (CPU tests, decode micro-batches).
    """
    from jax.sharding import PartitionSpec as _P

    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = m.n_experts, m.top_k
    try:
        mesh = jax.sharding.get_abstract_mesh()
        names = tuple(mesh.axis_names) if mesh is not None else ()
    except Exception:
        return None
    if not names:
        return None
    a2a_axes = tuple(n for n in ("data", "model") if n in names)
    n_a2a = 1
    for a in a2a_axes:
        n_a2a *= mesh.shape[a]
    all_axes = tuple(n for n in ("pod", "data", "model") if n in names)
    n_dev = 1
    for a in all_axes:
        n_dev *= mesh.shape[a]
    if e != n_a2a or t % n_dev != 0:
        return None

    t_loc = t // n_dev
    cap = max(1, int(math.ceil(t_loc * k / e * capacity_factor)))
    dt = x.dtype

    def local(x_loc, router_w, wg, wu, wd, shared):
        # x_loc: (T_loc, D); wg/wu/wd: (1, D, F)/(1, F, D) — one local expert
        logits = x_loc.astype(jnp.float32) @ router_w.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)                  # (T_loc, E)
        gates, idx = jax.lax.top_k(probs, k)
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)         # (T_loc,k,E)
        slot_major = jnp.swapaxes(onehot, 0, 1)                  # (k,T_loc,E)
        flat = slot_major.reshape(k * t_loc, e)
        pos = (jnp.cumsum(flat, axis=0) - flat).reshape(k, t_loc, e)
        pos = jnp.sum(pos * slot_major, axis=-1)                 # (k,T_loc)
        keep = pos < cap
        gates_km = jnp.swapaxes(gates, 0, 1) * keep.astype(jnp.float32)
        pos_oh = jax.nn.one_hot(pos, cap, dtype=dt) * keep[..., None].astype(dt)

        disp = jnp.einsum("kte,ktc->tec", slot_major.astype(dt), pos_oh)
        comb = jnp.einsum("kte,ktc,kt->tec", slot_major.astype(dt), pos_oh,
                          gates_km.astype(dt))

        send = jnp.einsum("tec,td->ecd", disp, x_loc)            # (E, C, D)
        recv = jax.lax.all_to_all(
            send, a2a_axes, split_axis=0, concat_axis=0, tiled=True
        )                                                        # (E·1? → (E,C,D) rows for MY expert)
        h = recv.reshape(e * cap, d)
        g_act = jax.nn.silu(h @ wg[0].astype(dt))
        u_act = h @ wu[0].astype(dt)
        h_out = (g_act * u_act) @ wd[0].astype(dt)               # (E·C, D)
        back = jax.lax.all_to_all(
            h_out.reshape(e, cap, d), a2a_axes, split_axis=0, concat_axis=0,
            tiled=True,
        )                                                        # (E, C, D) back at source
        y = jnp.einsum("tec,ecd->td", comb, back)
        if m.n_shared:
            y = y + swiglu(x_loc, shared)
        frac = jnp.mean(jnp.sum(onehot.astype(jnp.float32), axis=1), axis=0)
        prob_mean = jnp.mean(probs, axis=0)
        aux = e * jnp.sum(frac * prob_mean) * m.aux_loss_coef
        aux = jax.lax.pmean(aux, all_axes)
        return y, aux

    shared = p.get("shared")
    if shared is None:
        shared = {"wg": jnp.zeros((d, 1), dt), "wu": jnp.zeros((d, 1), dt),
                  "wd": jnp.zeros((1, d), dt)}
    flat_spec = _P(all_axes)
    expert_spec = _P(a2a_axes, None, None)
    rep = _P()
    out = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            _P(all_axes, None), rep, expert_spec, expert_spec,
            _P(a2a_axes, None, None), jax.tree.map(lambda _: rep, shared),
        ),
        out_specs=(_P(all_axes, None), rep),
        check_vma=False,
    )(x.reshape(t, d), p["router"], p["wg"], p["wu"], p["wd"], shared)
    y, aux = out
    return y.reshape(b, s, d), aux
