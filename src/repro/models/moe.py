"""Mixture-of-Experts layers: token-choice top-k routing.

Two interchangeable implementations (numerics identical):

* ``dense`` — masked all-expert compute combined by gate weights.  Shards
  cleanly (experts or ff over the 'model' axis) and compiles everywhere;
  costs E/k× extra FLOPs — visible in the roofline's MODEL/HLO ratio and
  the target of a §Perf iteration.
* ``ragged`` — sort-by-expert + ``lax.ragged_dot`` grouped GEMMs
  (dropless); FLOPs ∝ k, the optimized arm.

The router chain (softmax → top-k gate normalization) is a Row-template
fusion site.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def moe_params(key, cfg: ModelConfig, dtype) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    kg, k1, k2, k3 = jax.random.split(key, 4)
    s_in, s_out = d ** -0.5, f ** -0.5
    p = {"router": jax.random.normal(kg, (d, e), dtype) * s_in,
         "w1": jax.random.normal(k1, (e, d, f), dtype) * s_in,
         "w2": jax.random.normal(k2, (e, f, d), dtype) * s_out}
    if cfg.mlp_type in ("swiglu", "geglu"):
        p["w3"] = jax.random.normal(k3, (e, d, f), dtype) * s_in
    return p


def _gates(x, router, k):
    """(T, E) normalized top-k gate weights + aux load-balance loss."""
    logits = (x @ router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(topi, probs.shape[-1],
                                   dtype=probs.dtype)
                    * topv[..., None], axis=1)           # (T, E)
    # Switch-style load-balance aux loss
    e = probs.shape[-1]
    frac = jnp.mean(gates > 0, axis=0)
    aux = e * jnp.sum(frac * jnp.mean(probs, axis=0))
    return gates.astype(x.dtype), topv, topi, aux


def _act(cfg):
    return jax.nn.silu if cfg.mlp_type == "swiglu" else jax.nn.gelu


def moe_dense(x: jnp.ndarray, p: dict, cfg: ModelConfig):
    """x: (T, d) → (T, d).  All experts compute, gates combine."""
    gates, _, _, aux = _gates(x, p["router"], cfg.top_k)
    h = jnp.einsum("td,edf->tef", x, p["w1"])
    if "w3" in p:
        h = _act(cfg)(h) * jnp.einsum("td,edf->tef", x, p["w3"])
    else:
        h = _act(cfg)(h)
    y = jnp.einsum("tef,efd->ted", h, p["w2"])
    out = jnp.einsum("ted,te->td", y, gates)
    return out.astype(x.dtype), aux


def moe_ragged(x: jnp.ndarray, p: dict, cfg: ModelConfig):
    """Dropless sort-based routing with grouped (ragged) GEMMs."""
    T, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    _, topv, topi, aux = _gates(x, p["router"], k)
    flat_e = topi.reshape(-1)                      # (T*k,)
    flat_w = topv.reshape(-1).astype(x.dtype)
    order = jnp.argsort(flat_e)
    inv = jnp.argsort(order)
    xs = jnp.repeat(x, k, axis=0)[order]           # (T*k, d) sorted
    group_sizes = jnp.bincount(flat_e, length=e).astype(jnp.int32)
    h = jax.lax.ragged_dot(xs, p["w1"], group_sizes)
    if "w3" in p:
        h = _act(cfg)(h) * jax.lax.ragged_dot(xs, p["w3"], group_sizes)
    else:
        h = _act(cfg)(h)
    y = jax.lax.ragged_dot(h, p["w2"], group_sizes)
    y = y[inv] * flat_w[:, None]
    out = jnp.sum(y.reshape(T, k, d), axis=1)
    return out.astype(x.dtype), aux


def moe_capacity(x: jnp.ndarray, p: dict, cfg: ModelConfig,
                 capacity_factor: float = 1.25):
    """GShard-style capacity dispatch: sort (token, slot) pairs by expert,
    scatter into a (E, C, d) buffer, run per-expert batched GEMMs, gather
    back.  FLOPs = E·C·(GEMMs) ∝ k·capacity_factor — the §Perf optimized
    arm vs the E/k-overcompute of ``moe_dense`` (tokens beyond capacity
    drop to the residual path, standard Switch/GShard semantics)."""
    T, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    _, topv, topi, aux = _gates(x, p["router"], k)
    flat_e = topi.reshape(-1)                       # (T*k,)
    flat_w = topv.reshape(-1).astype(x.dtype)
    tok_of = jnp.repeat(jnp.arange(T), k)

    order = jnp.argsort(flat_e)
    ranked_e = flat_e[order]
    ranked_tok = tok_of[order]
    ranked_w = flat_w[order]
    # position within expert group: running index minus group start
    starts = jnp.searchsorted(ranked_e, jnp.arange(e), side="left")
    pos_in_grp = jnp.arange(T * k) - starts[ranked_e]

    C = max(1, int(T * k / e * capacity_factor))
    keep = pos_in_grp < C
    slot = jnp.where(keep, ranked_e * C + pos_in_grp, e * C)  # overflow bin
    buf = jnp.zeros((e * C + 1, d), x.dtype).at[slot].set(x[ranked_tok])
    buf = buf[:e * C].reshape(e, C, d)

    h = jnp.einsum("ecd,edf->ecf", buf, p["w1"])
    if "w3" in p:
        h = _act(cfg)(h) * jnp.einsum("ecd,edf->ecf", buf, p["w3"])
    else:
        h = _act(cfg)(h)
    y = jnp.einsum("ecf,efd->ecd", h, p["w2"]).reshape(e * C, d)

    contrib = jnp.where(keep[:, None], y[jnp.minimum(slot, e * C - 1)]
                        * ranked_w[:, None], 0.0)
    out = jnp.zeros((T, d), x.dtype).at[ranked_tok].add(contrib)
    return out.astype(x.dtype), aux


def moe_a2a(x: jnp.ndarray, p: dict, cfg: ModelConfig,
            capacity_factor: float = 1.25):
    """Expert-parallel dispatch with explicit ``shard_map`` + all_to_all.

    The capacity dispatch's scatter/gather are *device-local* (no GSPMD
    inference on data-dependent indices), and tokens travel to their
    expert's shard via one all_to_all over the EP ('model') axis each
    way — the production fix for the collective blow-up measured on the
    GSPMD capacity arm (EXPERIMENTS.md §Perf Cell 2/3 it2).

    Requires E % ep == 0 (olmoe 64/16, jamba 16/16).  Activates only
    inside ``activation_rules`` (the mesh carrier); otherwise falls back
    to the local capacity dispatch.
    """
    from repro.dist import sharding as shlib
    rules = shlib.current_rules()
    if rules is None:
        return moe_capacity(x, p, cfg, capacity_factor)
    mesh, _mode = rules
    if not shlib.moe_expert_parallel(mesh, cfg):
        return moe_capacity(x, p, cfg, capacity_factor)
    EP = shlib.TP_AXIS              # experts travel over the TP axis
    e, k, d = cfg.n_experts, cfg.top_k, x.shape[-1]
    fsdp = shlib.fsdp_axes(mesh)
    # shard_map would reject a token count that doesn't split over the
    # data axes — degrade like every other rule instead of erroring
    if not fsdp or x.shape[0] % shlib.axis_size(mesh, fsdp) != 0:
        return moe_capacity(x, p, cfg, capacity_factor)

    from jax.sharding import PartitionSpec as P

    has_w3 = "w3" in p

    def local(x_loc, router, w1, w2, w3):
        # x_loc: (T_loc, d); expert weights: local shard (E/ep, d, f)
        T_loc = x_loc.shape[0]
        _, topv, topi, aux = _gates(x_loc, router, k)
        flat_e = topi.reshape(-1)
        flat_w = topv.reshape(-1).astype(x_loc.dtype)
        tok_of = jnp.repeat(jnp.arange(T_loc), k)
        order = jnp.argsort(flat_e)
        ranked_e, ranked_tok = flat_e[order], tok_of[order]
        ranked_w = flat_w[order]
        starts = jnp.searchsorted(ranked_e, jnp.arange(e), side="left")
        pos = jnp.arange(T_loc * k) - starts[ranked_e]
        C = max(1, int(T_loc * k / e * capacity_factor))
        keep = pos < C
        slot = jnp.where(keep, ranked_e * C + pos, e * C)
        buf = jnp.zeros((e * C + 1, d), x_loc.dtype) \
            .at[slot].set(x_loc[ranked_tok])
        buf = buf[:e * C].reshape(e, C, d)
        # ship each expert's rows to its owner: (E, C, d) → (E/ep, ep·C, d)
        buf = jax.lax.all_to_all(buf, EP, split_axis=0, concat_axis=1,
                                 tiled=True)
        h = jnp.einsum("ecd,edf->ecf", buf, w1)
        if has_w3:
            h = _act(cfg)(h) * jnp.einsum("ecd,edf->ecf", buf, w3)
        else:
            h = _act(cfg)(h)
        y = jnp.einsum("ecf,efd->ecd", h, w2)
        # ship results home: (E/ep, ep·C, d) → (E, C, d)
        y = jax.lax.all_to_all(y, EP, split_axis=1, concat_axis=0,
                               tiled=True)
        y = y.reshape(e * C, d)
        contrib = jnp.where(keep[:, None],
                            y[jnp.minimum(slot, e * C - 1)]
                            * ranked_w[:, None], 0.0)
        out = jnp.zeros((T_loc, d), x_loc.dtype).at[ranked_tok].add(contrib)
        # aux is identical across 'model' (x replicated there); average
        # over the data shards
        for a in fsdp:
            aux = jax.lax.pmean(aux, a)
        return out.astype(x_loc.dtype), aux

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(fsdp, None), P(None, None),
                  P(EP, None, None), P(EP, None, None),
                  P(EP, None, None)),
        out_specs=(P(fsdp, None), P()),
        check_vma=False)
    return fn(x, p["router"], p["w1"], p["w2"],
              p["w3"] if has_w3 else p["w1"])


def moe(x: jnp.ndarray, p: dict, cfg: ModelConfig):
    """x: (B, S, d) → (B, S, d), plus load-balance aux scalar."""
    B, S, d = x.shape
    flat = x.reshape(B * S, d)
    fn = {"ragged": moe_ragged, "capacity": moe_capacity,
          "dense": moe_dense, "a2a": moe_a2a}[cfg.moe_impl]
    out, aux = fn(flat, p, cfg)
    return out.reshape(B, S, d), aux
