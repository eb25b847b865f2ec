"""Mixture-of-Experts FFN with grouped capacity dispatch (GShard-style).

Design notes (roofline-driven):
* Dispatch is *index-based* (scatter token-ids into capacity slots, gather
  token activations, run batched expert GEMMs, gather back with combine
  weights).  The classic one-hot dispatch einsum costs N*E*C*D MACs —
  comparable to the expert GEMMs themselves at E=128 — so we avoid it
  entirely; gathers count as bytes, not FLOPs.
* Dispatch is GROUPED: tokens are split into G groups (= the data-parallel
  shard count at trace time), each group gathers its expert buffers
  LOCALLY, and only the (E, G, Cg, D) buffer is resharded data->model for
  the expert GEMMs.  GSPMD lowers that single resharding to an
  all-to-all.  The ungrouped formulation gathered straight from the
  data-sharded token buffer, which GSPMD implements as partial-gather +
  full-buffer ALL-REDUCE — 2(n-1)/n x the whole expert buffer on the wire
  per MoE layer (16 GB/layer on Jamba prefill; found in the first
  roofline pass, see EXPERIMENTS.md §Perf iteration J1).
* Capacity C = ceil(top_k * Ng * cf / E) per group; overflow tokens are
  dropped (contribute only through the shared/residual paths), matching
  capacity-based MoE practice.  Mode-dependent floors in ``_capacity``.

Supports DeepSeek-style shared experts and Arctic's parallel dense
residual.  Router aux losses (load-balance + z-loss) are returned for the
trainer.

``held_moe_ffn`` is the dropless layer of a device that holds a
contiguous range of the experts (``RoutingConfig``, MiMo-V2-Flash): it
routes over all of them and computes, with grouped matmuls
(``jax.lax.ragged_dot``) whose work follows the routed pairs, only its
held experts' part of the result.  No capacity: no token is dropped at
any batch size or routing skew.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import MoEConfig, RoutingConfig
from repro.models.layers import init_dense, init_swiglu, swiglu
from repro.sharding.partition import axis_size, shard


def init_moe(key, d_model: int, mo: MoEConfig, dtype,
             routing: Optional[RoutingConfig] = None) -> Dict:
    """Init router (fp32) + stacked expert SwiGLU weights, plus the
    shared-expert params when configured.  With ``routing`` the router
    keeps all ``n_experts`` outputs (and a selection bias) while only
    the held experts' weights exist here."""
    keys = jax.random.split(key, 8)
    E, ff = mo.n_experts, mo.expert_ff
    n_local = E
    if routing is not None:
        lo, hi = routing.held_range(E)
        n_local = hi - lo
    p = {
        "router": init_dense(keys[0], (d_model, E), d_model, jnp.float32),
        "wi_e": init_dense(keys[1], (n_local, d_model, ff), d_model, dtype),
        "wg_e": init_dense(keys[2], (n_local, d_model, ff), d_model, dtype),
        "wo_e": init_dense(keys[3], (n_local, ff, d_model), ff, dtype),
    }
    if routing is not None:
        p["bias"] = jnp.zeros((E,), jnp.float32)
    if mo.n_shared_experts:
        p["shared"] = init_swiglu(keys[4], d_model,
                                  mo.n_shared_experts * ff, dtype)
    if mo.dense_residual:
        p["residual"] = init_swiglu(keys[5], d_model,
                                    mo.dense_residual_ff or ff, dtype)
    return p


def _capacity(n_tokens: int, mo: MoEConfig, mode: str) -> int:
    """Expert capacity per dispatch group.

    * decode: capacity_factor floored at 4.0 (capped at N) — near-dropless
      with negligible FLOP padding.  The earlier C = N choice guaranteed
      exactness but computed E/top_k x the active FLOPs on wide-expert
      models (64x on Arctic's E=128); consistency tests pin
      capacity_factor = E, which still yields C = N;
    * prefill/calibrate: capacity_factor floored at 2.0 (drops are rare
      and documented as the capacity-MoE serving approximation);
    * train: the configured capacity_factor (GShard-style dropping).
    """
    floor = {"decode": 4.0, "train": 0.0}.get(mode, 2.0)
    cf = max(mo.capacity_factor, floor)
    c = int(math.ceil(mo.top_k * n_tokens * cf / mo.n_experts))
    return max(1, min(c, n_tokens))


def moe_ffn(p: Dict, x: jnp.ndarray, mo: MoEConfig, mode: str = "train",
            n_groups: Optional[int] = None,
            token_mask: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B, S, D) -> (y, aux_losses).

    ``token_mask``: optional (B, S) bool of *live* tokens.  Masked
    tokens (finished/empty serving slots in the fused decode scan) are
    excluded from capacity assignment — they claim no expert slots, so
    dead slots cannot crowd live tokens out in dropping configs — and
    from the router aux statistics.  Their output rows are zero.
    """
    B, S, D = x.shape
    N = B * S
    E, K = mo.n_experts, mo.top_k
    G = n_groups or axis_size(("pod", "data"))
    if N % G:
        G = 1
    Ng = N // G
    C = _capacity(Ng, mo, mode)
    xg = x.reshape(G, Ng, D)
    xg = shard(xg, ("pod", "data"), None, None)

    logits = jnp.einsum("gnd,de->gne", xg.astype(jnp.float32),
                        p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                  # (G, Ng, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)          # (G, Ng, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert's capacity slots,
    # computed per group (local to the data shard)
    flat_e = expert_idx.reshape(G, Ng * K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)      # (G, NgK, E)
    live = None
    if token_mask is not None:
        live = jnp.repeat(token_mask.reshape(G, Ng), K, axis=1)  # (G,NgK)
        onehot = onehot * live[..., None]    # dead tokens take no slot
    pos_in_e = (jnp.cumsum(onehot, axis=1) * onehot).sum(-1) - 1
    keep = pos_in_e < C                                       # (G, NgK)
    if live is not None:
        keep = keep & live

    # scatter local token ids into (E, C) slots; sentinel row Ng is zeros
    token_ids = jnp.broadcast_to(
        jnp.repeat(jnp.arange(Ng), K)[None], (G, Ng * K))
    slot_ids = jnp.where(keep, flat_e * C + pos_in_e, E * C)
    g_ix = jnp.arange(G)[:, None]
    dispatch = jnp.full((G, E * C + 1), Ng, jnp.int32).at[
        g_ix, slot_ids].set(token_ids, mode="drop")[:, : E * C]
    xp = jnp.concatenate([xg, jnp.zeros((G, 1, D), xg.dtype)], axis=1)
    expert_in = jnp.take_along_axis(
        xp, dispatch[:, :, None], axis=1).reshape(G, E, C, D)
    expert_in = shard(expert_in, ("pod", "data"), None, None, None)

    # reshard group-major -> expert-major: ONE all-to-all under GSPMD
    ein = expert_in.transpose(1, 0, 2, 3)                    # (E, G, C, D)
    ein = shard(ein, "model", ("pod", "data"), None, None)
    h = jnp.einsum("egcd,edf->egcf", ein, p["wi_e"])
    g = jnp.einsum("egcd,edf->egcf", ein, p["wg_e"])
    h = h * g * jax.nn.sigmoid(g.astype(jnp.float32)).astype(h.dtype)
    eout = jnp.einsum("egcf,efd->egcd", h, p["wo_e"])
    eout = shard(eout, "model", ("pod", "data"), None, None)

    # back to group-major (second all-to-all), combine locally
    out_g = eout.transpose(1, 0, 2, 3).reshape(G, E * C, D)
    out_g = shard(out_g, ("pod", "data"), None, None)
    out_p = jnp.concatenate(
        [out_g, jnp.zeros((G, 1, D), out_g.dtype)], axis=1)
    gathered = jnp.take_along_axis(
        out_p, jnp.where(keep, slot_ids, E * C)[:, :, None], axis=1)
    y = (gathered.reshape(G, Ng, K, D)
         * gate_vals[..., None].astype(gathered.dtype)).sum(2)
    y = y.reshape(B, S, D)

    # aux losses (f32) — over live tokens only when a mask is given
    if live is None:
        me = probs.mean((0, 1))                              # (E,)
        ce = (onehot * keep[..., None]).sum((0, 1)).astype(
            jnp.float32) / (N * K)
        dropped = 1.0 - keep.mean()
        router_z = jnp.mean(
            jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    else:
        tok_live = token_mask.reshape(G, Ng).astype(jnp.float32)
        n_live = jnp.maximum(tok_live.sum(), 1.0)
        me = (probs * tok_live[..., None]).sum((0, 1)) / n_live
        ce = (onehot * keep[..., None]).sum((0, 1)).astype(
            jnp.float32) / (n_live * K)
        live_choices = tok_live.sum() * K          # 0 if batch all dead
        dropped = ((live_choices - keep.sum())
                   / jnp.maximum(live_choices, 1.0))
        zsq = jax.scipy.special.logsumexp(logits, axis=-1) ** 2
        router_z = (zsq * tok_live).sum() / n_live
    aux = {
        "load_balance": E * jnp.sum(me * ce),
        "router_z": router_z,
        "dropped_frac": dropped,
    }

    xf = x.reshape(N, D)
    if "shared" in p:
        y = y + swiglu(p["shared"], xf).reshape(B, S, D).astype(y.dtype)
    if "residual" in p:
        y = y + swiglu(p["residual"], xf).reshape(B, S, D).astype(y.dtype)
    return y.astype(x.dtype), aux


def route(p: Dict, x: jnp.ndarray, mo: MoEConfig):
    """Top-k choice of a ``RoutingConfig`` router over all experts.
    x: (N, D) -> (expert ids (N, K), weights (N, K) f32): ids by sigmoid
    score plus selection bias, weights the chosen scores (bias left
    out) normalised over the k chosen."""
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32), p["router"])
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + p["bias"], mo.top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    return idx, w / jnp.maximum(w.sum(-1, keepdims=True), 1e-20)


def held_moe_ffn(p: Dict, x: jnp.ndarray, mo: MoEConfig, rt: RoutingConfig,
                 token_mask: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B, S, D) -> (y, aux): the held experts' share of the layer.

    Every (token, choice) pair whose expert lies in the held range is
    sorted by expert, and three grouped matmuls (``ragged_dot``) run
    each held expert over its own rows only; pairs outside the range, and
    masked tokens (``token_mask`` (B, S) False), sort last and are
    computed by none.  The outputs are weighted by the normalised router
    weights and summed per token.  ``aux["held_pairs"]`` counts the
    pairs computed, ``aux["held_experts"]`` the held experts given any
    (the ones whose weights the grouped matmuls read)."""
    B, S, D = x.shape
    N, K = B * S, mo.top_k
    lo, hi = rt.held_range(mo.n_experts)
    n_local = hi - lo
    xf = x.reshape(N, D)
    with jax.named_scope("moe_route"):
        idx, w = route(p, xf, mo)
        local = idx - lo
        held = (local >= 0) & (local < n_local)
        if token_mask is not None:
            held = held & token_mask.reshape(N, 1)
        key = jnp.where(held, local, n_local).reshape(N * K)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((n_local + 1,), jnp.int32).at[key].add(1)
        n_held = N * K - sizes[n_local]
        rows = xf[order // K]                                 # (N*K, D)
        wts = jnp.where(held, w, 0.0).reshape(N * K)[order]
    with jax.named_scope("moe_experts"):
        gs = sizes[:n_local]
        h = jax.lax.ragged_dot(rows, p["wi_e"], gs,
                               preferred_element_type=jnp.float32)
        g = jax.lax.ragged_dot(rows, p["wg_e"], gs,
                               preferred_element_type=jnp.float32)
        a = (jax.nn.silu(g) * h).astype(x.dtype)
        o = jax.lax.ragged_dot(a, p["wo_e"], gs,
                               preferred_element_type=jnp.float32)
        # rows past the held pairs belong to no group: their output is
        # not defined, so it is dropped, not weighted by zero
        o = jnp.where((jnp.arange(N * K) < n_held)[:, None],
                      o * wts[:, None], 0.0)
        y = o[jnp.argsort(order)].reshape(N, K, D).sum(1)
    aux = {"held_pairs": n_held.astype(jnp.float32),
           "held_experts": (gs > 0).sum().astype(jnp.float32)}
    return y.reshape(B, S, D).astype(x.dtype), aux

