"""The language model: embed -> (scanned) blocks -> norm -> head.

Public entry points (all pure functions of pytrees, pjit-able):
    init(rng)                                   -> params
    train_logits(params, batch)                 -> (logits, aux)
    prefill(params, batch, max_len, proj)       -> (logits, cache)
    prefill_chunk(params, cache, tokens, pos0, valid, proj, block_table)
                                                -> (logits, cache)
        (chunked prefill straight into a paged cache; DESIGN.md §prefill)
    decode_step(params, cache, tokens, pos, proj) -> (logits, cache)
        (pos: per-sequence (B,) positions; scalars broadcast)
    calibrate(params, tokens)                   -> per-attn-layer captures
    group_output_weights(params)                -> stacked W^O per kv group

Depth is executed with ``lax.scan`` over structurally identical steps
(``blocks.step_layout``); heterogeneous leading layers run unrolled in a
prefix.  ``cfg.scan_layers=False`` unrolls everything (debug/calibration).
The KQ-SVD projections enter as a separate pytree ``proj`` with
``{"prefix": [...], "steps": stacked}`` mirroring the cache structure.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.core.calibration import ModelProjections
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models.blocks import (apply_layer, attn_sublayer_indices,
                                 init_layer, init_layer_cache, step_layout)
from repro.models.layers import dtype_of, init_rms, rms_norm
from repro.serving.paged_cache import LayerPool, is_layer_pool
from repro.sharding.partition import shard

#: summed over layers; ``held_pairs`` counts (token, held expert) pairs
#: of the held-experts MoE layers (``moe.held_moe_ffn``), and
#: ``held_experts`` the held experts those pairs touch
AUX_KEYS = ("load_balance", "router_z", "dropped_frac", "held_pairs",
            "held_experts")


def _zero_aux() -> Dict[str, jnp.ndarray]:
    d = {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}
    d["n_moe"] = jnp.zeros((), jnp.float32)
    return d


def _add_aux(acc, aux):
    if not aux:
        return acc
    out = dict(acc)
    for k in AUX_KEYS:
        if k in aux:
            out[k] = acc[k] + aux[k]
    out["n_moe"] = acc["n_moe"] + (1.0 if "load_balance" in aux else 0.0)
    return out


class LM:
    """The language model: layer stack + embed/head, with train,
    prefill (full and chunked/paged) and decode entry points."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.dtype = dtype_of(cfg.dtype)
        self.prefix, self.steps = step_layout(cfg)
        self.step_template = self.steps[0] if self.steps else []
        # attention sublayers of a step: with more than one (MiMo's
        # S S S S S F) the step's projections are a tuple, one per
        # sublayer, else the sublayer's own dict
        self.attn_js = (attn_sublayer_indices(cfg, self.step_template)
                        if self.steps else [])
        # ordered list of attention layer ids (for projections/calibration)
        self.attn_layers = [i for i in range(cfg.n_layers)
                            if cfg.layer_kinds()[i] in ("attn", "mla")]

    # -- init ---------------------------------------------------------------

    def init(self, rng) -> Dict[str, Any]:
        """Init all model parameters (embed, head, layer stack)."""
        cfg = self.cfg
        k_embed, k_head, k_pre, k_body = jax.random.split(rng, 4)
        params: Dict[str, Any] = {
            "embed": (jax.random.normal(k_embed,
                                        (cfg.vocab_size, cfg.d_model))
                      * 0.02).astype(self.dtype),
            "final_norm": init_rms(cfg.d_model, self.dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = (jax.random.normal(
                k_head, (cfg.d_model, cfg.vocab_size))
                / np.sqrt(cfg.d_model)).astype(self.dtype)
        params["prefix"] = [
            init_layer(jax.random.fold_in(k_pre, i), cfg, i, self.dtype)
            for i in self.prefix]
        if self.steps:
            def init_step(key):
                ks = jax.random.split(key, len(self.step_template))
                return {"layers": tuple(
                    init_layer(ks[j], cfg, l, self.dtype)
                    for j, l in enumerate(self.step_template))}
            keys = jax.random.split(k_body, len(self.steps))
            if cfg.scan_layers:
                params["steps"] = jax.vmap(init_step)(keys)
            else:
                stepped = [init_step(k) for k in keys]
                params["steps"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *stepped)
        return params

    # -- embedding / head ----------------------------------------------------

    def _embed(self, params, batch) -> jnp.ndarray:
        cfg = self.cfg
        if "embeds" in batch:
            x = batch["embeds"].astype(self.dtype)
        else:
            x = jnp.take(params["embed"], batch["tokens"], axis=0)
        if "image_embeds" in batch:
            x = jnp.concatenate(
                [batch["image_embeds"].astype(self.dtype), x], axis=1)
        return shard(x, ("pod", "data"), None, None)

    def _logits(self, params, x) -> jnp.ndarray:
        head = (params["embed"].T if self.cfg.tie_embeddings
                else params["lm_head"])
        logits = jnp.einsum("bsd,dv->bsv", x, head,
                            preferred_element_type=jnp.float32)
        return shard(logits, ("pod", "data"), None, "model")

    # -- step application ----------------------------------------------------

    def _apply_step(self, step_params, x, mode, steps_cache=None, pos=None,
                    step_proj=None, max_len=0, block_table=None,
                    token_mask=None, num_splits=1, step=None):
        """One step's layers.  With a cache (decode, chunk), ``steps_cache``
        is the whole stack of step caches and ``step`` this step's index
        in it: each layer sees its leaves as ``LayerPool``s, writes its
        new entries into the stack in place, and the updated stack comes
        back (DESIGN.md §paged-cache)."""
        cfg = self.cfg
        views = None
        if steps_cache is not None:
            views = jax.tree.map(lambda a: LayerPool(a, step), steps_cache)
        new_caches, captures, aux_t = [], [], _zero_aux()
        for j, layer_idx in enumerate(self.step_template):
            lp = step_params["layers"][j]
            lc = views["layers"][j] if views is not None else None
            lproj = None
            if (j in self.attn_js and step_proj is not None
                    and len(step_proj)):
                lproj = (step_proj[self.attn_js.index(j)]
                         if len(self.attn_js) > 1 else step_proj)
            x, nc, caps, aux = apply_layer(
                lp, x, cfg, layer_idx, mode, lc, pos, lproj, max_len,
                block_table, token_mask, num_splits)
            new_caches.append(nc)
            if caps is not None:
                captures.append(caps)
            aux_t = _add_aux(aux_t, aux)
        cache_out = None
        if steps_cache is not None:
            # a leaf a layer wrote in place comes back as its stack; a
            # state it rewrote whole is stored at the step
            cache_out = jax.tree.map(
                lambda a, new: (new.stack if is_layer_pool(new) else
                                jax.lax.dynamic_update_index_in_dim(
                                    a, new.astype(a.dtype), step, 0)),
                steps_cache, {"layers": tuple(new_caches)})
        elif mode == "prefill":
            cache_out = {"layers": tuple(new_caches)}
        return x, cache_out, tuple(captures) or None, aux_t

    # -- full stack ----------------------------------------------------------

    def _run_stack(self, params, x, mode, cache=None, pos=None, proj=None,
                   max_len: int = 0, block_table=None, token_mask=None,
                   num_splits: int = 1):
        """Returns (x, cache_out, captures_list, aux)."""
        cfg = self.cfg
        aux = _zero_aux()
        captures_list: List = []
        prefix_cache_out, attn_ord = [], 0
        for n, layer_idx in enumerate(self.prefix):
            lp = params["prefix"][n]
            lc = cache["prefix"][n] if cache is not None else None
            is_attn = cfg.layer_kinds()[layer_idx] in ("attn", "mla")
            lproj = (proj["prefix"][attn_ord]
                     if (proj is not None and is_attn) else None)
            x, nc, caps, la = apply_layer(lp, x, cfg, layer_idx, mode,
                                          lc, pos, lproj, max_len,
                                          block_table, token_mask,
                                          num_splits)
            prefix_cache_out.append(nc)
            if caps is not None:
                captures_list.append(caps)
            if is_attn:
                attn_ord += 1
            aux = _add_aux(aux, la)

        steps_cache_out = None
        if self.steps:
            step_proj = proj["steps"] if proj is not None else None
            if not cfg.scan_layers:
                outs = []
                carried = cache["steps"] if cache is not None else None
                for i in range(len(self.steps)):
                    sp = jax.tree.map(lambda a: a[i], params["steps"])
                    spj = (jax.tree.map(lambda a: a[i], step_proj)
                           if step_proj is not None else None)
                    x, co, caps, sa = self._apply_step(
                        sp, x, mode, carried, pos, spj, max_len,
                        block_table, token_mask, num_splits, step=i)
                    if carried is not None:
                        carried = co
                    else:
                        outs.append(co)
                    if caps is not None:
                        captures_list.extend(caps)
                    aux = jax.tree.map(lambda a, b: a + b, aux, sa)
                steps_cache_out = carried
                if mode == "prefill":
                    steps_cache_out = jax.tree.map(
                        lambda *xs: jnp.stack(xs), *outs)
            else:
                x, steps_cache_out, caps_stacked, s_aux = self._scan_steps(
                    params["steps"], x, mode, cache, pos, step_proj,
                    max_len, block_table, token_mask, num_splits)
                aux = jax.tree.map(lambda a, b: a + b, aux, s_aux)
                if caps_stacked is not None:
                    for i in range(len(self.steps)):
                        captures_list.extend(jax.tree.map(
                            lambda a: a[i], caps_stacked))

        cache_out = None
        if mode in ("prefill", "decode", "chunk"):
            cache_out = {"prefix": prefix_cache_out,
                         "steps": steps_cache_out}
        return x, cache_out, captures_list, aux

    def _scan_steps(self, steps_params, x, mode, cache, pos, step_proj,
                    max_len, block_table=None, token_mask=None,
                    num_splits=1):
        cfg = self.cfg
        # decode and chunk carry the stacked caches (written in place
        # at each step's index); prefill emits each step's new cache
        emit_cache = mode == "prefill"
        emit_caps = mode == "calibrate"

        def body(carry, xs):
            x, aux, sc = carry
            i, sp = xs[0], xs[1]
            spj = xs[-1] if step_proj is not None else None
            x, co, caps, sa = self._apply_step(sp, x, mode, sc, pos, spj,
                                               max_len, block_table,
                                               token_mask, num_splits,
                                               step=i)
            aux = jax.tree.map(lambda a, b: a + b, aux, sa)
            ys = []
            if emit_cache:
                ys.append(co)
            if emit_caps:
                ys.append(caps)
            return (x, aux, co if sc is not None else None), (
                tuple(ys) if ys else None)

        if mode == "train" and cfg.remat_policy != "none":
            policy = {"nothing": jax.checkpoint_policies.nothing_saveable,
                      "dots": jax.checkpoint_policies.dots_saveable,
                      }.get(cfg.remat_policy,
                            jax.checkpoint_policies.nothing_saveable)
            body = jax.checkpoint(body, policy=policy)

        xs = [jnp.arange(len(self.steps)), steps_params]
        if step_proj is not None:
            xs.append(step_proj)
        carried = cache["steps"] if cache is not None else None
        (x, aux, cache_out), ys = jax.lax.scan(
            body, (x, _zero_aux(), carried), tuple(xs))
        caps_out = None
        if ys:
            ys = list(ys)
            if emit_cache:
                cache_out = ys.pop(0)
            if emit_caps:
                caps_out = ys.pop(0)
        return x, cache_out, caps_out, aux

    # -- public entry points ---------------------------------------------------

    def train_logits(self, params, batch):
        """Full-sequence logits + aux losses (training forward)."""
        x = self._embed(params, batch)
        x, _, _, aux = self._run_stack(params, x, "train")
        x = rms_norm(x, params["final_norm"], self.cfg.rms_eps)
        return self._logits(params, x), aux

    def prefill(self, params, batch, max_len: int, proj=None):
        """Full-prompt prefill: last-token logits + populated cache."""
        x = self._embed(params, batch)
        x, cache, _, _ = self._run_stack(params, x, "prefill", proj=proj,
                                         max_len=max_len)
        x = rms_norm(x, params["final_norm"], self.cfg.rms_eps)
        logits = self._logits(params, x[:, -1:])
        return logits, cache

    def prefill_chunk(self, params, cache, tokens, pos0, valid,
                      proj=None, block_table=None):
        """One bucket-padded prompt chunk straight into a paged cache
        (DESIGN.md §prefill).

        tokens: (B, S) chunk whose first real token sits at position
        ``pos0[b]`` of its sequence; ``valid``: (B, S) bool of real
        (non-bucket-padding) tokens, a contiguous prefix per row.  The
        chunk's (compressed) k/v entries are written through
        ``block_table`` into the page pools; its queries attend the
        already-written pages.  Returns ``(logits, cache)`` with logits
        (B, S, V) — rows past each sequence's last valid token are
        garbage (isolated: attention rows are independent and MoE
        routing masks them), so callers slice the last valid row.
        Compiles once per chunk bucket shape, not per prompt length."""
        pos0 = attn_mod.batched_positions(pos0, tokens.shape[0])
        x = self._embed(params, {"tokens": tokens})
        x, cache, _, _ = self._run_stack(params, x, "chunk", cache=cache,
                                         pos=pos0, proj=proj,
                                         block_table=block_table,
                                         token_mask=valid)
        x = rms_norm(x, params["final_norm"], self.cfg.rms_eps)
        return self._logits(params, x), cache

    def decode_step(self, params, cache, tokens, pos, proj=None,
                    block_table=None, token_mask=None, num_splits=1,
                    with_aux: bool = False):
        """tokens: (B, 1) int32; pos: per-sequence (B,) index of each new
        token (a scalar broadcasts — legacy lock-step decode).

        ``block_table``: (B, n_pages) int32 — present iff ``cache`` is
        paged (pool-shaped leaves; DESIGN.md §paged-cache).
        ``token_mask``: (B,) bool of live slots; dead slots are excluded
        from MoE capacity assignment.  ``num_splits`` (static Python
        int, paged only): split-KV flash-decoding fan-out for the
        attention read (DESIGN.md §split-kv); 1 is the unsplit parity
        oracle.  ``with_aux`` also returns the summed layer statistics
        (``AUX_KEYS``), e.g. the held-expert pairs of the step."""
        pos = attn_mod.batched_positions(pos, tokens.shape[0])
        x = self._embed(params, {"tokens": tokens})
        tm = token_mask[:, None] if token_mask is not None else None
        x, cache, _, aux = self._run_stack(params, x, "decode", cache=cache,
                                           pos=pos, proj=proj,
                                           block_table=block_table,
                                           token_mask=tm,
                                           num_splits=num_splits)
        x = rms_norm(x, params["final_norm"], self.cfg.rms_eps)
        if with_aux:
            return self._logits(params, x), cache, aux
        return self._logits(params, x), cache

    def calibrate(self, params, tokens):
        """Returns per-attention-layer captures (k, q, v) as a list."""
        batch = tokens if isinstance(tokens, dict) else {"tokens": tokens}
        x = self._embed(params, batch)
        _, _, captures, _ = self._run_stack(params, x, "calibrate")
        return captures

    def group_output_weights(self, params) -> List[np.ndarray]:
        """Stacked per-group output weights for the value-path solve."""
        cfg = self.cfg
        out = []
        for layer_idx in self.attn_layers:
            lp = self._layer_params(params, layer_idx)
            if cfg.layer_kinds()[layer_idx] == "mla":
                out.append(mla_mod.mla_group_output_weights(lp["attn"], cfg))
            else:
                out.append(attn_mod.group_output_weights(
                    lp["attn"], cfg, cfg.attn_spec(layer_idx)))
        return out

    def _layer_params(self, params, layer_idx: int):
        if layer_idx in self.prefix:
            return params["prefix"][self.prefix.index(layer_idx)]
        body = [l for st in self.steps for l in st]
        flat = body.index(layer_idx)
        step_i, j = divmod(flat, len(self.step_template))
        return jax.tree.map(lambda a: a[step_i],
                            params["steps"])["layers"][j]

    # -- caches & projections ---------------------------------------------------

    def init_cache(self, batch: int, max_len: int,
                   ranks: Tuple[int, int] = (0, 0), dtype=None,
                   paged: bool = False, ring_slots: int = 0):
        """Empty decode cache; ``paged=True`` builds page-pool leaves
        from the configured page layout (DESIGN.md §page-layouts)."""
        cfg = self.cfg
        dtype = dtype or self.dtype
        prefix = [init_layer_cache(cfg, i, batch, max_len, ranks, dtype,
                                   paged, ring_slots)
                  for i in self.prefix]
        step_caches = []
        for st in (self.steps[:1] if cfg.scan_layers else self.steps):
            step_caches.append({"layers": tuple(
                init_layer_cache(cfg, l, batch, max_len, ranks, dtype,
                                 paged, ring_slots)
                for l in st)})
        if self.steps:
            if cfg.scan_layers:
                n = len(self.steps)
                steps = jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (n,) + a.shape),
                    step_caches[0])
            else:
                steps = jax.tree.map(lambda *xs: jnp.stack(xs),
                                     *step_caches)
        else:
            steps = None
        return {"prefix": prefix, "steps": steps}

    def init_paged_cache(self, n_phys_pages: int, page_size: int,
                         ranks: Tuple[int, int] = (0, 0), dtype=None,
                         ring_slots: int = 0):
        """Page-pool cache (DESIGN.md §paged-cache): same pytree layout
        as ``init_cache`` but every attention leaf is a pool
        ``(n_phys_pages, Hkv, page_size, R)`` indexed through a block
        table instead of per-slot ``(B, max_seq_len, R)`` lanes.  This
        is exactly ``init_cache`` with (batch, max_len) reinterpreted as
        (pages, page_size) — restricted to plain-attention stacks.  The
        windowed layers of a model with per-layer kinds hold
        ``1 + ring_slots * ring_pages`` pages instead (DESIGN.md
        §window-rings)."""
        cfg = self.cfg
        kinds = set(cfg.layer_kinds())
        if kinds != {"attn"}:
            raise NotImplementedError(
                f"paged cache supports plain attention stacks only "
                f"(layer kinds: {sorted(kinds)})")
        if cfg.sliding_window:
            raise NotImplementedError(
                "paged cache: sliding window not supported")
        return self.init_cache(n_phys_pages, page_size, ranks, dtype,
                               paged=True, ring_slots=ring_slots)

    def projections_pytree(self, mp: ModelProjections, dtype=None):
        """Convert solved ModelProjections to the runtime pytree: one
        dict per prefix attention layer, and for the scanned steps the
        dicts stacked over steps (a tuple of them, one per attention
        sublayer, when a step holds several)."""
        dtype = dtype or self.dtype
        arrays = {"a_k": mp.a_k, "b_q": mp.b_q}
        if mp.a_v is not None:
            arrays["a_v"] = mp.a_v
            arrays["c_v"] = mp.c_v
        per_layer = [
            {k: jnp.asarray(v[i], dtype) for k, v in arrays.items()}
            for i in range(len(self.attn_layers))]
        prefix_attn = [i for i in self.prefix
                       if self.cfg.layer_kinds()[i] in ("attn", "mla")]
        n_pre = len(prefix_attn)
        pre = per_layer[:n_pre]
        body = per_layer[n_pre:]

        def stack(layers):
            return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)

        n_j = len(self.attn_js)
        if not body:
            steps = None
        elif n_j > 1:
            steps = tuple(stack(body[j::n_j]) for j in range(n_j))
        else:
            steps = stack(body)
        return {"prefix": pre, "steps": steps}


@functools.lru_cache(maxsize=None)
def build_model(cfg: ModelConfig) -> LM:
    """Memoized ``LM`` for a (frozen, hashable) ModelConfig."""
    return LM(cfg)
