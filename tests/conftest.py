"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests must see the
single real CPU device; multi-device coverage runs in subprocesses
(test_multidevice.py) that set --xla_force_host_platform_device_count
themselves."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ServeConfig

# CI engine matrix (.github/workflows/ci.yml): the default (paged)
# runs the serving tests on the engine's one path, the paged cache with
# chunked prefill; paged-preempt additionally switches to optimistic
# admission over a deliberately small pool so preempt-and-requeue
# actually fires under pytest; paged-prefix turns on cross-request prefix sharing with
# copy-on-write (refcounted pages + prefix index); paged-chaos layers
# a seeded FaultInjector (recoverable points only — greedy outputs
# stay token-for-token intact) plus per-step invariant auditing on top
# of the full optimistic+swap+sharing stack; paged-budget runs that
# same chaos stack through the token-budget scheduler
# (ServeConfig.max_num_batched_tokens, DESIGN.md §scheduler) so every
# serving test exercises fused prefill+decode iterations and
# residual-budget chunk truncation; paged-longctx runs the paged stack
# with split-KV flash-decoding (ServeConfig.decode_splits > 1, DESIGN.md
# §split-kv) so every parity test also covers the split+combine decode
# path; paged-quant runs the whole budget-leg stack on int8 scale-pool
# pages (ServeConfig.cache_quant, DESIGN.md §page-layouts) with
# per-step dynamic split derivation (decode_splits=0); paged-sharded
# runs the chaos stack on a multi-shard data mesh (ServeConfig.shards,
# DESIGN.md §sharded-engine) over forced host devices — greedy outputs
# must match the 1-shard legs token-for-token.  The parity reference
# for every leg is ``manual_greedy`` below: the model's own prefill and
# decode over a dense cache.
ENGINE = os.environ.get("REPRO_ENGINE", "paged")


def serve_config(**kw) -> ServeConfig:
    """ServeConfig honoring the CI engine matrix.

    Tests that pin specific settings construct ServeConfig directly;
    everything routed through here runs with page_size 4 (it divides
    every max_seq_len the serving tests use) and prefill_chunk 8 (it
    forces multi-chunk prompts) — the REPRO_ENGINE=paged leg, the
    default.  REPRO_ENGINE=paged-preempt shrinks the pool
    to one worst-case sequence (max_seq_len / page_size pages — the
    smallest size at which no single request can fail admission) and
    turns on optimistic admission, so multi-slot tests oversubscribe
    and exercise preemption.  REPRO_ENGINE=paged-prefix instead turns
    on share_prefix: every serving test runs through the refcounted
    page store with the prefix index live (matches on the tests'
    random prompts are rare — the leg asserts sharing never perturbs
    generations).  REPRO_ENGINE=paged-chaos is the hardest leg: the
    preempt pool + optimistic admission + swap preemption + sharing,
    with a seeded chaos FaultInjector (ServeConfig.chaos_seed; the
    default schedule arms only recoverable fault points, so every
    greedy parity assertion still holds bit-for-bit) and
    invariants.audit after every step (audit=True).
    REPRO_ENGINE=paged-budget keeps that whole chaos stack and
    additionally turns on the token-budget scheduler with a small
    per-step budget, so decode charges, residual-truncated prefill
    chunks, and fused iterations all fire under every serving test —
    greedy outputs still must match the paged leg token-for-token.
    REPRO_ENGINE=paged-longctx runs the paged stack with split-KV
    flash-decoding (decode_splits=3 — odd, so the tests' page chains
    split into uneven spans and boundary cases fire); greedy outputs
    must stay identical to the decode_splits=1 paged leg.
    REPRO_ENGINE=paged-quant layers the int8 scale-pool page layout
    (ServeConfig.cache_quant="int8", DESIGN.md §page-layouts) over the
    whole budget-leg stack — optimistic admission, swap preemption,
    sharing, chaos, sampled audits, token budget — plus per-step
    dynamic split derivation (decode_splits=0), so prefix sharing,
    COW forks, swap checksums and split-KV all run against int8 data
    pages moving in lockstep with their scale pools.  (Engines built
    without projections serve fp pages — a full cache has no
    compressed R_k/R_v entries to quantize.)
    REPRO_ENGINE=paged-sharded runs the chaos stack (optimistic
    admission, swap, sharing, chaos, sampled audits) with
    ServeConfig.shards > 1 on a forced-host-device data mesh
    (DESIGN.md §sharded-engine); shards adapts to the test's
    max_batch so every slot slice stays equal-width, and single-slot
    tests fall back to the unsharded oracle."""
    kw.setdefault("page_size", 4)
    kw.setdefault("prefill_chunk", 8)
    if ENGINE == "paged-longctx":
        kw.setdefault("decode_splits", 3)
    if ENGINE in ("paged-preempt", "paged-chaos", "paged-budget",
                  "paged-quant"):
        T = kw.get("max_seq_len", 4096)
        kw.setdefault("n_pages", max(2, T // kw["page_size"]))
        kw.setdefault("admission", "optimistic")
        kw.setdefault("watermark_low", 0.1)
    if ENGINE == "paged-sharded":
        # widest equal-slice shard count the test's max_batch allows;
        # per-shard pool sized like the preempt legs so oversubscription
        # still fires inside each shard
        T = kw.get("max_seq_len", 4096)
        B = kw.get("max_batch", 8)
        shards = 4 if B % 4 == 0 else (2 if B % 2 == 0 else 1)
        kw.setdefault("shards", shards)
        kw.setdefault("n_pages",
                      max(2, T // kw["page_size"]) * kw["shards"])
        kw.setdefault("admission", "optimistic")
        kw.setdefault("watermark_low", 0.1)
    if ENGINE == "paged-prefix":
        kw.setdefault("share_prefix", True)
    if ENGINE in ("paged-chaos", "paged-budget", "paged-quant",
                  "paged-sharded"):
        kw.setdefault("share_prefix", True)
        kw.setdefault("preempt_mode", "swap")
        kw.setdefault("chaos_seed", 0)
        kw.setdefault("audit", True)
        # sampled auditing (ServeConfig.audit_every): every 2nd step
        # still catches cross-step corruption while covering the
        # sampling arithmetic itself on the hardest legs
        kw.setdefault("audit_every", 2)
    if ENGINE in ("paged-budget", "paged-quant"):
        # small enough that residual truncation and budget-capped
        # admission actually happen under the tests' max_batch=4
        kw.setdefault("max_num_batched_tokens", 6)
    if ENGINE == "paged-quant":
        kw.setdefault("cache_quant", "int8")
        # per-step split derivation from the live max length, snapped
        # to {1, 2, 4, 8} (bounded-compile satellite)
        kw.setdefault("decode_splits", 0)
    return ServeConfig(**kw)


def manual_greedy(model, params, prompt, n, max_seq_len=64, proj=None):
    """Reference greedy decoding of one request: the model's own
    ``prefill`` / ``decode_step`` over a dense ``max_seq_len`` cache,
    token by token, with no engine in the way.

    ``proj`` (solved ``ModelProjections``) serves the compressed cache.
    Truncation follows the engine's: the token sampled once the cache
    holds ``max_seq_len`` positions is still emitted, but nothing is
    decoded from it, so at most ``max_seq_len - len(prompt) + 1``
    tokens come back."""
    kw = {} if proj is None else {"proj": model.projections_pytree(proj)}
    toks = jnp.asarray(prompt, jnp.int32)[None]
    logits, cache = model.prefill(params, {"tokens": toks}, max_seq_len,
                                  **kw)
    pos = toks.shape[1]
    n = min(n, max_seq_len - pos + 1)
    out = []
    while True:
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        if len(out) == n:
            return out
        logits, cache = model.decode_step(
            params, cache, jnp.asarray([[nxt]], jnp.int32), jnp.int32(pos),
            **kw)
        pos += 1


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def tpu_kernels(monkeypatch):
    """Route model attention through the Pallas kernels as on a TPU
    backend; off TPU they run in interpret mode."""
    import repro.kernels
    monkeypatch.setattr(repro.kernels, "use_kernels", lambda: True)


def dropless(cfg):
    """Reduced config with capacity high enough that no token drops
    (required for exact train/decode consistency checks)."""
    if cfg.moe is None:
        return cfg
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe,
                                     capacity_factor=float(cfg.moe.n_experts)))


def make_batch(cfg, B, S, seed=1):
    key = jax.random.PRNGKey(seed)
    if cfg.inputs_embeds:
        batch = {"embeds": jax.random.normal(key, (B, S, cfg.d_model))
                 * 0.1}
    else:
        batch = {"tokens": jax.random.randint(key, (B, S), 0,
                                              cfg.vocab_size)}
    if cfg.num_patch_tokens:
        batch["image_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 1),
            (B, cfg.num_patch_tokens, cfg.d_model)) * 0.1
    return batch
