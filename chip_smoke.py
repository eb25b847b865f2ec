#!/usr/bin/env python3
"""Bring-up check of the KQ-SVD serving engine on a TPU.

    python3 chip_smoke.py [--seed N]          # one chip
    python3 chip_smoke.py --four-chip         # sharded engine, 4 chips

Serves full-width TinyLlama-1.1B (random weights drawn from ``--seed``)
through the user entry points — ``calibrate_model`` then
``ServingEngine(...).generate`` — in one process.  Phases, in order:

  a. the device: platform, kind and count; anything but a TPU exits
     non-zero, with no CPU fallback;
  b. weights plus KQ-SVD projections calibrated on seeded synthetic
     batches (``repro.launch.serve.calibrated_model``);
  c. each Pallas kernel on the serving path (paged decode unsplit,
     split, int8, and paged prefill) against its ``ref.py`` oracle at the
     served shapes, within ``BF16_TOL``;
  d. the engine's decode dispatch compiled, with its ``tpu_custom_call``
     count (the kernels are in the program);
  e. 8 requests (prompts of 64-512 tokens, 32 new tokens each) through
     paged pages, chunked prefill and the KQ-SVD projections, served cold
     and again warm with identical greedy tokens; then a shorter run on
     int8 pages with 4-way split-KV decode.  Every request must finish,
     none may fail, every token must lie inside the vocabulary;
  f. compile seconds, first-token latency, decode tokens/s and peak
     device memory, printed for information (not claims).

``--four-chip`` runs only the sharded phase: the same seeded requests
at ``shards=4`` and at ``shards=1``, one token per dispatch so every
step's logits are seen; greedy tokens must match, and where a request's
tokens part, the two runs' logits at the first divergent step must
agree within ``BF16_TOL``.

Any failed phase raises, so the exit code is non-zero.  The last line of
standard output is one JSON object, ``{"ok": true, "device": {...}}``,
printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

#: |kernel - oracle| <= BF16_TOL * (1 + |oracle|).  bf16 keeps 8
#: mantissa bits (a rounding costs up to 2^-9 ~ 2e-3 relative); the
#: kernel rounds its output once and may run its f32 dots as bf16
#: passes, so a few roundings at O(1) magnitudes stay well under this.
BF16_TOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Plan:
    """Request mix and serving shapes of one smoke run."""
    n_requests: int = 8
    min_prompt: int = 64
    max_prompt: int = 512
    max_new: int = 32
    max_batch: int = 8
    page_size: int = 16
    prefill_chunk: int = 256
    prefill_buckets: tuple = (64, 128, 256)
    calib_seqs: int = 8
    calib_len: int = 256
    int8_requests: int = 4
    int8_max_new: int = 16
    int8_splits: int = 4


def device_info() -> dict:
    """Phase (a): the platform JAX found, as it reports it."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def serve_config(plan: Plan, **kw):
    """Paged, chunked-prefill serving config sized for ``plan``."""
    from repro.config import ServeConfig
    T = plan.max_prompt + plan.max_new
    T = -(-T // plan.page_size) * plan.page_size
    return ServeConfig(max_seq_len=T, max_batch=plan.max_batch,
                       page_size=plan.page_size,
                       prefill_chunk=plan.prefill_chunk,
                       prefill_buckets=plan.prefill_buckets, **kw)


def build(cfg, plan: Plan, seed: int):
    """Phase (b): weights from ``seed`` and KQ-SVD projections."""
    from repro.launch.serve import calibrated_model
    return calibrated_model(cfg, method="kqsvd", calib_seqs=plan.calib_seqs,
                            calib_len=plan.calib_len, seed=seed)


def requests(cfg, plan: Plan, seed: int, n: int, max_new: int):
    """``n`` seeded requests with prompt lengths in the plan's range."""
    import numpy as np
    from repro.launch.serve import synthetic_requests
    rng = np.random.default_rng(seed)
    lens = rng.integers(plan.min_prompt, plan.max_prompt + 1, n)
    return synthetic_requests(cfg.vocab_size, lens, max_new, rng)


def check_kernels(cfg, sc, ranks, seed: int) -> dict:
    """Phase (c): every kernel on the serving path against its oracle,
    at the served shapes and dtype; returns the max error of each.

    The oracles run in f32 at full matmul precision on the same inputs;
    on TPU the kernels compile for Mosaic, elsewhere they interpret."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import kq_decode as kd

    dt = jnp.dtype(cfg.dtype)
    B, H, Hkv = sc.max_batch, cfg.n_heads, cfg.n_kv_heads
    rk, rv = ranks
    ps, npp, T = sc.page_size, sc.pages_per_seq, sc.max_seq_len
    n_phys = sc.total_pages + 1
    scale = 1.0 / float(np.sqrt(cfg.d_head))
    k = jax.random.split(jax.random.PRNGKey(seed), 10)
    q = jax.random.normal(k[0], (B, H, rk)).astype(dt)
    kc = jax.random.normal(k[1], (n_phys, Hkv, ps, rk)).astype(dt)
    vc = jax.random.normal(k[2], (n_phys, Hkv, ps, rv)).astype(dt)
    btab = jax.random.permutation(k[3], n_phys)[:B * npp].reshape(B, npp)
    btab = btab.astype(jnp.int32)
    lens = jax.random.randint(k[4], (B,), 1, T + 1)
    k8 = jax.random.randint(k[5], kc.shape, -127, 128).astype(jnp.int8)
    v8 = jax.random.randint(k[6], vc.shape, -127, 128).astype(jnp.int8)
    ks = jax.random.uniform(k[7], kc.shape[:3] + (1,), minval=0.5,
                            maxval=2.0).astype(jnp.bfloat16) / 127
    vs = jax.random.uniform(k[8], vc.shape[:3] + (1,), minval=0.5,
                            maxval=2.0).astype(jnp.bfloat16) / 127
    # one prefill chunk continuing a prompt: a full chunk already paged
    S = sc.prefill_chunk
    qp = jax.random.normal(k[9], (1, H, S, rk)).astype(dt)
    pos0 = jnp.asarray([min(S, T - S)], jnp.int32)
    plens = pos0 + S // 2 + 1

    got = {
        "paged_decode": kd.kq_decode_paged_attention_op(
            q, kc, vc, lens, btab, scale=scale, max_len=T),
        "paged_decode_split4": kd.kq_decode_paged_attention_op(
            q, kc, vc, lens, btab, scale=scale, max_len=T, num_splits=4),
        "paged_decode_int8": kd.kq_decode_paged_attention_op(
            q, k8, v8, lens, btab, scale=scale, max_len=T, kscale=ks,
            vscale=vs),
        "paged_prefill": kd.kq_prefill_paged_attention_op(
            qp, kc, vc, plens, pos0, btab[:1], scale=scale, max_len=T),
    }
    f32 = (lambda a: a.astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        want = {
            "paged_decode": kd.kq_decode_paged_attention_ref(
                f32(q), f32(kc), f32(vc), lens, btab, scale=scale),
            "paged_decode_split4": kd.kq_decode_paged_attention_split_ref(
                f32(q), f32(kc), f32(vc), lens, btab, num_splits=4,
                scale=scale),
            "paged_decode_int8": kd.kq_decode_paged_attention_int8_ref(
                f32(q), k8, v8, ks, vs, lens, btab, scale=scale),
            "paged_prefill": kd.kq_prefill_paged_attention_ref(
                f32(qp), f32(kc), f32(vc), plens, pos0, btab[:1],
                scale=scale),
        }
    errs, bad = {}, []
    for name, out in got.items():
        out = np.asarray(out, np.float32)
        ref = np.asarray(want[name], np.float32)
        if out.shape != ref.shape or not np.isfinite(out).all():
            raise AssertionError(f"{name}: shape {out.shape} vs "
                                 f"{ref.shape} or non-finite output")
        err = np.abs(out - ref)
        errs[name] = float(err.max())
        if (err > BF16_TOL * (1.0 + np.abs(ref))).any():
            bad.append(name)
    if bad:
        raise AssertionError(f"kernels outside tolerance {BF16_TOL}: "
                             f"{bad}; max errors {errs}")
    return errs


def compile_decode(eng) -> tuple[int, float]:
    """Phase (d): compile the started engine's decode dispatch; return
    its ``tpu_custom_call`` count and the compile seconds."""
    t0 = time.perf_counter()
    text = eng.lower_decode().compile().as_text()
    secs = time.perf_counter() - t0
    return text.count('custom_call_target="tpu_custom_call"'), secs


def check_served(reqs, vocab_size: int) -> None:
    """Every request finished, none failed, every token in vocabulary."""
    for r in reqs:
        if r.failed or not r.done:
            raise AssertionError(f"request {r.rid} did not finish: "
                                 f"done={r.done} error={r.error}")
        if len(r.out_tokens) != r.max_new_tokens:
            raise AssertionError(f"request {r.rid}: {len(r.out_tokens)} "
                                 f"of {r.max_new_tokens} tokens")
        if not all(0 <= t < vocab_size for t in r.out_tokens):
            raise AssertionError(f"request {r.rid}: token outside "
                                 f"[0, {vocab_size})")


def record_logits(eng, into: dict) -> None:
    """Add each decodable request's next-token logits to ``into``, keyed
    ``(rid, tokens emitted so far)``: the row its next greedy token is
    read from.  Mid-prefill slots hold no logits yet and are skipped."""
    import numpy as np
    for w in getattr(eng, "workers", [eng]):
        rows = None
        for b, r in enumerate(w._slot_req):
            key = None if r is None else (r.rid, len(r.out_tokens))
            if (key is None or r.done or key in into
                    or w._prefilled[b] is not None):
                continue
            if rows is None:
                rows = np.asarray(w._logits, np.float32)
            into[key] = rows[b]


def serve(eng, reqs, logits: dict | None = None) -> dict:
    """Phase (e): drive ``start``/``step`` to the end, timing each
    request's first token; with ``logits``, record every step's
    next-token logits there."""
    t0 = time.perf_counter()
    first: dict = {}
    eng.start(reqs)
    busy = True
    while busy:
        busy = eng.step()
        now = time.perf_counter() - t0
        for r in reqs:
            if r.out_tokens and r.rid not in first:
                first[r.rid] = now
        if logits is not None:
            record_logits(eng, logits)
    return {"wall_s": time.perf_counter() - t0,
            "first_token_s": sorted(first.values()),
            "tokens": sum(len(r.out_tokens) for r in reqs)}


def run_single(cfg, plan: Plan, seed: int, log=print) -> dict:
    """Phases (b)-(e) on one device; returns what phase (f) prints."""
    from repro.serving import ServingEngine

    t0 = time.perf_counter()
    params, proj = build(cfg, plan, seed)
    log(f"[b] built {cfg.name} ({cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.dtype}) and calibrated KQ-SVD in "
        f"{time.perf_counter() - t0:.1f}s: ranks k={max(proj.ranks_k)} "
        f"v={max(proj.ranks_v)}")
    sc = serve_config(plan)
    ranks = (proj.a_k.shape[-1], proj.a_v.shape[-1])
    errs = check_kernels(cfg, sc, ranks, seed)
    log(f"[c] kernels vs oracle (tol {BF16_TOL}): "
        + ", ".join(f"{n} {e:.2e}" for n, e in errs.items()))

    eng = ServingEngine(cfg, params, sc, projections=proj)
    eng.start([])
    n_calls, compile_s = compile_decode(eng)
    log(f"[d] decode step compiled in {compile_s:.1f}s: "
        f"{n_calls} tpu_custom_call")

    cold = requests(cfg, plan, seed, plan.n_requests, plan.max_new)
    cold_rep = serve(eng, cold)
    check_served(cold, cfg.vocab_size)
    warm = requests(cfg, plan, seed, plan.n_requests, plan.max_new)
    warm_rep = serve(eng, warm)
    check_served(warm, cfg.vocab_size)
    if [r.out_tokens for r in warm] != [r.out_tokens for r in cold]:
        raise AssertionError("warm run's greedy tokens differ from the "
                             "cold run's")
    log(f"[e] served {len(cold)} requests (prompts "
        f"{sorted(len(r.prompt) for r in cold)}, {plan.max_new} new "
        f"tokens each): cold {cold_rep['wall_s']:.1f}s, warm "
        f"{warm_rep['wall_s']:.2f}s, identical tokens")

    sc8 = serve_config(plan, cache_quant="int8",
                       decode_splits=plan.int8_splits)
    eng8 = ServingEngine(cfg, params, sc8, projections=proj)
    reqs8 = requests(cfg, plan, seed + 1, plan.int8_requests,
                     plan.int8_max_new)
    int8_rep = serve(eng8, reqs8)
    check_served(reqs8, cfg.vocab_size)
    log(f"[e] int8 pages, {plan.int8_splits}-way split decode: served "
        f"{len(reqs8)} requests in {int8_rep['wall_s']:.1f}s")
    return {"kernel_errors": errs, "tpu_custom_calls": n_calls,
            "decode_compile_s": compile_s, "cold": cold_rep,
            "warm": warm_rep, "int8": int8_rep}


def divergences(a_reqs, b_reqs, a_logits: dict, b_logits: dict
                ) -> list:
    """Where two greedy runs of the same requests part: per parting
    request, the first step whose tokens differ, the largest difference
    between the two runs' logits at that step, and the bf16 tolerance
    it is held to."""
    import numpy as np
    parted = []
    for a, b in zip(a_reqs, b_reqs):
        if a.out_tokens == b.out_tokens:
            continue
        step = next(i for i, (x, y) in enumerate(
            zip(a.out_tokens, b.out_tokens)) if x != y)
        key = (a.rid, step)
        if key not in a_logits or key not in b_logits:
            raise AssertionError(f"request {a.rid} parts at step {step}, "
                                 f"where no logits were recorded")
        la, lb = a_logits[key], b_logits[key]
        parted.append({
            "rid": a.rid, "step": step,
            "max_err": float(np.abs(la - lb).max()),
            "tol": BF16_TOL * max(1.0, float(np.abs(lb).max()))})
    return parted


def run_sharded(cfg, plan: Plan, seed: int, shards: int, log=print
                ) -> list:
    """``shards`` data-axis shards against one, same seeded requests;
    returns the divergences (each within tolerance, else it raises)."""
    import jax
    from repro.serving import ServingEngine

    params, proj = build(cfg, plan, seed)
    outs, seen = {}, {}
    for n in (shards, 1):
        # one token per dispatch: every step's logits reach the host
        sc = serve_config(plan, shards=n, decode_chunk=1)
        eng = ServingEngine(cfg, params, sc, projections=proj)
        reqs = requests(cfg, plan, seed, plan.n_requests, plan.max_new)
        seen[n] = {}
        rep = serve(eng, reqs, seen[n])
        check_served(reqs, cfg.vocab_size)
        if n > 1:
            for what, tree in (("page pools", eng._g_cache),
                               ("weights", eng.params)):
                held = jax.tree.leaves(tree)[0].sharding.device_set
                if len(held) != n:
                    raise AssertionError(f"{what} on {len(held)} "
                                         f"device(s), expected {n}")
        log(f"[sharded] shards={n}: {len(reqs)} requests in "
            f"{rep['wall_s']:.1f}s")
        outs[n] = reqs
        del eng
    parted = divergences(outs[shards], outs[1], seen[shards], seen[1])
    log(f"[sharded] shards={shards} vs 1: "
        f"{len(outs[1]) - len(parted)}/{len(outs[1])} requests "
        f"token-identical; divergences {parted}")
    bad = [d for d in parted if d["max_err"] > d["tol"]]
    if bad:
        raise AssertionError(f"logits at the divergent step differ "
                             f"beyond tolerance: {bad}")
    return parted


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded phase: shards=4 vs 1")
    args = ap.parse_args()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

    dev = device_info()
    print(f"[a] platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found {dev['platform']}")
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = get_config("tinyllama-1.1b")
    plan = Plan()
    log = (lambda s: print(s, flush=True))
    if args.four_chip:
        if dev["count"] < 4:
            sys.exit(f"chip_smoke: --four-chip needs 4 chips, found "
                     f"{dev['count']}")
        run_sharded(cfg, plan, args.seed, 4, log)
    else:
        rep = run_single(cfg, plan, args.seed, log)
        if rep["tpu_custom_calls"] == 0:
            raise AssertionError("the compiled decode step holds no "
                                 "tpu_custom_call: kernels are off the "
                                 "path")
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        warm = rep["warm"]
        log("[f] " + json.dumps({
            "decode_compile_s": rep["decode_compile_s"],
            "cold_serve_s": rep["cold"]["wall_s"],
            "warm_first_token_s": warm["first_token_s"],
            "int8_serve_s": rep["int8"]["wall_s"],
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}))
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
