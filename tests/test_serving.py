"""Serving engine: greedy decode correctness, compressed-cache serving."""
import dataclasses

import pytest

import jax
import numpy as np

from conftest import dropless, manual_greedy, serve_config
from repro.config import CompressionConfig
from repro.configs import get_config
from repro.core.calibration import GramAccumulator
from repro.models import build_model
from repro.serving import Request, ServingEngine


def setup(compressed=False, rank=None):
    cfg = dropless(get_config("tinyllama-1.1b").reduced())
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    proj = None
    if compressed:
        acc = GramAccumulator(len(model.attn_layers))
        for i in range(2):
            toks = jax.random.randint(jax.random.PRNGKey(5 + i), (2, 32),
                                      0, cfg.vocab_size)
            caps = model.calibrate(params, toks)
            acc.update_from_captures([jax.tree.map(np.asarray, c)
                                      for c in caps])
        ccfg = CompressionConfig(method="kqsvd",
                                 rank_k=rank or cfg.d_head,
                                 rank_v=rank or cfg.d_head)
        proj = acc.solve(ccfg, model.group_output_weights(params))
    sc = serve_config(max_seq_len=64, max_batch=4, temperature=0.0)
    return cfg, model, params, ServingEngine(cfg, params, sc,
                                             projections=proj)


def test_engine_matches_manual_greedy():
    cfg, model, params, eng = setup()
    prompt = np.arange(8, dtype=np.int32) % cfg.vocab_size
    reqs = [Request(rid=0, prompt=prompt, max_new_tokens=6)]
    eng.generate(reqs)
    assert reqs[0].out_tokens == manual_greedy(model, params, prompt, 6)


def test_engine_batched_requests_complete():
    cfg, model, params, eng = setup()
    prompts = [np.full((8,), i, np.int32) for i in range(6)]
    reqs = [Request(rid=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    eng.generate(reqs)
    assert all(r.done and len(r.out_tokens) == 4 for r in reqs)


def test_compressed_engine_full_rank_matches_uncompressed():
    cfg, model, params, eng_c = setup(compressed=True)
    _, _, _, eng_f = setup(compressed=False)
    prompt = (np.arange(8) * 3 % cfg.vocab_size).astype(np.int32)
    r_c = [Request(rid=0, prompt=prompt, max_new_tokens=5)]
    r_f = [Request(rid=0, prompt=prompt, max_new_tokens=5)]
    eng_c.generate(r_c)
    eng_f.generate(r_f)
    assert r_c[0].out_tokens == r_f[0].out_tokens
    assert eng_c.capacity_gain() == 1.0      # full rank: no gain


def test_compressed_engine_capacity_gain():
    cfg, model, params, eng = setup(compressed=True, rank=4)
    assert eng.capacity_gain() == pytest.approx(16 / 4, rel=1e-6) \
        or eng.capacity_gain() > 1.0


# ---------------------------------------------------------------------------
# Continuous batching over mixed-length prompts
# ---------------------------------------------------------------------------


def test_engine_mixed_lengths_match_one_by_one():
    """One continuous batch of mixed prompt lengths == serving each
    request alone (greedy)."""
    cfg, model, params, _ = setup()
    rng_ = np.random.default_rng(3)
    lens = [3, 9, 6, 12, 5, 8]                 # > max_batch: forces refill
    prompts = [rng_.integers(0, cfg.vocab_size, L).astype(np.int32)
               for L in lens]
    sc = serve_config(max_seq_len=64, max_batch=4, temperature=0.0,
                      decode_chunk=4)
    eng = ServingEngine(cfg, params, sc)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    eng.generate(reqs)
    for i, p in enumerate(prompts):
        single = ServingEngine(cfg, params, dataclasses.replace(
            sc, max_batch=1, shards=1))
        r1 = [Request(rid=0, prompt=p, max_new_tokens=6)]
        single.generate(r1)
        assert reqs[i].out_tokens == r1[0].out_tokens, i
        assert reqs[i].done and not reqs[i].truncated


def test_engine_surfaces_truncation():
    """Hitting max_seq_len mid-generation is reported, not silent."""
    cfg, model, params, _ = setup()
    sc = serve_config(max_seq_len=12, max_batch=2, decode_chunk=4)
    eng = ServingEngine(cfg, params, sc)
    prompt = (np.arange(10) % cfg.vocab_size).astype(np.int32)
    reqs = [Request(rid=0, prompt=prompt, max_new_tokens=8)]
    eng.generate(reqs)
    r = reqs[0]
    assert r.done and r.truncated
    # tokens at positions 10, 11 and the final sampled-but-unplaceable one
    assert len(r.out_tokens) == 3
    assert len(r.out_tokens) < r.max_new_tokens


def test_engine_eos_stops_slot_early():
    cfg, model, params, _ = setup()
    # find the greedy continuation's second token, use it as EOS
    prompt = (np.arange(8) * 7 % cfg.vocab_size).astype(np.int32)
    probe = [Request(rid=0, prompt=prompt, max_new_tokens=5)]
    ServingEngine(cfg, params, serve_config(max_seq_len=64, max_batch=1)
                  ).generate(probe)
    eos = probe[0].out_tokens[1]
    sc = serve_config(max_seq_len=64, max_batch=2, decode_chunk=4,
                      eos_token=int(eos))
    eng = ServingEngine(cfg, params, sc)
    reqs = [Request(rid=0, prompt=prompt, max_new_tokens=5)]
    eng.generate(reqs)
    assert reqs[0].done and not reqs[0].truncated
    assert reqs[0].out_tokens == probe[0].out_tokens[:2]   # EOS included


def test_engine_mixed_lengths_compressed():
    """Mixed-length continuous batching through the compressed cache."""
    cfg, model, params, eng = setup(compressed=True)
    rng_ = np.random.default_rng(5)
    prompts = [rng_.integers(0, cfg.vocab_size, L).astype(np.int32)
               for L in (4, 11, 7)]
    reqs = [Request(rid=i, prompt=p, max_new_tokens=5)
            for i, p in enumerate(prompts)]
    eng.generate(reqs)
    for i, p in enumerate(prompts):
        _, _, _, single = setup(compressed=True)
        r1 = [Request(rid=0, prompt=p, max_new_tokens=5)]
        single.generate(r1)
        assert reqs[i].out_tokens == r1[0].out_tokens, i
