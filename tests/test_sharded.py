"""Data-axis sharded serving engine (ServeConfig.shards, DESIGN.md
§sharded-engine).

Mesh-backed coverage runs in a subprocess that forces 4 host devices
(the main test process must keep the single real CPU device —
tests/conftest.py): greedy parity vs the 1-shard oracle under the
chaos-capable stack, skewed-length rebalancing across shards, pool
exhaustion preempting only the exhausted shard's own slots, and
cross-shard prefix-index isolation.  The in-process tests cover the
pieces that need no mesh: ServeConfig.shards validation and the
global router's scoring rule on stub workers.
"""
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from repro.config import ServeConfig
from repro.serving.engine import pick_shard

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
from repro.config import CompressionConfig, ServeConfig
from repro.configs import get_config
from repro.core.calibration import GramAccumulator
from repro.models import build_model
from repro.serving import Request, ServingEngine
from repro.serving.engine import ShardedServingEngine

cfg = get_config("tinyllama-1.1b").reduced()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
acc = GramAccumulator(len(model.attn_layers))
for i in range(2):
    toks = jax.random.randint(jax.random.PRNGKey(5 + i), (2, 32),
                              0, cfg.vocab_size)
    caps = model.calibrate(params, toks)
    acc.update_from_captures([jax.tree.map(np.asarray, c) for c in caps])
ccfg = CompressionConfig(method="kqsvd", rank_k=16, rank_v=16)
proj = acc.solve(ccfg, model.group_output_weights(params))

rng = np.random.default_rng(0)


def mk(rid, length, max_new=5):
    p = rng.integers(0, cfg.vocab_size, size=length).astype(np.int32)
    return Request(rid=rid, prompt=p, max_new_tokens=max_new)


BASE = dict(max_seq_len=64, temperature=0.0, decode_chunk=4, paged=True,
            page_size=4, chunked_prefill=True, prefill_chunk=8,
            share_prefix=True, preempt_mode="swap",
            admission="optimistic", watermark_low=0.1, audit=True,
            audit_every=2)

# --- parity + skewed-length rebalance: 12 requests over 8 slots on a
# 4-shard mesh; the 4 queued requests route to whichever shard frees
# pages first, so every shard ends up doing real work ---------------
lens = [3, 30, 5, 26, 4, 22, 6, 18, 5, 7, 9, 11]
prompts = [rng.integers(0, cfg.vocab_size, size=L).astype(np.int32)
           for L in lens]


def reqs12():
    return [Request(rid=i, prompt=p, max_new_tokens=5)
            for i, p in enumerate(prompts)]


sc1 = ServeConfig(**BASE, max_batch=8, n_pages=64, shards=1)
out1 = ServingEngine(cfg, params, sc1, projections=proj).generate(reqs12())
ref = [list(r.out_tokens) for r in out1]
assert all(r.done and not r.failed for r in out1)

sc4 = ServeConfig(**BASE, max_batch=8, n_pages=64, shards=4)
eng4 = ServingEngine(cfg, params, sc4, projections=proj)
assert isinstance(eng4, ShardedServingEngine)
out4 = eng4.generate(reqs12())
assert [list(r.out_tokens) for r in out4] == ref
assert eng4.n_completed == 12 and eng4.n_audits > 0
print("SHARDED_PARITY_OK")

done_per_shard = [w.n_completed for w in eng4.workers]
assert sum(done_per_shard) == 12, done_per_shard
assert min(done_per_shard) >= 1, done_per_shard
print("REBALANCE_OK", done_per_shard)

# --- pool exhaustion stays shard-local: shard 0 gets two sequences
# whose prompts both fit its 10-page pool (4 pages each, so optimistic
# admission takes both) but which outgrow it during decode (16 + 16
# tokens -> 8 pages each); shard 1 two short ones.  Preemption must
# fire only on shard 0's slots and every request must still complete
# (swap preserves progress) -----------------------------------------
sc2 = ServeConfig(**BASE, max_batch=4, n_pages=20, shards=2)
eng2 = ServingEngine(cfg, params, sc2, projections=proj)
iso = [mk(0, 16, max_new=16), mk(1, 16, max_new=16),
       mk(2, 5, max_new=4), mk(3, 5, max_new=4)]
eng2.generate(iso)
assert all(r.done and not r.failed for r in iso), [r.error for r in iso]
w0, w1 = eng2.workers
assert w0.n_preempted > 0, "shard 0 never oversubscribed"
assert w1.n_preempted == 0, "exhaustion leaked to shard 1"
assert set(eng2.preempted_rids) <= {0, 1}, eng2.preempted_rids
print("ISOLATION_OK", w0.n_preempted)

# --- cross-shard prefix-index isolation: identical prompts routed to
# different shards never share pages (each worker owns its own index),
# and the outputs still agree token-for-token ------------------------
P = rng.integers(0, cfg.vocab_size, size=16).astype(np.int32)
Q = rng.integers(0, cfg.vocab_size, size=16).astype(np.int32)
sc3 = ServeConfig(**BASE, max_batch=4, n_pages=32, shards=2)
eng3 = ServingEngine(cfg, params, sc3, projections=proj)
# routing fills shard 0's two slots first: [P, Q] -> s0, [P, Q] -> s1
pre = [Request(rid=i, prompt=p.copy(), max_new_tokens=5)
       for i, p in enumerate([P, Q, P, Q])]
eng3.generate(pre)
assert all(r.done and not r.failed for r in pre)
assert pre[0].out_tokens == pre[2].out_tokens
assert pre[1].out_tokens == pre[3].out_tokens
assert eng3.n_shared_pages == 0 and eng3.n_full_hits == 0
ix = [w._pindex for w in eng3.workers]
assert ix[0] is not None and ix[0] is not ix[1]
print("PREFIX_ISOLATION_OK")
"""


@pytest.mark.slow
def test_sharded_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    env.pop("REPRO_ENGINE", None)      # configs above are pinned
    env["JAX_PLATFORMS"] = "cpu"       # forced host devices, never a chip
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    for sentinel in ("SHARDED_PARITY_OK", "REBALANCE_OK",
                     "ISOLATION_OK", "PREFIX_ISOLATION_OK"):
        assert sentinel in r.stdout, r.stdout


def test_shards_validation():
    base = dict(max_seq_len=32, max_batch=4, paged=True, page_size=4,
                chunked_prefill=True, prefill_chunk=8)
    with pytest.raises(ValueError, match="shards must be >= 1"):
        ServeConfig(**base, shards=0)
    with pytest.raises(ValueError, match="paged=True"):
        ServeConfig(max_seq_len=32, max_batch=4, shards=2)
    with pytest.raises(ValueError, match="token-budget"):
        ServeConfig(**base, shards=2, max_num_batched_tokens=6)
    with pytest.raises(ValueError, match="max_batch 3"):
        ServeConfig(max_seq_len=32, max_batch=3, paged=True, page_size=4,
                    chunked_prefill=True, prefill_chunk=8, shards=2)
    with pytest.raises(ValueError, match="total_pages 5"):
        ServeConfig(**base, shards=2, n_pages=5)
    # equal slices of both axes: fine
    assert ServeConfig(**base, shards=2, n_pages=8).shards == 2


def _stub(shard, free_slots, pending, free, used, high):
    pool = SimpleNamespace(free_count=free, used_count=used,
                           high_pages=high)
    return SimpleNamespace(_shard=shard, pool=pool,
                           _slot_req=[None] * free_slots,
                           _pending=[object()] * pending)


def test_pick_shard_scoring():
    # most admission headroom wins: free pages capped at the
    # high-watermark budget
    a = _stub(0, free_slots=2, pending=0, free=4, used=6, high=8)
    b = _stub(1, free_slots=2, pending=0, free=9, used=1, high=8)
    assert pick_shard([a, b]) is b        # 2 vs 7
    # past the watermark the cap zeroes the score even with free pages
    c = _stub(1, free_slots=2, pending=0, free=3, used=9, high=8)
    assert pick_shard([a, c]) is a        # 2 vs 0
    # ties break to the lower shard index (determinism)
    d = _stub(0, free_slots=1, pending=0, free=5, used=0, high=8)
    e = _stub(1, free_slots=1, pending=0, free=5, used=0, high=8)
    assert pick_shard([d, e]) is d


def test_pick_shard_capacity():
    # a local backlog (preemption requeues) consumes routing capacity
    # even while slots sit free, so new work repels from that shard
    a = _stub(0, free_slots=2, pending=2, free=9, used=0, high=8)
    b = _stub(1, free_slots=1, pending=0, free=2, used=7, high=8)
    assert pick_shard([a, b]) is b
    # no capacity anywhere: the head request waits (global FIFO)
    assert pick_shard([a, _stub(1, 1, 1, 9, 0, 8)]) is None
    # the routing loop threads residual capacities explicitly
    x = _stub(0, free_slots=2, pending=0, free=9, used=0, high=8)
    y = _stub(1, free_slots=2, pending=0, free=9, used=0, high=8)
    assert pick_shard([x, y], [0, 1]) is y
    assert pick_shard([x, y], [0, 0]) is None
