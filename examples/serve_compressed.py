"""Compressed serving demo: method comparison on the same model.

    PYTHONPATH=src python examples/serve_compressed.py

Serves identical greedy requests with the full cache and with
K-SVD / Eigen / KQ-SVD compressed caches at the same rank, reporting
agreement with the uncompressed output and the HBM capacity gain.
"""

import jax
import numpy as np

from repro.config import CompressionConfig, ServeConfig
from repro.configs import get_config
from repro.core.calibration import GramAccumulator
from repro.models import build_model
from repro.serving import Request, ServingEngine

cfg = get_config("tinyllama-1.1b").reduced()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))

acc = GramAccumulator(len(model.attn_layers))
for i in range(4):
    toks = jax.random.randint(jax.random.PRNGKey(10 + i), (4, 64), 0,
                              cfg.vocab_size)
    caps = model.calibrate(params, toks)
    acc.update_from_captures([jax.tree.map(np.asarray, c) for c in caps])
w_out = model.group_output_weights(params)

prompt = (np.arange(12) * 5 % cfg.vocab_size).astype(np.int32)
sc = ServeConfig(max_seq_len=48, max_batch=2, page_size=16)

ref_eng = ServingEngine(cfg, params, sc)
ref = [Request(rid=0, prompt=prompt, max_new_tokens=8)]
ref_eng.generate(ref)
print(f"{'full':8s}: {ref[0].out_tokens}")

R = cfg.d_head // 2
for method in ("ksvd", "eigen", "kqsvd"):
    mp = acc.solve(CompressionConfig(method=method, rank_k=R, rank_v=R),
                   w_out)
    eng = ServingEngine(cfg, params, sc, projections=mp)
    reqs = [Request(rid=0, prompt=prompt, max_new_tokens=8)]
    eng.generate(reqs)
    agree = sum(a == b for a, b in zip(reqs[0].out_tokens,
                                       ref[0].out_tokens))
    print(f"{method:8s}: {reqs[0].out_tokens}  "
          f"agree {agree}/8  capacity x{eng.capacity_gain():.1f}")
