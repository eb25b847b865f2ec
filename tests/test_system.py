"""End-to-end system test: train -> calibrate -> compress -> serve.

The full lifecycle a deployment would run, on a reduced config: a few
training steps, paper-style calibration, KQ-SVD solve at eps, compressed
serving, and the accounting that justifies it.
"""

import jax
import jax.numpy as jnp
import numpy as np

from conftest import dropless
from repro.config import (CompressionConfig, ServeConfig, TrainConfig)
from repro.configs import get_config
from repro.core.calibration import calibrate_model
from repro.core.compressed import cache_footprint, projection_param_bytes
from repro.data import DataConfig, batches, calibration_batches
from repro.serving import Request, ServingEngine
from repro.train import Trainer


def test_full_lifecycle():
    cfg = dropless(get_config("tinyllama-1.1b").reduced())
    tc = TrainConfig(learning_rate=3e-3, warmup_steps=2, total_steps=10,
                     checkpoint_every=0)
    trainer = Trainer(cfg, tc)
    state = trainer.init_state()
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, batch_size=4)
    report = trainer.run(batches(dc), 10, state=state)
    assert report.losses[-1] < report.losses[0]

    # calibration (paper: sequences through the model, collect caches)
    model = trainer.model
    params = trainer.resume_or_init()["params"] if trainer.ckpt else None
    params = model.init(jax.random.PRNGKey(0))
    calib = [jnp.asarray(b) for b in
             calibration_batches(cfg.vocab_size, n_seqs=4, seq_len=32,
                                 batch=2)]
    ccfg = CompressionConfig(method="kqsvd", epsilon=0.05)
    mp = calibrate_model(model, params, calib, ccfg)
    assert len(mp.ranks_k) == len(model.attn_layers)

    # compressed serving
    eng = ServingEngine(cfg, params, ServeConfig(max_seq_len=64,
                                                 max_batch=2, page_size=16,
                                                 prefill_chunk=16),
                        projections=mp)
    reqs = [Request(rid=i, prompt=np.arange(8, dtype=np.int32),
                    max_new_tokens=4) for i in range(2)]
    eng.generate(reqs)
    assert all(len(r.out_tokens) == 4 for r in reqs)

    # accounting: compressed cache strictly smaller at eps=0.05 or equal
    fp = cache_footprint(cfg.n_kv_heads, cfg.d_head, mp.rank_k, mp.rank_v)
    assert fp.compressed_bytes <= fp.full_bytes
    assert projection_param_bytes(mp) > 0
