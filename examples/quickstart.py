"""Quickstart: the full KQ-SVD lifecycle in ~60 lines.

    PYTHONPATH=src python examples/quickstart.py

1. train a small llama-family model on synthetic data,
2. calibrate K/Q/V Gram statistics (the paper's 128x2048 protocol,
   scaled down),
3. solve the closed-form KQ-SVD projections (Thm 2) at eps=0.1,
4. serve with the compressed cache and compare against the full cache.
"""
import jax.numpy as jnp
import numpy as np

from repro.config import CompressionConfig, ServeConfig, TrainConfig
from repro.configs import get_config
from repro.core.calibration import calibrate_model
from repro.core.compressed import cache_footprint
from repro.data import DataConfig, batches, calibration_batches
from repro.serving import Request, ServingEngine
from repro.train import Trainer

cfg = get_config("tinyllama-1.1b").reduced()
print(f"model: {cfg.name}  layers={cfg.n_layers} d={cfg.d_model} "
      f"heads={cfg.n_heads}/{cfg.n_kv_heads}")

# 1. train briefly
tc = TrainConfig(learning_rate=3e-3, warmup_steps=2, total_steps=30,
                 checkpoint_every=0)
trainer = Trainer(cfg, tc)
report = trainer.run(
    batches(DataConfig(cfg.vocab_size, seq_len=64, batch_size=4)), 30)
print(f"trained 30 steps: loss {report.losses[0]:.3f} -> "
      f"{report.final_loss:.3f}")
params = trainer.state["params"]
model = trainer.model

# 2 + 3. calibrate and solve KQ-SVD projections
calib = [jnp.asarray(b) for b in
         calibration_batches(cfg.vocab_size, n_seqs=8, seq_len=64,
                             batch=4)]
proj = calibrate_model(model, params, calib,
                       CompressionConfig(method="kqsvd", epsilon=0.1))
fp = cache_footprint(cfg.n_kv_heads, cfg.d_head, proj.rank_k,
                     proj.rank_v)
print(f"KQ-SVD ranks per layer: k={proj.ranks_k} v={proj.ranks_v}")
print(f"cache bytes/token/layer: {fp.full_bytes} -> "
      f"{fp.compressed_bytes} ({1/fp.ratio:.2f}x more sequences per HBM)")

# 4. serve, compressed vs full
prompt = np.arange(12, dtype=np.int32) % cfg.vocab_size
for label, p in [("full cache ", None), ("kqsvd cache", proj)]:
    eng = ServingEngine(cfg, params, ServeConfig(max_seq_len=64,
                                                 max_batch=2,
                                                 page_size=16),
                        projections=p)
    reqs = [Request(rid=0, prompt=prompt, max_new_tokens=8)]
    eng.generate(reqs)
    print(f"{label}: {reqs[0].out_tokens}")
