"""How the program under test takes a ``dense_reference`` configuration.

The only file of the benchmark that knows the program's layouts: it
builds the program's model configuration from a configuration file, and
hands the benchmark's weights and projections over in the program's
pytree and dataclass, without copying an array.
"""
from __future__ import annotations

import numpy as np


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.config import ModelConfig
    D, H = conf["hidden_size"], conf["num_attention_heads"]
    return ModelConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=D, n_heads=H,
        n_kv_heads=conf["num_key_value_heads"],
        d_head=conf.get("head_dim") or D // H,
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        rms_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        dtype=conf["torch_dtype"], source=conf["source"])


def params(w: dict) -> dict:
    """The benchmark's weights as the program's parameter pytree: one
    scanned step per layer, leaves stacked on the layer axis."""
    layer = {"ln1": w["ln1"], "ln2": w["ln2"],
             "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                      "wo": w["wo"]},
             "ffn": {"wi": w["wi"], "wg": w["wg"], "wo": w["wdown"]}}
    return {"embed": w["embed"], "lm_head": w["lm_head"],
            "final_norm": w["final_norm"], "prefix": [],
            "steps": {"layers": (layer,)}}


def projections(p: dict):
    """The benchmark's KQ-SVD factors as the program's projections."""
    from repro.core.calibration import ModelProjections
    L = p["a_k"].shape[0]
    return ModelProjections(
        a_k=np.asarray(p["a_k"]), b_q=np.asarray(p["b_q"]),
        a_v=np.asarray(p["a_v"]), c_v=np.asarray(p["c_v"]),
        ranks_k=[p["a_k"].shape[-1]] * L, ranks_v=[p["a_v"].shape[-1]] * L,
        method="kqsvd")
