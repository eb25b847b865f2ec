"""How the program under test takes a ``mimo_reference`` configuration.

The only file of the benchmark that knows the program's layouts for
this model: it builds the program's model configuration (per-layer
attention kinds, routing and the experts held here) from a
configuration file, and hands the benchmark's weights and per-kind
projections over in the program's pytree and dataclass.  A program
without ``ModelConfig.attn_kinds`` fails here, at once.
"""
from __future__ import annotations

import numpy as np


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.config import (AttnKind, AttnKindsConfig, ModelConfig,
                              MoEConfig, RoutingConfig)
    L = conf["num_hidden_layers"]
    dqk = conf["head_dim"]
    full = AttnKind(n_kv_heads=conf["num_key_value_heads"],
                    rope_theta=float(conf["rope_theta"]))
    window = AttnKind(n_kv_heads=conf["swa_num_key_value_heads"],
                      rope_theta=float(conf["swa_rope_theta"]),
                      window=conf["sliding_window"],
                      sink=bool(conf["add_swa_attention_sink_bias"]))
    dense = [not f for f in conf["moe_layer_freq"][:L]]
    assert dense == [True] + [False] * (L - 1), "one leading dense layer"
    # the program's RoutingConfig router
    assert (conf["scoring_func"], conf["topk_method"],
            conf["norm_topk_prob"]) == ("sigmoid", "noaux_tc", True)
    return ModelConfig(
        name=conf["name"], family="moe", n_layers=L,
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], d_head=dqk,
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        rms_eps=float(conf["layernorm_epsilon"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        attn_kinds=AttnKindsConfig(
            pattern=tuple(conf["hybrid_layer_pattern"][:L]),
            kinds=(full, window), v_head_dim=conf["v_head_dim"],
            rotary_dim=int(round(conf["partial_rotary_factor"] * dqk)),
            value_scale=float(conf["attention_value_scale"])),
        moe=MoEConfig(n_experts=conf["published"]["n_routed_experts"],
                      top_k=conf["num_experts_per_tok"],
                      expert_ff=conf["moe_intermediate_size"],
                      first_k_dense=1,
                      first_dense_ff=conf["intermediate_size"]),
        routing=RoutingConfig(held=(0, conf["n_routed_experts"])),
        dtype=conf["torch_dtype"], source=conf["source"])


def _kind_index(pattern, i: int) -> int:
    return sum(1 for k in pattern[:i] if k == pattern[i])


def _step_layout(pattern):
    """The program's prefix and scanned steps for a layer pattern (one
    leading dense layer, experts after it), from a skeleton config."""
    from repro.config import AttnKind, AttnKindsConfig, ModelConfig, \
        MoEConfig
    from repro.models.blocks import step_layout
    return step_layout(ModelConfig(
        name="layout", family="moe", n_layers=len(pattern), d_model=1,
        n_heads=1, n_kv_heads=1, d_ff=1, vocab_size=1,
        attn_kinds=AttnKindsConfig(
            pattern=tuple(pattern),
            kinds=(AttnKind(1, 1.0), AttnKind(1, 1.0, window=1))),
        moe=MoEConfig(n_experts=1, top_k=1, expert_ff=1, first_k_dense=1)))


def params(w: dict) -> dict:
    """The benchmark's weights as the program's parameter pytree.  The
    program runs a stack of one period or less (this cut's 12 layers)
    unrolled, one prefix entry a layer, so every leaf passes through
    without a copy; deeper stacks' scanned steps are stacked (copied).
    The program is served with projections, whose C_v takes W_o's
    place, so W_o (``w["wo"]``, the solve's input) is left out."""
    import jax
    import jax.numpy as jnp
    pattern = [int(k) for k in np.asarray(w["layer_kind"])]
    prefix, steps = _step_layout(pattern)

    def layer(i):
        # the served path reads C_v in W_o's place: W_o is not handed over
        attn = {k: w[f"{k}.{i}"] for k in ("wq", "wk", "wv", "sink")
                if f"{k}.{i}" in w}
        if f"wi.{i}" in w:
            ffn = {"wi": w[f"wi.{i}"], "wg": w[f"wg.{i}"],
                   "wo": w[f"wdown.{i}"]}
        else:
            ffn = {k: w[f"{k}.{i}"] for k in ("router", "bias", "wi_e",
                                              "wg_e", "wo_e")}
        return {"ln1": w[f"ln1.{i}"], "ln2": w[f"ln2.{i}"], "attn": attn,
                "ffn": ffn}

    out = {"embed": w["embed"], "lm_head": w["lm_head"],
           "final_norm": w["final_norm"],
           "prefix": [layer(i) for i in prefix]}
    if steps:
        out["steps"] = {"layers": tuple(
            jax.tree.map(lambda *xs: jnp.stack(xs),
                         *[layer(st[j]) for st in steps])
            for j in range(len(steps[0])))}
    return out


def projections(p: dict):
    """The benchmark's per-kind KQ-SVD factors as the program's
    projections: one list entry per attention layer, in layer order."""
    from repro.core.calibration import ModelProjections
    pattern = [int(k) for k in np.asarray(p["layer_kind"])]
    kinds = ("full", "window")

    def per_layer(name):
        return [np.asarray(p[f"{name}.{kinds[k]}"][_kind_index(pattern, i)])
                for i, k in enumerate(pattern)]

    a_k, a_v = per_layer("a_k"), per_layer("a_v")
    return ModelProjections(
        a_k=a_k, b_q=per_layer("b_q"), a_v=a_v, c_v=per_layer("c_v"),
        ranks_k=[a.shape[-1] for a in a_k],
        ranks_v=[a.shape[-1] for a in a_v], method="kqsvd")
