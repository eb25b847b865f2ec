"""A tiny MiMo-shaped configuration for CPU tests: two attention kinds
with 1 (full) and 2 (window) KV heads, q/k heads 24 and v heads 16 with
8 dims rotated, a 16-token window with sinks, and an expert layer that
holds 4 of 16 experts, through the same reference and program adapter
as ``mimo-v2-flash-12l``."""
import copy
import json
from pathlib import Path

from bench import harness

#: 0 full, 1 window: the published pattern's first 18 layers
PATTERN = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 2


def conf(n_layers: int = 6, dtype: str = "float32", n_held: int = 4,
         n_experts: int = 16) -> dict:
    """The full configuration file's keys at test widths."""
    c = json.loads((Path(harness.BENCH) / "configs"
                    / "mimo-v2-flash-12l.json").read_text())
    c.update({"name": "tiny-mimo", "hidden_size": 64,
              "intermediate_size": 96, "moe_intermediate_size": 32,
              "num_attention_heads": 4, "num_key_value_heads": 1,
              "swa_num_key_value_heads": 2, "swa_num_attention_heads": 4,
              "head_dim": 24, "swa_head_dim": 24, "v_head_dim": 16,
              "swa_v_head_dim": 16, "partial_rotary_factor": 1 / 3,
              "sliding_window": 16, "sliding_window_size": 16,
              "num_hidden_layers": n_layers, "num_experts_per_tok": 4,
              "n_routed_experts": n_held, "vocab_size": 256,
              "hybrid_layer_pattern": PATTERN,
              "moe_layer_freq": [0] + [1] * 17, "torch_dtype": dtype,
              "kqsvd": {"method": "kqsvd", "rank_k": 12, "rank_v": 8,
                        "calib_seqs": 2, "calib_len": 64}})
    c["published"] = dict(c["published"], n_routed_experts=n_experts)
    return c


MIX = {"kind": "offline_batch", "backlog": 64, "strata": 8,
       "prompt": {"median": 40, "sigma": 0.4, "min": 17, "max": 72},
       "output": {"median": 12, "sigma": 0.5, "min": 3, "max": 24}}
#: readings on the CPU in bfloat16 (1 s windows, router near-ties
#: unscored): sound tiny runs 0.22, 0.21, 0.12 and 0.06 (seeds 1, 2, 3,
#: 2**33 + 7), the float8 control 0.61, 0.35, 0.81 and 2.37 on the same
#: seeds; the limit sits between
WORKLOAD = {"config": "tiny-mimo", "traffic": "tiny", "chips": 1,
            "serve": {"max_seq_len": 96, "max_batch": 4, "paged": True,
                      "chunked_prefill": True, "cache_quant": "none",
                      "page_size": 8, "prefill_chunk": 32},
            "check": {"sample_tokens": 32, "max_requests": 4,
                      "min_tokens_checked": 8, "logit_gap": 0.3}}


def cell(name: str = "mimov2f-mooncake", **serve) -> harness.Cell:
    """The tiny cell under a real cell's name (so it reports that cell's
    metrics), in bfloat16 as served."""
    wl = copy.deepcopy(WORKLOAD)
    wl["serve"].update(serve)
    return harness.Cell(name, wl, conf(dtype="bfloat16"), copy.deepcopy(MIX))
