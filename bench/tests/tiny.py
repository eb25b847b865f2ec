"""A tiny cell for CPU tests: the same harness, reference and engine
path as a real cell, at widths a test run can hold."""
import copy

from bench import harness

CONF = {"name": "tiny-dense", "source": "test",
        "reference": "dense_reference", "program": "dense_program",
        "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 256, "rms_norm_eps": 1e-5,
        "rope_theta": 10000.0, "tie_word_embeddings": False,
        "torch_dtype": "bfloat16",
        "kqsvd": {"method": "kqsvd", "rank_k": 8, "rank_v": 8,
                  "calib_seqs": 2, "calib_len": 64}}
MIX = {"kind": "offline_batch", "backlog": 400, "strata": 8,
       "prompt": {"median": 24, "sigma": 0.4, "min": 9, "max": 48},
       "output": {"median": 8, "sigma": 0.5, "min": 3, "max": 16}}
#: readings on the CPU: sound tiny runs at most 0.024 (seeds 1-12), the
#: float8 control at least 0.22 (seeds 1-12), the planted faults 2.9 and
#: more; the limit sits between, nearer the lower reading
WORKLOAD = {"config": "tiny-dense", "traffic": "tiny", "chips": 1,
            "serve": {"max_seq_len": 64, "max_batch": 4, "paged": True,
                      "chunked_prefill": True, "cache_quant": "none",
                      "page_size": 8, "prefill_chunk": 32},
            "check": {"sample_tokens": 32, "max_requests": 4,
                      "min_tokens_checked": 8, "logit_gap": 0.1}}


def cell(name: str = "phi3v-azconv", **serve) -> harness.Cell:
    """The tiny cell under a real cell's name (so it reports that cell's
    metrics)."""
    wl = copy.deepcopy(WORKLOAD)
    wl["serve"].update(serve)
    return harness.Cell(name, wl, copy.deepcopy(CONF), copy.deepcopy(MIX))
