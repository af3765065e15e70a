"""A cell at toy widths for the CPU tests: the same harness, engine
path and reference as the chip cells, in seconds."""

from __future__ import annotations

import copy

CFG = {
    "name": "tiny-gqa", "model_type": "dense_gqa",
    "reference": "bench/reference/dense_gqa.py",
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 512, "hidden_act": "silu", "tie_word_embeddings": True,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "deployment": {"chips": 1, "mesh_model": 1, "num_pages": 192,
                   "page_size": 16, "prefix_cache": True,
                   "max_prefill_chunk": 64, "mixed_rounds": False,
                   "server": {"admit_backlog_chunks": 8}, "max_batch": 4,
                   "max_chunk_rows": 1, "max_page_init": 24},
    # at these widths, over three seeds and 60-115 compared tokens, the
    # program read at most 0.0022 and the float8 control at least 0.030
    "check": {"min_tokens": 96, "max_seqs": 6, "max_logit_gap": 0.015},
}

CHAT = {
    "arrival": "open_sessions", "rate_rps": 3.0, "lead_in_s": 1.0,
    "max_horizon_s": 10, "mix_seed": 5, "share_prefixes": True,
    "context_tokens": 320,
    "system_prompts": {"count": 2, "min": 64, "max": 120, "zipf_s": 1.0},
    "turns": {"mean": 2, "max": 3},
    "message": {"median": 24, "sigma": 0.5, "min": 16, "max": 64},
    "output": {"median": 16, "sigma": 0.5, "min": 8, "max": 32},
    "think_s": {"min": 0.5, "max": 1.0},
    "bounds": {"min_seq_tokens": 80, "max_seq_tokens": 320},
}

OFFLINE = {
    "arrival": "closed", "concurrency": 4, "fill_per_round": 2,
    "lead_in_s": 0.5, "pool": 16, "mix_seed": 6,
    "prompt": {"median": 48, "sigma": 0.4, "min": 33, "max": 64},
    "output": {"median": 8, "sigma": 0.4, "min": 4, "max": 12},
    "bounds": {"min_seq_tokens": 33, "max_seq_tokens": 76,
               "prompt_buckets": [64]},
}

PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def cell(mix=None, **dep):
    """A tiny chat cell (or ``mix``), with deployment overrides."""
    cfg = copy.deepcopy(CFG)
    cfg["deployment"].update(dep)
    mix = copy.deepcopy(mix or CHAT)
    if mix["arrival"] == "closed":
        cfg["deployment"].update(prefix_cache=False, max_prefill_chunk=None,
                                 max_prefill_rows=4, min_decode_batch=2,
                                 max_page_init=8)
    names = ["ttft_p90_ms", "output_tokens_per_s", "setup_s"]
    units = {"ttft_p90_ms": "ms",
             "output_tokens_per_s": "tokens/s", "setup_s": "s"}
    return {"cell": {"name": "tiny", "chips": 1}, "cfg": cfg, "mix": mix,
            "end_to_end": [{"name": n, "unit": units[n]} for n in names],
            "per_layer": [], "peak": PEAK}
