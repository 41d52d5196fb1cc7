"""Smoke sizes of the cells' configurations and traffic: every key of the
real ones, at widths the Pallas interpreter runs in seconds on the CPU."""

DENSE = {
    "family": "dense", "hidden_size": 256, "intermediate_size": 384,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "num_hidden_layers": 2,
}
SSM = {
    "family": "ssm", "d_model": 128, "d_inner": 256, "nheads": 4,
    "headdim": 64, "d_state": 16, "ngroups": 1, "chunk_size": 32,
    "in_proj_size": 2 * 256 + 2 * 16 + 4, "n_layer": 3,
    "A_init_range": [1, 16], "dt_min": 0.001, "dt_max": 0.1,
}
PREFILL = {"phase": "prefill", "batch": 2, "seq_len": 64, "causal": True,
           "cache_batch": 4, "cache_slots": 128, "inputs": 2}
DECODE = {"phase": "decode", "batch": 4, "cache_slots": 128, "len_min": 16,
          "len_max": 96, "inputs": 2}

CELLS = {
    "dense_prefill": (DENSE, PREFILL),
    "dense_decode": (DENSE, DECODE),
    "ssm_prefill": (SSM, PREFILL),
    "ssm_decode": (SSM, DECODE),
}
