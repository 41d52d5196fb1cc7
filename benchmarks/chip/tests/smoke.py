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

# every key of granite-4.0-h-small's configuration that its pass reads: 3
# of 8 experts held from the third on, top 3, a Mamba, attention, Mamba
# stage
HYBRID = {
    "family": "hybrid_moe", "hidden_size": 256, "mamba_d_inner": 256,
    "mamba_n_heads": 4, "mamba_d_head": 64, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_in_proj_size": 2 * 256 + 2 * 16 + 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "intermediate_size": 128, "shared_intermediate_size": 256,
    "num_local_experts": 3, "first_held_expert": 2,
    "published": {"num_local_experts": 8}, "num_experts_per_tok": 3,
    "attention_multiplier": 0.125, "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-5, "layer_types": ["mamba", "attention", "mamba"],
    "num_hidden_layers": 3, "A_init_range": [1, 16], "dt_min": 0.001,
    "dt_max": 0.1,
}
# BERT-Large's Table 2 rows at smoke widths: 2 layers of 4 heads of 64
LAYERS = {
    "family": "layers", "hidden_size": 256, "num_attention_heads": 4,
    "head_dim": 64, "intermediate_size": 512, "num_hidden_layers": 2,
    "seq_len": 32,
    "rows": [dict(zip(("name", "m", "n", "k", "heads"), row)) for row in (
        ("BERT-LG-ATN1-GEMM", 32, 64, 256, 4),
        ("BERT-LG-ATN2-GEMM", 32, 32, 64, 4),
        ("BERT-LG-ATN3-GEMM", 32, 64, 32, 4),
        ("BERT-LG-ATN4-GEMM", 32, 256, 256, 1),
        ("BERT-LG-GEMM1", 32, 512, 256, 1),
        ("BERT-LG-GEMM2", 32, 256, 512, 1))],
}
GEMM = {"phase": "gemm", "batch": 3, "inputs": 2}

CELLS = {
    "dense_prefill": (DENSE, PREFILL),
    "dense_decode": (DENSE, DECODE),
    "ssm_prefill": (SSM, PREFILL),
    "ssm_decode": (SSM, DECODE),
    "hybrid_moe_decode": (HYBRID, DECODE),
    "layers_gemm": (LAYERS, GEMM),
}
