"""The program's side of a dense decoder configuration: its ``ArchConfig``
built from the published keys, and where each reference parameter sits in
the program's parameter tree."""
from __future__ import annotations

PATHS = {
    "embed": ("embed",),
    "lm_head": ("lm_head",),
    "final_norm": ("final_norm", "scale"),
    "layers/attn_norm": ("blocks", "attn_norm", "scale"),
    "layers/wq": ("blocks", "attn", "wq"),
    "layers/wk": ("blocks", "attn", "wk"),
    "layers/wv": ("blocks", "attn", "wv"),
    "layers/bq": ("blocks", "attn", "bq"),
    "layers/bk": ("blocks", "attn", "bk"),
    "layers/bv": ("blocks", "attn", "bv"),
    "layers/wo": ("blocks", "attn", "wo"),
    "layers/mlp_norm": ("blocks", "mlp_norm", "scale"),
    "layers/w_gate": ("blocks", "mlp", "w_gate"),
    "layers/w_up": ("blocks", "mlp", "w_up"),
    "layers/w_down": ("blocks", "mlp", "w_down"),
}


def arch_config(c: dict):
    from repro.configs import get_config

    H = c["num_attention_heads"]
    return get_config(c["program_arch"]).with_(
        arch_type="dense",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=H, n_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // H, d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], qkv_bias=True, act=c["hidden_act"],
        tie_embeddings=bool(c["tie_word_embeddings"]),
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        norm_type="rmsnorm", window=0)
