"""The program's side of a Mamba-2 configuration: its ``ArchConfig`` built
from the published keys, and where each reference parameter sits in the
program's parameter tree."""
from __future__ import annotations

PATHS = {
    "embed": ("embed",),
    "lm_head": ("lm_head",),
    "final_norm": ("final_norm", "scale"),
    "layers/norm": ("ssm_blocks", "norm", "scale"),
    "layers/in_proj": ("ssm_blocks", "ssm", "in_proj"),
    "layers/conv_w": ("ssm_blocks", "ssm", "conv_w"),
    "layers/conv_b": ("ssm_blocks", "ssm", "conv_b"),
    "layers/A_log": ("ssm_blocks", "ssm", "A_log"),
    "layers/dt_bias": ("ssm_blocks", "ssm", "dt_bias"),
    "layers/D": ("ssm_blocks", "ssm", "D"),
    "layers/gate_norm": ("ssm_blocks", "ssm", "norm"),
    "layers/out_proj": ("ssm_blocks", "ssm", "out_proj"),
}


def arch_config(c: dict):
    from repro.configs import get_config
    from repro.models.base import SSMConfig

    m = c["mamba2_module"]
    return get_config(c["program_arch"]).with_(
        arch_type="ssm", n_layers=c["n_layer"], d_model=c["d_model"],
        n_heads=0, n_kv_heads=0, d_ff=0, vocab=c["padded_vocab_size"],
        head_dim=m["headdim"], tie_embeddings=bool(c["tie_embeddings"]),
        norm_eps=float(m["norm_epsilon"]), norm_type="rmsnorm",
        ssm=SSMConfig(d_state=m["d_state"], d_conv=m["d_conv"],
                      expand=m["expand"], head_dim=m["headdim"],
                      n_groups=m["ngroups"], chunk=m["chunk_size"]))
