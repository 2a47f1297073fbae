"""Hymba 1.5B: hybrid-head blocks — attention and Mamba(SSM) heads run in
PARALLEL inside every layer; 128 learnable meta tokens prepended; sliding-
window attention everywhere except a few global anchor layers.

[arXiv:2411.13676; hf]  32L d_model=1600 25H (GQA kv=5) d_ff=5504
vocab=32001, ssm_state=16.  head_dim=64.
"""

from repro.configs.base import ArchConfig, register

CONFIG = register(ArchConfig(
    name="hymba-1.5b",
    family="hybrid",
    num_layers=32,
    d_model=1600,
    num_heads=25,
    num_kv_heads=5,
    head_dim=64,
    d_ff=5504,
    vocab_size=32001,
    attn_pattern="swa_mostly",
    window_size=1024,
    ssm_state=16,
    ssm_expand=2,
    ssm_headdim=64,
    ssm_chunk=256,
    conv_width=4,
    num_meta_tokens=128,
    source="arXiv:2411.13676; hf",
))
