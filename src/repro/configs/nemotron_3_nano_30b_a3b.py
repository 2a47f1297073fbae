"""NVIDIA Nemotron 3 Nano 30B-A3B: 52 layers of three kinds, each a pre-norm
mixer and a residual add, in the published order
``MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME``:

* M, a Mamba-2 mixer: 64 heads of 64 (d_inner 4096, not 2 x d_model),
  8 B/C groups of state 128, a causal convolution of width 4 with a bias,
  chunk 128, and the gated RMSNorm over each group's 512 channels;
* E, an expert layer: a sigmoid router over 128 experts, 6 per token, the
  weights renormalised over the 6 and scaled by 2.5; relu^2 experts of
  width 1856, ``down(relu(up x)^2)``; one shared expert of width 3712;
* *, grouped-query attention, 32 query heads and 2 KV heads of 128, with
  no rotary embedding.

[hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json]  d_model 2688,
vocabulary 131,072 untied, RMSNorm eps 1e-5; 31,577,940,288 parameters.
The router's zero-initialised correction bias only picks experts.
"""

from repro.configs.base import ArchConfig, register

CONFIG = register(ArchConfig(
    name="nemotron-3-nano-30b-a3b",
    family="interleaved",
    num_layers=52,
    layer_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    d_model=2688,
    num_heads=32,
    num_kv_heads=2,
    head_dim=128,
    rope=False,
    d_ff=1856,
    vocab_size=131072,
    num_experts=128,
    experts_per_token=6,
    moe_router="sigmoid",
    moe_routed_scale=2.5,
    expert_act="relu2",
    shared_expert=True,
    shared_expert_ff=3712,
    ssm_state=128,
    ssm_num_heads=64,
    ssm_headdim=64,
    ssm_groups=8,
    ssm_chunk=128,
    conv_width=4,
    conv_bias=True,
    tie_embeddings=False,
    norm_eps=1e-5,
    source="hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16; config.json",
))
