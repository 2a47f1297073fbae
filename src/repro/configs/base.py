"""Architecture configuration schema and registry.

Every assigned architecture lives in its own module (``configs/<id>.py``)
holding the exact published configuration, registered under its public id
(e.g. ``gemma2-27b``).  ``reduced()`` derives a family-preserving small
variant used by the per-arch CPU smoke tests; the full configs are only
ever lowered abstractly via the dry-run (ShapeDtypeStruct, no allocation).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple


@dataclass(frozen=True)
class ArchConfig:
    """A complete, family-generic model description.

    ``repro.models.transformer.layer_plan`` reads ``family`` and
    ``layer_pattern`` once into each layer's mixer and FFN; unused fields
    are zero/None for families that do not need them.
    """

    name: str
    family: str  # dense | moe | ssm | hybrid | interleaved | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int  # 0 for attention-free (SSM) architectures
    num_kv_heads: int
    d_ff: int  # per-expert FFN dim for MoE archs
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads

    # --- attention features -------------------------------------------------
    # layer pattern: "global" | "local_global_1_1" | "local_global_5_1"
    #              | "swa_mostly" (hybrid: global only at a few anchor layers)
    attn_pattern: str = "global"
    window_size: int = 4096
    attn_logit_softcap: float = 0.0  # 0 -> disabled
    final_logit_softcap: float = 0.0
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    rope_theta_global: float = 0.0  # 0 -> same as rope_theta
    rope: bool = True             # False: attention without positions

    # --- interleaved: one mixer per layer, in the order of this pattern ----
    # "M" Mamba-2 mixer, "E" expert layer, "*" attention (one char a layer)
    layer_pattern: str = ""

    # --- MoE -----------------------------------------------------------------
    num_experts: int = 0          # the router's width
    experts_per_token: int = 0
    # experts whose weights live here; 0 -> all.  Fewer than num_experts is
    # one chip's cut of an expert-parallel layout, whose held experts take
    # exactly their balanced share of each sequence's slots (models/moe.py)
    moe_experts_held: int = 0
    moe_router: str = "softmax"   # softmax | sigmoid (bias picks, not weighs)
    moe_routed_scale: float = 1.0
    expert_act: str = "swiglu"    # swiglu: down(silu(gate x) up x) | relu2
    shared_expert: bool = False  # Llama-4 style always-on shared expert
    shared_expert_ff: int = 0     # 0 -> d_ff

    # --- SSM (Mamba-2 SSD) ---------------------------------------------------
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_chunk: int = 256
    ssm_num_heads: int = 0        # 0 -> ssm_expand * d_model // ssm_headdim
    ssm_groups: int = 1           # B/C groups, each shared by heads/groups
    conv_width: int = 4
    conv_bias: bool = False

    # --- hybrid (Hymba) ------------------------------------------------------
    num_meta_tokens: int = 0

    # --- modality frontends (stubs per task spec) ----------------------------
    frontend: str = "tokens"  # tokens | audio_frames | image_patches
    cross_attn_every: int = 0  # vlm: every k-th layer is a cross-attn layer
    num_image_tokens: int = 0

    # --- misc ----------------------------------------------------------------
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    scale_embed: bool = False     # gemma-style sqrt(d_model) embedding scale
    query_scale: float = 0.0      # 0 -> head_dim**-0.5
    post_norms: bool = False      # gemma-2/3 sandwich (post-block) norms
    source: str = ""  # provenance note from the assignment table

    # ------------------------------------------------------------------ utils
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    @property
    def d_inner(self) -> int:
        """SSM inner width."""
        if self.ssm_num_heads:
            return self.ssm_num_heads * self.ssm_headdim
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim if self.ssm_state else 0

    @property
    def ssm_conv_dim(self) -> int:
        """Channels of the SSM's convolution: x, then B and C per group."""
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def experts_held(self) -> int:
        return self.moe_experts_held or self.num_experts

    @property
    def shared_ff(self) -> int:
        return self.shared_expert_ff or self.d_ff

    @property
    def has_attention(self) -> bool:
        return self.num_heads > 0

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def sub_quadratic(self) -> bool:
        """True when long_500k is runnable: SSM/hybrid or sliding-window
        local layers dominate (gemma-style local:global alternation)."""
        if self.family in ("ssm", "hybrid"):
            return True
        return self.attn_pattern.startswith("local_global")

    def layer_kinds(self) -> List[str]:
        """Per-layer attention kind: 'global' | 'local' | 'ssm'.  A hybrid's
        attention heads follow ``attn_pattern`` like any other's."""
        n = self.num_layers
        if self.family == "ssm":
            return ["ssm"] * n
        if self.layer_pattern:
            if len(self.layer_pattern) != n:
                raise ValueError(f"layer_pattern {self.layer_pattern!r} has "
                                 f"{len(self.layer_pattern)} layers, not {n}")
            kinds = {"M": "ssm", "E": "moe", "*": "global"}
            return [kinds[ch] for ch in self.layer_pattern]
        if self.attn_pattern == "global":
            return ["global"] * n
        if self.attn_pattern == "local_global_1_1":
            # gemma-2: alternate local, global, local, global, ...
            return ["local" if i % 2 == 0 else "global" for i in range(n)]
        if self.attn_pattern == "local_global_5_1":
            # gemma-3: every 6th layer is global
            return ["global" if (i + 1) % 6 == 0 else "local" for i in range(n)]
        if self.attn_pattern == "swa_mostly":
            anchors = {0, n // 2, n - 1}
            return ["global" if i in anchors else "local" for i in range(n)]
        raise ValueError(f"unknown attn_pattern {self.attn_pattern!r}")

    def cross_attn_layers(self) -> List[int]:
        if not self.cross_attn_every:
            return []
        return [i for i in range(self.num_layers)
                if (i + 1) % self.cross_attn_every == 0]

    def _expert_params(self, ff: int) -> int:
        return (3 if self.expert_act == "swiglu" else 2) * self.d_model * ff

    def param_count(self) -> int:
        """Number of parameters of the model as built: the elements of
        ``repro.models.Model(self).init``'s tree, from its shapes alone,
        so that no second description of a layer is kept here.  This is
        the module's one reference up to the model, on purpose; the import
        sits in the method because ``repro.models`` imports this module."""
        import jax
        from repro.models import Model
        tree = jax.eval_shape(Model(self).init, jax.random.PRNGKey(0))
        return sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))

    def active_param_count(self) -> int:
        """Active parameters per token (MoE: only routed experts count)."""
        if not self.is_moe:
            return self.param_count()
        moe_layers = (self.layer_kinds().count("moe") if self.layer_pattern
                      else self.num_layers)
        skipped = self.experts_held - self.experts_per_token
        return (self.param_count()
                - skipped * self._expert_params(self.d_ff) * moe_layers)


# --------------------------------------------------------------------------
_REGISTRY: Dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    if cfg.name in _REGISTRY:
        raise ValueError(f"duplicate arch {cfg.name}")
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_arch(name: str) -> ArchConfig:
    from repro import configs  # noqa: F401  (triggers per-arch module imports)
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_archs() -> List[str]:
    from repro import configs  # noqa: F401
    return sorted(_REGISTRY)


def reduced(cfg: ArchConfig) -> ArchConfig:
    """Family-preserving tiny variant for CPU smoke tests."""
    n_q = min(cfg.num_heads, 4) if cfg.num_heads else 0
    n_kv = 0
    if n_q:
        n_kv = max(1, min(cfg.num_kv_heads, 2))
        while n_q % n_kv:
            n_kv -= 1
    # an interleaved model keeps each kind of layer once, in pattern order
    pattern = "".join(dict.fromkeys(cfg.layer_pattern))
    updates = dict(
        name=cfg.name + "-smoke",
        num_layers=len(pattern) if pattern else min(cfg.num_layers, 4),
        layer_pattern=pattern,
        d_model=128,
        num_heads=n_q,
        num_kv_heads=n_kv,
        head_dim=32 if n_q else 0,
        d_ff=(64 if cfg.is_moe else 256) if cfg.d_ff else 0,
        vocab_size=512,
        num_experts=min(cfg.num_experts, 4),
        experts_per_token=min(cfg.experts_per_token, 2),
        moe_experts_held=min(cfg.moe_experts_held, 4),
        shared_expert_ff=96 if cfg.shared_expert_ff else 0,
        ssm_state=min(cfg.ssm_state, 16),
        ssm_headdim=32 if cfg.ssm_state else 64,
        ssm_num_heads=min(cfg.ssm_num_heads, 8),
        ssm_groups=min(cfg.ssm_groups, 2),
        ssm_chunk=32,
        num_meta_tokens=min(cfg.num_meta_tokens, 8),
        cross_attn_every=2 if cfg.cross_attn_every else 0,
        num_image_tokens=min(cfg.num_image_tokens, 16) if cfg.num_image_tokens else 0,
        window_size=min(cfg.window_size, 16),
        dtype="float32",
    )
    return dataclasses.replace(cfg, **updates)
