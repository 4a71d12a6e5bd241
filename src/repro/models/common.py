"""Shared model configuration + small building blocks.

One ``ArchConfig`` dataclass covers all 10 assigned architectures; per-arch
files in :mod:`repro.configs` instantiate it with the exact assigned numbers.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


def pad_to(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    arch_type: str               # dense | moe | ssm | hybrid | vlm | audio
                                 # | pattern
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    source: str = ""             # citation bracket from the assignment
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    mlp_type: str = "swiglu"     # swiglu | gelu | geglu | relu2
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"

    # --- MoE ---------------------------------------------------------------
    num_experts: int = 0
    experts_per_token: int = 0
    num_shared_experts: int = 0
    capacity_factor: float = 1.25
    moe_dispatch: str = "gather"   # gather | einsum (see moe.moe_ffn)
    moe_chunk: int = 4096          # tokens per einsum-dispatch group
    # the held-expert layer of a ``pattern`` stack (moe.moe_held)
    routed_scale: float = 1.0      # routed weights x this after top-k
    shared_expert_ff: int = 0      # width of one non-gated shared expert
    experts_held: int = 0          # experts this chip holds (0 = all)
    expert_first: int = 0          # global id of the first held expert

    # --- MLA (DeepSeek-V2) ---------------------------------------------------
    use_mla: bool = False
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    # --- SSM (Mamba2 / SSD) --------------------------------------------------
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_ngroups: int = 1
    ssm_heads: int = 0             # explicit head count (0 = d_inner / P)
    conv_width: int = 4
    ssd_chunk: int = 256
    use_ssd_kernel: bool = False   # Pallas ssd_chunk path (TPU deploy)

    # --- attention pattern -----------------------------------------------
    sliding_window: int = 0        # 0 = full attention everywhere
    global_every: int = 0          # gemma3: 1 global layer per `global_every`
    hybrid_attn_every: int = 0     # zamba2: shared attn block every k layers
    attn_logit_softcap: float = 0.0
    use_rope: bool = True

    # --- pattern stack (nemotron-h): one letter per layer ------------------
    # M = Mamba2, E = held-expert MoE, * = attention; the first num_layers
    # letters are the layers (a depth cut keeps a prefix)
    layer_pattern: str = ""

    # --- VLM ----------------------------------------------------------------
    cross_attn_every: int = 0      # llama-3.2-vision: cross-attn each k layers
    num_image_tokens: int = 0

    # --- encoder-decoder (whisper) -----------------------------------------
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    num_audio_frames: int = 0

    # ------------------------------------------------------------------
    def __post_init__(self):
        if self.head_dim is None and self.num_heads:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.num_heads)

    @property
    def jax_dtype(self):
        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[self.dtype]

    @property
    def padded_vocab(self) -> int:
        return pad_to(self.vocab_size, 256)

    @property
    def d_inner(self) -> int:          # SSM inner width
        if self.ssm_heads:
            return self.ssm_heads * self.ssm_headdim
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_headdim

    @property
    def pattern(self) -> str:
        """The letters of the layers this config holds."""
        return self.layer_pattern[:self.num_layers]

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def uses_attention(self) -> bool:
        return self.arch_type != "ssm"

    def param_count(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS = 6 N D)."""
        from repro.models.init import init_params  # noqa: cyclic-light
        import numpy as np
        shapes = jax.eval_shape(
            lambda: init_params(self, jax.random.PRNGKey(0)))
        return int(sum(np.prod(s.shape) for s in jax.tree_util.tree_leaves(shapes)))

    def active_param_count(self) -> int:
        """Active params per token (MoE: only routed-to experts count)."""
        total = self.param_count()
        if self.num_experts == 0:
            return total
        from repro.models.init import init_params
        import numpy as np
        shapes = jax.eval_shape(lambda: init_params(self, jax.random.PRNGKey(0)))
        leaves = jax.tree_util.tree_leaves_with_path(shapes)
        expert_total = sum(
            int(np.prod(l.shape)) for p, l in leaves
            if any("experts" == getattr(k, "key", None) for k in p))
        active_frac = self.experts_per_token / max(self.num_experts, 1)
        return int(total - expert_total + expert_total * active_frac)


# ---------------------------------------------------------------------------
# tiny building blocks
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.  x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]                       # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def relu2(x: jax.Array) -> jax.Array:
    """relu(x)^2 in f32, back in x's dtype."""
    return jnp.square(jax.nn.relu(x.astype(jnp.float32))).astype(x.dtype)


def mlp_apply(p: dict, x: jax.Array, mlp_type: str) -> jax.Array:
    if mlp_type == "relu2":
        return relu2(x @ p["w_in"]) @ p["w_out"]
    if mlp_type == "gelu":
        h = jax.nn.gelu(x @ p["w_in"] + p.get("b_in", 0.0))
        return h @ p["w_out"] + p.get("b_out", 0.0)
    gate = x @ p["w_gate"]
    act = jax.nn.gelu(gate, approximate=True) if mlp_type == "geglu" \
        else jax.nn.silu(gate)
    return (act * (x @ p["w_in"])) @ p["w_out"]


def softcap(logits: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return logits
    return cap * jnp.tanh(logits / cap)
