"""NVIDIA-Nemotron-3-Nano-30B-A3B [pattern] — Mamba-2, MoE and attention
layers in one stack (https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16).

52 layers d_model=2688 in ``hybrid_override_pattern`` (23 Mamba-2, 23 MoE,
6 attention), untied head over vocab=131072.  Every layer is
``x + mixer(rmsnorm(x))``, the mixer chosen by the layer's letter:

* ``M`` Mamba-2: ``in_proj`` (no bias) gives z (4096), xBC (4096 + 2*8*128)
  and dt (64); a causal depthwise conv (width 4, with bias) and SiLU on
  xBC; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; chunked SSD
  (chunk 128) in which head h reads group h // 8's B and C; a D skip;
  ``y = rmsnorm_per_group_of_512(y * silu(z)) * w``, then ``out_proj``.
* ``E`` MoE: ``s = sigmoid(x W_r)`` over all 128 experts; the top 6 by
  ``s + b`` (b the score-correction bias, held at 0 and not trained);
  weights are the chosen s normalised to sum 1, times 2.5; the output is
  ``shared(x) + sum_{chosen e held here} w_e down_e(relu(up_e x)^2)`` with
  the shared expert ``down(relu(up x)^2)`` of width 3712.
* ``*`` attention: causal GQA, 32 query and 2 KV heads of 128, scale
  1/sqrt(128), no bias and no rotary embedding.

Then the final RMSNorm and the untied head.  Norm weights are ``(1 + w)``
as everywhere in the program.  ``launch/train.py`` cuts depth
(``--layers``), the experts held (``--experts``) and the vocabulary rows
(``--vocab``) to one chip's share.
"""
import dataclasses

from repro.models.common import ArchConfig

#: the published ``hybrid_override_pattern``
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

CONFIG = ArchConfig(
    name="nemotron-3-nano-30b-a3b",
    arch_type="pattern",
    num_layers=len(PATTERN),
    d_model=2688,
    num_heads=32,
    num_kv_heads=2,
    head_dim=128,
    d_ff=1856,
    vocab_size=131072,
    source="NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json",
    mlp_type="relu2",
    norm_eps=1e-5,
    tie_embeddings=False,
    use_rope=False,
    num_experts=128,
    experts_per_token=6,
    routed_scale=2.5,
    shared_expert_ff=3712,
    ssm_state=128,
    ssm_heads=64,
    ssm_headdim=64,
    ssm_ngroups=8,
    conv_width=4,
    ssd_chunk=128,
    layer_pattern=PATTERN,
)

SMOKE = dataclasses.replace(
    CONFIG, name="nemotron-h-smoke", num_layers=5, layer_pattern="MEM*E",
    d_model=128, num_heads=4, num_kv_heads=2, head_dim=32, d_ff=64,
    vocab_size=512, num_experts=16, experts_held=4, experts_per_token=3,
    shared_expert_ff=128, ssm_state=16, ssm_heads=8, ssm_headdim=32,
    ssm_ngroups=4, ssd_chunk=16)
