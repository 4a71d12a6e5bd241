"""The work a Nemotron-H chip share needs, computed from the benchmark
configuration's sizes (the published ``config.json`` keys).

Model FLOPs per trained token count the matmuls a token needs in each
layer the chip holds: the Mamba-2 projections and its chunked SSD scan,
the router, the expected share of routed-expert work that lands on the
held experts (k of the published experts chosen, ``n_routed_experts`` of
them held), the shared expert, the attention projections and its causal
core, and the untied head over the held vocabulary.  Utilisation divides
them by measured time, so it can only rise when an implementation does
less redundant work.
"""
from __future__ import annotations

from typing import Dict

from bench.counts import ORACLE_CALLS, UPDATE_BYTES_PER_ELEM
from bench.gen_nemotron_h import pattern


def _sizes(m: Dict) -> Dict[str, int]:
    H, P = int(m["mamba_num_heads"]), int(m["mamba_head_dim"])
    return {"d": int(m["hidden_size"]), "V": int(m["vocab_size"]),
            "H": H, "P": P, "inner": H * P, "G": int(m["n_groups"]),
            "N": int(m["ssm_state_size"]), "W": int(m["conv_kernel"]),
            "Q": int(m["chunk_size"]),
            "E": int(m["published_n_routed_experts"]),
            "Eh": int(m["n_routed_experts"]),
            "K": int(m["num_experts_per_tok"]),
            "f": int(m["moe_intermediate_size"]),
            "fs": int(m["moe_shared_expert_intermediate_size"]),
            "Ha": int(m["num_attention_heads"]),
            "Ga": int(m["num_key_value_heads"]), "hd": int(m["head_dim"])}


def layer_params(m: Dict) -> Dict[str, int]:
    """Trained parameters of one layer of each kind, as held here."""
    s = _sizes(m)
    d, conv = s["d"], s["inner"] + 2 * s["G"] * s["N"]
    mamba = (d + d * (s["inner"] + conv + s["H"]) + s["W"] * conv + conv
             + 3 * s["H"] + s["inner"] + s["inner"] * d)
    moe = d + d * s["E"] + s["Eh"] * 2 * d * s["f"] + 2 * d * s["fs"]
    attn = d + d * (s["Ha"] + 2 * s["Ga"]) * s["hd"] + s["Ha"] * s["hd"] * d
    return {"M": mamba, "E": moe, "*": attn}


def params(m: Dict) -> int:
    """Every trained parameter held here: the layers, the embedding, the
    final norm and the head over the held vocabulary."""
    per = layer_params(m)
    s = _sizes(m)
    return sum(per[c] for c in pattern(m)) + 2 * s["V"] * s["d"] + s["d"]


def forward_flops_per_token(m: Dict, seq: int) -> int:
    """Matmul FLOPs of one forward pass per token at sequence length
    ``seq``.  The SSD scan counts whole Q x Q chunk blocks (the chunk's
    C B^T per group, (L o scores) x, B^T x and C h per head); causal
    attention counts the S/2 keys a query sees on average, for QK^T and
    PV."""
    s = _sizes(m)
    d, conv = s["d"], s["inner"] + 2 * s["G"] * s["N"]
    ssd = s["G"] * s["Q"] * s["N"] + s["H"] * (s["Q"] * s["P"]
                                               + 2 * s["N"] * s["P"])
    macs = {"M": d * (s["inner"] + conv + s["H"]) + s["inner"] * d + ssd,
            "E": d * s["E"] + s["K"] * s["Eh"] * 2 * d * s["f"] // s["E"]
            + 2 * d * s["fs"],
            "*": d * (s["Ha"] + 2 * s["Ga"]) * s["hd"] + s["Ha"] * s["hd"] * d
            + seq * s["Ha"] * s["hd"]}
    return 2 * (sum(macs[c] for c in pattern(m)) + d * s["V"])


def train_step_flops(m: Dict, variant: str, seq: int,
                     tokens_per_step: int) -> int:
    """Model FLOPs of one DASHA step over all nodes: 3 x the forward per
    token (a recomputed forward is not counted) per oracle call."""
    return 3 * forward_flops_per_token(m, seq) * tokens_per_step \
        * ORACLE_CALLS[variant]


def node_update_min_bytes(m: Dict, variant: str, nodes: int) -> int:
    """Bytes the node update of one step must move over all nodes."""
    return params(m) * nodes * UPDATE_BYTES_PER_ELEM[variant]


def expected_held_rows(m: Dict, tokens_per_step: int) -> float:
    """(token, choice) rows a uniform router sends to the held experts in
    one MoE layer: k of the published experts a token, the held ones of
    them."""
    s = _sizes(m)
    return tokens_per_step * s["K"] * s["Eh"] / s["E"]


def held_expert_flops(m: Dict, variant: str, tokens_per_step: int) -> int:
    """Matmul FLOPs of the held experts' grouped products in one step at
    the expected load: per MoE layer and oracle call, the forward's two
    products (up, down) and, for each, the backward's input and weight
    gradients, over :func:`expected_held_rows`; a recomputed forward is not
    counted.  The products compute the rows actually routed, which differ
    with the seed's weights."""
    s = _sizes(m)
    rows = expected_held_rows(m, tokens_per_step)
    per_layer = 6 * 2 * rows * s["d"] * s["f"]
    return int(pattern(m).count("E") * per_layer * ORACLE_CALLS[variant])

