"""The work an algorithm needs, computed from a configuration's sizes.

These are the yardstick's counts, not the program's: FLOPs per trained
token of a Mamba2 language model and the bytes any implementation of the
DASHA / DASHA-MVR node update has to move.  Utilisation and roofline
shares divide them by measured time, so they can only rise when an
implementation does less redundant work, and can never pass 100%.
"""
from __future__ import annotations

from typing import Dict

#: oracle (gradient) calls per node per step: DASHA evaluates the gradient
#: at x^{t+1}; DASHA-MVR at x^{t+1} and x^t on the same batch
ORACLE_CALLS = {"dasha": 1, "mvr": 2}

#: bytes per element of the per-node state that the node update must move
#: (f32 gradient and state).  DASHA reads grad, h_i and g_i and writes g_i;
#: h_i <- grad can alias the gradient and the mask can be drawn on the chip,
#: so neither costs a byte.  MVR also reads the old gradient and writes the
#: new h_i.
UPDATE_BYTES_PER_ELEM = {"dasha": 4 * 4, "mvr": 6 * 4}


def _mamba2_sizes(m: Dict) -> Dict[str, int]:
    d = int(m["d_model"])
    inner = int(m["expand"]) * d
    p = int(m["headdim"])
    return {"d": d, "L": int(m["n_layer"]), "V": int(m["vocab_size"]),
            "H": inner // p, "P": p, "N": int(m["d_state"]),
            "G": int(m.get("ngroups", 1)), "W": int(m["d_conv"]),
            "Q": int(m["chunk_size"]), "inner": inner}


def mamba2_matmul_params(m: Dict) -> int:
    """Weights a token multiplies by: each layer's input projection (z, xBC
    and dt) and output projection, and the tied LM head over the published
    vocabulary.  The embedding gather, norms and the depthwise conv are not
    matmuls."""
    s = _mamba2_sizes(m)
    conv_dim = s["inner"] + 2 * s["G"] * s["N"]
    in_proj = s["d"] * (s["inner"] + conv_dim + s["H"])
    out_proj = s["inner"] * s["d"]
    return s["L"] * (in_proj + out_proj) + s["d"] * s["V"]


def mamba2_params(m: Dict) -> int:
    """Every trained parameter of the model at the published vocabulary."""
    s = _mamba2_sizes(m)
    conv_dim = s["inner"] + 2 * s["G"] * s["N"]
    per_layer = (s["d"]                                  # pre-norm
                 + s["d"] * (s["inner"] + conv_dim + s["H"])  # in_proj
                 + s["W"] * conv_dim + conv_dim          # conv weight, bias
                 + 3 * s["H"]                            # dt_bias, A_log, D
                 + s["inner"]                            # gated norm
                 + s["inner"] * s["d"])                  # out_proj
    return s["L"] * per_layer + s["V"] * s["d"] + s["d"]


def ssd_forward_flops_per_token(m: Dict) -> int:
    """Matmul FLOPs of one layer's chunked SSD scan per token, at chunk Q:
    the chunk's C B^T scores (per group), the diagonal block's
    (L o scores) x product, the chunk state B^T x and the off-diagonal
    C h product (per head).  Whole Q x Q blocks, as the chunked algorithm
    computes them."""
    s = _mamba2_sizes(m)
    macs = (s["G"] * s["Q"] * s["N"]
            + s["H"] * (s["Q"] * s["P"] + 2 * s["N"] * s["P"]))
    return 2 * macs


def mamba2_train_flops_per_token(m: Dict) -> int:
    """Forward and backward FLOPs of one gradient evaluation per token:
    3 x the forward (6 x the matmul parameters plus the SSD scans).  A
    recomputed forward (rematerialisation) is not counted."""
    fwd = 2 * mamba2_matmul_params(m) \
        + int(m["n_layer"]) * ssd_forward_flops_per_token(m)
    return 3 * fwd


def train_step_flops(m: Dict, variant: str, tokens_per_step: int) -> int:
    """Model FLOPs of one DASHA step over all nodes."""
    return mamba2_train_flops_per_token(m) * tokens_per_step \
        * ORACLE_CALLS[variant]


def node_update_min_bytes(m: Dict, variant: str, nodes: int) -> int:
    """Bytes the node update of one step must move over all nodes."""
    return mamba2_params(m) * nodes * UPDATE_BYTES_PER_ELEM[variant]
