"""Operations and bytes the algorithm needs, computed from shapes, and
the table of peaks. Kept with the benchmark so that no later PR can
move the yardstick. Compiler cost estimates are not used.
"""

import json
import os


def peaks_for(device_kind):
    from benchmark import harness
    with open(os.path.join(harness.HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in "
            "benchmark/peaks.json; a device that is not in the table is "
            "an error, not a default")
    return table[device_kind]


def param_count(sizes):
    """Parameters of a GPT-2 of `sizes`, embeddings included (the
    head is tied, so it adds none)."""
    L, H = sizes["n_layer"], sizes["n_embd"]
    per_layer = 12 * H * H + 13 * H          # 4 matmuls, biases, 2 LN
    return L * per_layer + (sizes["vocab_size"] + sizes["n_positions"]) \
        * H + 2 * H


def train_flops_per_token(sizes):
    """6 N: forward and backward through every parameter once.
    Attention's own T-dependent operations and every recomputed
    operation are left out, so the utilisation read from this is a
    floor."""
    return 6 * param_count(sizes)


def flash_causal_cost(batch, heads, seq, head_dim, bytes_per_el=2):
    """(flops, bytes) of one causal flash-attention forward and one
    backward over [batch, seq, heads, head_dim]. Causal: half the
    square is computed. Forward: QK^T and PV (2 matmuls); backward:
    the scores again, dV, dP, dQ, dK (5). Bytes: q, k, v, o read or
    written once each way, plus the float32 log-sum-exp; the padding
    of lanes is not counted as useful."""
    square = batch * heads * seq * seq * head_dim      # one full matmul
    tensor = batch * heads * seq * head_dim * bytes_per_el
    lse = batch * heads * seq * 4
    fwd = (2 * 2 * square // 2, 4 * tensor + lse)
    bwd = (5 * 2 * square // 2, 8 * tensor + 2 * lse)
    return {"fwd": fwd, "bwd": bwd}


def roofline_seconds(flops, nbytes, peaks):
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
