"""Operations and bytes that the algorithm REQUIRES, from shapes alone.

Kept with the benchmark so that no later PR can move the yardstick.
Recomputed operations (remat, the flash backward's second look at
Q K^T) are never counted: a kernel that recomputes more reads a lower
share, not a higher one.
"""


def matmul_params(arch):
    """Parameters that a token multiplies: every projection and the
    head (the embedding lookup and the position table multiply
    nothing; the tied head is counted once, as the head)."""
    d, H, Hkv, D = (arch["hidden_size"], arch["num_heads"],
                    arch["num_kv_heads"], arch["head_dim"])
    m = arch["mlp_hidden"]
    attn = d * (H + 2 * Hkv) * D + H * D * d
    mlp = 3 * d * m if arch["mlp"] == "swiglu" else 2 * d * m
    return arch["num_layers"] * (attn + mlp) + arch["vocab_size"] * d


def attention_flops_fwd(arch, seq_len):
    """Causal attention, forward, one sequence, all layers: Q K^T and
    P V are 2 * S^2 * D flops per head each, halved by causality."""
    per_layer = 2 * (2 * seq_len * seq_len * arch["head_dim"]
                     * arch["num_heads"]) / 2
    return arch["num_layers"] * per_layer


def train_flops_per_token(arch, seq_len):
    """Forward + backward, per token of a [*, seq_len] batch:
    6 flops per matmul parameter, and 3x the forward's attention."""
    return (6 * matmul_params(arch)
            + 3 * attention_flops_fwd(arch, seq_len) / seq_len)


def flash_fwd(batch, seq_len, heads, kv_heads, head_dim, bytes_per=2):
    """(flops, bytes) one causal flash forward call needs: two matmuls
    over the lower triangle; q, k, v read and o written once."""
    flops = 2 * (2 * batch * heads * seq_len * seq_len * head_dim) / 2
    byts = (batch * seq_len * head_dim * bytes_per
            * (2 * heads + 2 * kv_heads))
    return flops, byts


def flash_bwd(batch, seq_len, heads, kv_heads, head_dim, bytes_per=2):
    """(flops, bytes) the backward needs: four matmuls over the lower
    triangle (dV, dP, dQ, dK; the recomputation of P is not required
    work); q, k, v, o, do read, dq, dk, dv written."""
    flops = 4 * (2 * batch * heads * seq_len * seq_len * head_dim) / 2
    byts = (batch * seq_len * head_dim * bytes_per
            * (4 * heads + 4 * kv_heads))
    return flops, byts


def roofline_seconds(flops, byts, peaks):
    """The least time the chip could take, and which peak binds."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = byts / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")


def decode_tick_bytes(arch, lanes, mean_context, weight_bytes=2,
                      kv_bytes=2):
    """Bytes one decode tick must read: every matmul weight once, and
    each live lane's keys and values up to its context."""
    kv = (2 * arch["num_layers"] * arch["num_kv_heads"]
          * arch["head_dim"] * kv_bytes)
    return matmul_params(arch) * weight_bytes + lanes * mean_context * kv
