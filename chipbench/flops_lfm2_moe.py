"""Operations an LFM2 expert decoder's training step needs, from shapes
alone (``flops.py``'s rules: matrix products only, a multiply-add is two
operations, nothing recomputed is in a model's total), and what one call
of a flash-attention kernel needs at the model's grouped-query geometry.

A kernel is priced at what the model's equations need of it at the
model's shapes — every query head's products over the causal pairs, the
keys and values read once a key head — whatever implements it: a program
that hands the kernel keys repeated to the query heads reads them four
times over and reads low.
"""


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def conv_mixer_flops_per_position(cfg):
    """One position's forward pass through a gated short convolution
    mixer: the projection to three chunks and the projection out.  The
    gates and the taps are no matrix products."""
    C = cfg["hidden_size"]
    return 2 * C * 3 * C + 2 * C * C


def attention_flops_per_token(cfg, seq):
    """One token's forward pass through a grouped-query attention layer
    at sequence length ``seq``, causal: the projections of q, k, v and
    the output, Q K^T and P V over the causal pairs.  The head norms and
    the rotation are no products."""
    C, H, G, D = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"], head_dim(cfg))
    return 2 * C * (H + 2 * G) * D + 2 * H * D * C \
        + 2 * 2 * H * D * causal_pairs(seq) / seq


def expert_layer_flops_per_token(cfg, held_pairs_per_token=None):
    """The router over all experts and the held experts at the pairs
    routed to them (``held_pairs_per_token``; by default what a uniform
    router sends: experts per token x held / all).  No shared expert."""
    C = cfg["hidden_size"]
    if held_pairs_per_token is None:
        held_pairs_per_token = cfg["num_experts_per_tok"] \
            * cfg["num_experts"] / cfg["num_experts_total"]
    return 2 * C * cfg["num_experts_total"] \
        + held_pairs_per_token * 3 * 2 * C * cfg["moe_intermediate_size"]


def train_flops_per_token(cfg, seq, held_pairs_per_token=None,
                          conv_positions_per_token=None):
    """Forward plus backward (twice the forward's products), no
    recomputation; the embedding look-ups are no product, the shared
    embedding's product as the head is.  The convolution mixers count at
    the positions that went through them (``conv_positions_per_token``:
    what the program's ``conv.positions`` counted over the tokens
    trained; by default one for each ``"conv"`` layer): a program that
    drops the convolution counts none, and its total says so."""
    C = cfg["hidden_size"]
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    if conv_positions_per_token is None:
        conv_positions_per_token = kinds.count("conv")
    forward = 2 * C * cfg["vocab_size"]                         # the head
    forward += conv_positions_per_token * conv_mixer_flops_per_position(cfg)
    forward += kinds.count("full_attention") \
        * attention_flops_per_token(cfg, seq)
    for i in range(len(kinds)):
        forward += 3 * 2 * C * cfg["intermediate_size"] \
            if i < cfg["num_dense_layers"] \
            else expert_layer_flops_per_token(cfg, held_pairs_per_token)
    return 3 * forward


# ----------------------------------------------------------------- kernels

def flash_cost(kernel, batch, cfg, seq, itemsize):
    """(operations, bytes) one call of a flash-attention kernel needs at
    the model's geometry, causal.  ``fwd``: S = Q K^T and O = P V.
    ``bwd``, the one fused kernel: S again, dP = dO V^T, dV = P^T dO,
    dK = dS^T Q, dQ = dS K.  Operations: every query head's products
    over the causal pairs.  Bytes: q, o (and dO, dQ) at the query heads,
    k, v (and dK, dV) at the key heads, each once; lse and delta a
    float32 a row."""
    H, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               head_dim(cfg))
    products = {"fwd": 2, "bwd": 5}[kernel]
    q, kv, rows = H * seq * D * itemsize, G * seq * D * itemsize, H * seq * 4
    moved = {"fwd": 2 * q + 2 * kv + rows,
             "bwd": 4 * q + 4 * kv + 2 * rows}[kernel]
    return batch * H * products * 2 * causal_pairs(seq) * D, batch * moved
