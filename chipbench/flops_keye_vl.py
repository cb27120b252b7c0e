"""Operations a Keye-VL-2.0 language-model training step needs, from
shapes alone (``flops.py``'s rules: matrix products only, a multiply-add
is two operations, nothing recomputed is in a model's total), and what
one call of each kernel of its attention needs: the attention over the
kept keys, and the indexer's scores, their backward and the loss's pass
over the heads' probabilities.

A kernel is priced at what the model's equations need of it at the
model's shapes — the kept pairs ``sum_t min(t + 1, topk)`` for what
follows the selection, the causal pairs for what precedes it — whatever
the implementation visits: a kernel that masks a dense walk reads low
and one that skips what is not kept gains.
"""


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def kept_pairs(seq, topk):
    """(query, key) pairs a sequence's selection keeps, ties aside:
    query t keeps min(t + 1, topk) of its t + 1 causal keys."""
    short = min(seq, topk)
    return short * (short + 1) // 2 + (seq - short) * topk


def attention_flops_per_token(cfg, seq, kept_pairs_per_token=None):
    """(forward products that the backward doubles, forward products it
    repeats once) of one token in one attention layer.  Doubled: the
    projections of q, k, v and the output, Q K^T and P V over the kept
    pairs (``kept_pairs_per_token``: what the program's counters say a
    query kept; by default the shapes' ``kept_pairs / seq``), and the
    indexer's scores over the causal pairs.  Repeated once: the
    indexer's three projections, whose input is detached — their
    backward is the weight's gradient alone.  The loss's second pass
    over the heads' probabilities forms again what attention formed and
    is in no total; the rotations and norms are no products."""
    C, H, G, D = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"], cfg["head_dim"])
    sa = cfg["sa_config"]
    Hi, d = sa["indexer_num_heads"], sa["indexer_head_dim"]
    if kept_pairs_per_token is None:
        kept_pairs_per_token = kept_pairs(seq, sa["topk"]) / seq
    doubled = 2 * C * (H + 2 * G) * D + 2 * H * D * C \
        + 2 * 2 * H * D * kept_pairs_per_token \
        + 2 * Hi * d * causal_pairs(seq) / seq
    return doubled, 2 * C * (Hi * d + d + Hi)


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
                          kept_pairs_per_token=None):
    """Forward plus backward, no recomputation; the embedding look-ups
    are no product.  A program that drops the selection counts every
    causal pair as kept, and its total says so."""
    doubled, once = attention_flops_per_token(cfg, seq,
                                              kept_pairs_per_token)
    layer = 3 * (doubled + expert_layer_flops_per_token(
        cfg, held_pairs_per_token)) + 2 * once
    return cfg["num_hidden_layers"] * layer \
        + 3 * 2 * cfg["hidden_size"] * cfg["vocab_size"]


# ----------------------------------------------------------------- kernels

def sparse_attention_cost(kernel, batch, cfg, seq, itemsize):
    """(operations, bytes) one call of an attention kernel needs over
    the kept pairs.  ``fwd``: S = Q K^T and O = P V.  ``bwd``: S again,
    dP = dO V^T, dV = P^T dO, dK = dS^T Q, dQ = dS K.  Bytes: q, o (and
    dO, dQ) at the query heads, k, v (and dK, dV) at the key-value
    heads, each once; lse and delta a float32 a row.  The selection's
    scores are not in it: a kernel that reads them pays for its mask."""
    H, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    pairs = kept_pairs(seq, cfg["sa_config"]["topk"])
    products = {"fwd": 2, "bwd": 5}[kernel]
    q, kv, rows = H * seq * D * itemsize, G * seq * D * itemsize, H * seq * 4
    moved = {"fwd": 2 * q + 2 * kv + rows,
             "bwd": 4 * q + 4 * kv + 2 * rows}[kernel]
    return batch * H * products * 2 * pairs * D, batch * moved


def indexer_cost(kernel, batch, cfg, seq, itemsize):
    """(operations, bytes) one call of an indexer kernel needs.
    ``scores_fwd``: the heads' qI kI^T over the causal pairs; it writes
    the scores of those pairs.  ``scores_bwd_q``: the products again and
    dqI (dw is a sum); ``scores_bwd_k``: dkI alone — the equations need
    the heads' products once for the whole backward, and the query's
    pass is charged them.  ``probs``: the main heads' Q K^T over the
    kept pairs, and the mean's kept pairs written."""
    sa = cfg["sa_config"]
    Hi, d = sa["indexer_num_heads"], sa["indexer_head_dim"]
    H, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    causal, kept = causal_pairs(seq), kept_pairs(seq, sa["topk"])
    index_in = (Hi * seq * d + seq * d + Hi * seq) * itemsize
    if kernel == "probs":
        ops = H * 2 * kept * D
        moved = (H + G) * seq * D * itemsize + H * seq * 4 + 2 * kept * 4
    else:
        products = {"scores_fwd": 1, "scores_bwd_q": 2,
                    "scores_bwd_k": 1}[kernel]
        ops = Hi * products * 2 * causal * d
        moved = causal * 4 + index_in + {
            "scores_fwd": 0, "scores_bwd_q": (Hi * seq * d + Hi * seq) * 4,
            "scores_bwd_k": seq * d * 4}[kernel]
    return batch * ops, batch * moved
