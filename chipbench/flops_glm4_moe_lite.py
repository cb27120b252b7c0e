"""Operations a GLM-4.7-Flash training step needs, from shapes alone
(``flops.py``'s rules: matrix products only, a multiply-add is two
operations, nothing recomputed is in a model's total).  The flash
kernels' own count is ``flops_kimi_linear.flash_cost``, which the
roofline reader uses for every latent-attention model.
"""


def attention_flops_per_token(cfg, seq):
    """One token's forward pass through a latent-attention mixer with a
    low-rank query at sequence length ``seq``, causal: a token meets
    (seq + 1) / 2 keys.  The rotation is no product."""
    C, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    qr, rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    projections = 2 * C * qr + 2 * qr * H * (dn + dr) \
        + 2 * C * (rank + dr) + 2 * rank * H * (dn + dv) + 2 * H * dv * C
    return projections + 2 * H * (dn + dr + dv) * (seq + 1) / 2


def expert_layer_flops_per_token(cfg, held_pairs_per_token=None):
    """The router over all experts, the shared experts, and the held
    experts at the pairs routed to them (``held_pairs_per_token``; by
    default what a uniform router sends: experts per token x held /
    all)."""
    C = cfg["hidden_size"]
    if held_pairs_per_token is None:
        held_pairs_per_token = cfg["num_experts_per_tok"] \
            * cfg["n_routed_experts"] / cfg["num_experts_total"]
    expert = 3 * 2 * C * cfg["moe_intermediate_size"]
    return 2 * C * cfg["num_experts_total"] \
        + (cfg["n_shared_experts"] + held_pairs_per_token) * expert


def mtp_flops_per_position(cfg, seq, held_pairs_per_token=None):
    """One position's forward pass through the prediction module: the
    projection of the concatenation, one decoder layer, the head."""
    C = cfg["hidden_size"]
    return 2 * 2 * C * C + attention_flops_per_token(cfg, seq) \
        + expert_layer_flops_per_token(cfg, held_pairs_per_token) \
        + 2 * C * cfg["vocab_size"]


def train_flops_per_token(cfg, seq, held_pairs_per_token=None,
                          mtp_positions_per_token=None):
    """Forward plus backward (twice the forward's products), no
    recomputation; the embedding look-ups are no product.  The module's
    products count at the share of positions that entered its loss
    (``mtp_positions_per_token``; by default (seq - 1) / seq)."""
    C = cfg["hidden_size"]
    forward = 2 * C * cfg["vocab_size"]                         # the head
    for i in range(cfg["num_hidden_layers"]):
        forward += attention_flops_per_token(cfg, seq)
        forward += 3 * 2 * C * cfg["intermediate_size"] \
            if i < cfg["first_k_dense_replace"] \
            else expert_layer_flops_per_token(cfg, held_pairs_per_token)
    if cfg["num_nextn_predict_layers"]:
        if mtp_positions_per_token is None:
            mtp_positions_per_token = (seq - 1) / seq
        forward += mtp_positions_per_token * mtp_flops_per_position(
            cfg, seq, held_pairs_per_token)
    return 3 * forward
