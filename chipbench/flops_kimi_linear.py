"""Operations and bytes a Kimi-Linear training step needs, from shapes
alone (``flops.py``'s rules: matrix products only, a multiply-add is two
operations, nothing recomputed is in a model's total, a kernel's own
count is what that kernel has to compute).
"""

from chipbench import harness


def layer_kinds(cfg):
    return harness.load_module("references", "kimi_linear").layer_kinds(cfg)


def kda_layer_flops_per_token(cfg):
    """One token's forward pass through a KDA mixer.  The recurrence is
    counted as the rule states it — per head k^T S, the rank-one write
    and S^T q, 2 K V each — not as the chunk algebra the program runs
    (which forms more products than that)."""
    C = cfg["hidden_size"]
    lin = cfg["linear_attn_config"]
    H, K = lin["num_heads"], lin["head_dim"]
    R = cfg["assumed_sizes"]["kda_gate_rank"]
    projections = 2 * C * H * K * 4                 # q, k, v, out
    gates = 2 * (2 * C * R + 2 * R * H * K) + 2 * C * H   # decay, gate, beta
    return projections + gates + 3 * 2 * K * K * H


def mla_layer_flops_per_token(cfg, seq):
    """One token's forward pass through a latent-attention mixer at
    sequence length ``seq``, causal: a token meets (seq + 1) / 2 keys."""
    C, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dn, dv, rank = (cfg["qk_nope_head_dim"], cfg["v_head_dim"],
                    cfg["kv_lora_rank"])
    projections = 2 * C * H * dk + 2 * C * (rank + cfg["qk_rope_head_dim"]) \
        + 2 * rank * H * (dn + dv) + 2 * H * dv * C
    return projections + 2 * H * (dk + dv) * (seq + 1) / 2


def ffn_flops_per_token(cfg, kind, held_pairs_per_token=None):
    """A dense gated MLP, or the expert layer: router over all experts,
    the shared experts, and the held experts at the pairs routed to them
    (``held_pairs_per_token``; by default what a uniform router sends:
    experts per token x held / all)."""
    C = cfg["hidden_size"]
    if kind == "dense":
        return 3 * 2 * C * cfg["intermediate_size"]
    if held_pairs_per_token is None:
        held_pairs_per_token = cfg["num_experts_per_token"] \
            * cfg["num_experts"] / cfg["num_experts_total"]
    expert = 3 * 2 * C * cfg["moe_intermediate_size"]
    return 2 * C * cfg["num_experts_total"] \
        + (cfg["num_shared_experts"] + held_pairs_per_token) * expert


def train_flops_per_token(cfg, seq, held_pairs_per_token=None):
    """Forward plus backward (twice the forward's products), no
    recomputation; the embedding look-up is no product."""
    forward = 2 * cfg["hidden_size"] * cfg["vocab_size"]        # the head
    for mixer, ffn in layer_kinds(cfg):
        forward += kda_layer_flops_per_token(cfg) if mixer == "kda" \
            else mla_layer_flops_per_token(cfg, seq)
        forward += ffn_flops_per_token(cfg, ffn, held_pairs_per_token)
    return 3 * forward


# ----------------------------------------------------------------- kernels

def flash_cost(kernel, batch_heads, seq, key_dim, value_dim, itemsize,
               causal=True):
    """(operations, bytes) of one call of a flash-attention kernel whose
    values are ``value_dim`` wide and keys ``key_dim``.  ``fwd``: S = Q K^T
    and O = P V.  ``bwd``, the one fused kernel: S again, dP = dO V^T,
    dV = P^T dO, dK = dS^T Q, dQ = dS K — five products.  Bytes: each
    operand read once, each result written once; lse and delta a float32
    a row."""
    pairs = seq * seq * (0.5 if causal else 1.0)
    widths = {"fwd": key_dim + value_dim,
              "bwd": 3 * key_dim + 2 * value_dim}[kernel]
    ops = batch_heads * 2 * pairs * widths
    qk, v = seq * key_dim * itemsize, seq * value_dim * itemsize
    rows = seq * 4
    moved = {"fwd": 2 * qk + 2 * v + rows,              # q k v -> o, lse
             "bwd": 2 * qk + 2 * v + 2 * rows + 2 * qk + v}[kernel]
    return ops, batch_heads * moved                     # .. do -> dq dk dv


def kda_state_cost(kernel, heads, seq, key_dim, value_dim, chunk):
    """(operations, bytes) of one call of a KDA state kernel over
    ``heads`` heads, float32.  Per chunk of C rows the forward forms
    W S, (Q e^G) S, M U and U^T Khat (6 C K V + 2 C^2 V); the backward
    forms U again and eight more products (14 C K V + 4 C^2 V).  ``fwd_states`` also writes
    every chunk's starting state."""
    C, K, V = chunk, key_dim, value_dim
    chunks = -(-seq // C)
    operands = 3 * C * K + C * V + C * C + K            # w qg khat u0 m gamma
    ops = {"fwd": 6 * C * K * V + 2 * C * C * V,
           "fwd_states": 6 * C * K * V + 2 * C * C * V,
           "bwd": 14 * C * K * V + 4 * C * C * V}[kernel]
    moved = {"fwd": operands + C * V,
             "fwd_states": operands + C * V + K * V,
             "bwd": 2 * operands + K * V + C * V}[kernel]
    return heads * chunks * ops, heads * chunks * moved * 4


def kda_chunk_cost(kernel, heads, seq, key_dim, value_dim, chunk):
    """(operations, bytes) of one call of a KDA chunk kernel over
    ``heads`` heads, float32.  Per chunk of C rows the forward forms q k^T
    once, per halving (log2 C of them) the partial sums of the log decay,
    two decayed products and the two products of the inverse's merge, and
    at the end two more sums and T (b K e^G), T (b V); the backward forms
    all of it again and twice more for the cotangents."""
    C, K, V = chunk, key_dim, value_dim
    chunks = -(-seq // C)
    levels = C.bit_length() - 1
    forward = 2 * C * C * K + levels * (3 * 2 * C * C * K + 2 * 2 * C ** 3) \
        + 3 * 2 * C * C * K + 2 * C * C * V
    rows = 4 * C * K + C * V                             # q k bk bv g
    operands = 3 * C * K + C * V + C * C + K
    ops = {"fwd": forward, "bwd": 3 * forward}[kernel]
    moved = {"fwd": rows + operands, "bwd": 2 * rows + operands}[kernel]
    return heads * chunks * ops, heads * chunks * moved * 4
