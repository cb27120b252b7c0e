"""Operations and bytes the algorithm needs, from shapes alone.

Matrix products only (a multiply-add is two operations); elementwise
work, softmax, norms and embedding look-ups are left out, as model-FLOP
utilization conventionally does.  Nothing recomputed is counted in a
model's total; a kernel's own count is what that kernel has to compute.
"""


# ---------------------------------------------------------------- encoders

def encoder_forward_flops_per_token(cfg, seq):
    """One token's forward pass through a BERT-style encoder with an MLM
    projection over every position, at sequence length ``seq`` (full,
    unmasked attention: every token meets ``seq`` keys)."""
    C, F = cfg["hidden_size"], cfg["intermediate_size"]
    projections = 2 * (3 * C * C + C * C)       # fused QKV, output
    mlp = 2 * (C * F + F * C)
    attention = 2 * seq * C + 2 * seq * C       # QK^T and AV, all heads
    head = 2 * C * cfg["vocab_size"]
    return cfg["num_hidden_layers"] * (projections + mlp + attention) + head


def encoder_train_flops_per_token(cfg, seq):
    """Forward plus backward (twice the forward's products: one for the
    input's gradient, one for the weight's), no recomputation."""
    return 3 * encoder_forward_flops_per_token(cfg, seq)


# ---------------------------------------------------------------- decoders

def decoder_layer_flops_per_token(cfg, context):
    """One token's forward pass through one pre-norm decoder layer
    (grouped-query attention, gated MLP) that attends to ``context``
    cached positions."""
    C, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = cfg.get("head_dim", C // H)
    projections = 2 * C * (H + 2 * KV) * D + 2 * H * D * C
    mlp = 3 * 2 * C * F                          # gate, up, down
    attention = 2 * context * H * D + 2 * context * H * D
    return projections + mlp + attention


def decoder_prefill_flops(cfg, prompt):
    """Every layer's forward pass over a prompt of ``prompt`` tokens,
    causal: token p meets p keys (itself included).  The head is not in
    it: only the last position's logits are needed."""
    return cfg["num_hidden_layers"] * sum(
        decoder_layer_flops_per_token(cfg, p) for p in (1, prompt)
    ) * prompt / 2


def decoder_head_flops_per_token(cfg):
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


# ----------------------------------------------------------------- kernels

def flash_attention_cost(kernel, batch_heads, seq_q, seq_k, head_dim,
                         itemsize, causal=False):
    """(operations, bytes) one call of a flash-attention kernel needs.

    ``fwd``: S = QK^T, O = PV.  ``dq``: S again (the algorithm keeps no
    T x T matrix), dP = dO V^T, dQ = dS K.  ``dkv``: S again, dV = P^T dO,
    dP = dO V^T, dK = dS^T Q.  Bytes: each operand read once and each
    result written once (q, k, v, o, do, dq, dk, dv of T x D; the
    per-row log-sum-exp and delta vectors in float32)."""
    products = {"fwd": 2, "dq": 3, "dkv": 4}[kernel]
    pairs = seq_q * seq_k * (0.5 if causal else 1.0)
    ops = batch_heads * products * 2 * pairs * head_dim
    q, k = seq_q * head_dim * itemsize, seq_k * head_dim * itemsize
    rows = seq_q * 4
    moved = {
        "fwd": q + 2 * k + q + rows,                 # q k v -> o, lse
        "dq": q + 2 * k + q + 2 * rows + q,          # q k v do lse delta -> dq
        "dkv": q + 2 * k + q + 2 * rows + 2 * k,     # ... -> dk, dv
    }[kernel]
    return ops, batch_heads * moved


def least_time(ops, moved, peaks):
    """The roofline bound: the chip can do neither faster."""
    return max(ops / peaks["bf16_flops"], moved / peaks["hbm_bytes_per_s"])
