"""Operations and bytes that the algorithms need, counted from shapes. No
number here comes from the compiler's cost analysis or from a run."""


def encoder_block_params(hidden, intermediate):
    """Weights of one transformer block that multiply activations (q, k, v,
    o and the two feed-forward matrices); biases and norms do no products."""
    return 4 * hidden * hidden + 2 * hidden * intermediate


def attention_flops(seq_q, seq_k, hidden, causal=False):
    """Forward products of one attention layer for ``seq_q`` queries against
    ``seq_k`` keys, all heads: ``q k^T`` and ``p v``, 2 FLOPs a
    multiply-add. A causal square does half of them."""
    flops = 2 * 2 * seq_q * seq_k * hidden
    return flops // 2 if causal else flops


def bert_train_flops_per_token(cfg, seq):
    """Forward and backward FLOPs a trained position: 6 a parameter that
    multiplies it, plus attention over ``seq`` keys three times (forward
    and the two backward products of each forward product). Embedding
    look-ups, norms, the pooler and the head (one row a sequence) are left
    out: they are under a thousandth."""
    h, i, n = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_hidden_layers"])
    return n * (6 * encoder_block_params(h, i)
                + 3 * attention_flops(1, seq, h))


def lm_forward_flops(cfg, positions, context, with_head):
    """Forward FLOPs of ``positions`` new positions of a decoder whose
    attention reads ``context`` keys a position on average; ``with_head``
    adds the product with the tied embedding."""
    h, i, n = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    flops = positions * n * (2 * encoder_block_params(h, i)
                             + attention_flops(1, context, h))
    if with_head:
        flops += positions * 2 * h * cfg["vocab_size"]
    return flops


def lm_param_bytes(cfg, itemsize=4):
    h, i, n = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    block = encoder_block_params(h, i) + 9 * h + i  # biases, two norms
    return itemsize * (n * block + cfg["vocab_size"] * h
                       + cfg["n_positions"] * h + 2 * h)


def decode_step_bytes(cfg, live_positions, kv_itemsize=4):
    """Bytes one decode step has to read: every parameter once, and K and V
    of the live positions of all streams, in every block."""
    kv = 2 * cfg["n_layer"] * live_positions * cfg["n_embd"] * kv_itemsize
    return lm_param_bytes(cfg) + kv


def short_attention_cost(batch, heads, seq, head_dim, itemsize, backward,
                         causal=False):
    """FLOPs and bytes of one call of the fused short-sequence attention
    kernel over ``[batch, heads, seq, head_dim]``. Forward: ``q k^T`` and
    ``p v``; reads q, k, v, writes o. Backward: five products of that size
    (the scores again, dv, dp, dq, dk); reads q, k, v, o, do, writes dq, dk,
    dv. The score matrix never leaves the chip's fast memory."""
    product = 2 * batch * heads * seq * seq * head_dim
    if causal:
        product //= 2
    tensor = batch * heads * seq * head_dim * itemsize
    if backward:
        return 5 * product, 8 * tensor
    return 2 * product, 4 * tensor


def roofline_seconds(flops, nbytes, peaks):
    """The least time the chip could take, and which peak sets it."""
    compute, memory = flops / peaks["bf16_flops"], \
        nbytes / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
