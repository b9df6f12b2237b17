"""Operations and bytes that MiniCPM-SALA's layers need, counted from the
configuration's shapes (``configs/minicpm_sala.json``) and the traffic. No
number here comes from the compiler's cost analysis or from what the
program moves: the byte counts are the least that the work needs, so a
share of the roofline computed from them cannot pass 100 %."""

LINEAR, SPARSE = "lightning-attn", "minicpm4"


def layer_params(cfg, kind):
    """Weights of one layer that multiply activations: q, k, v, o, the
    output gate and the three feed-forward matrices (norms do no products)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    if kind == LINEAR:
        h = kv = cfg["lightning_nh"] * cfg["lightning_head_dim"]
    else:
        h = cfg["num_attention_heads"] * cfg["head_dim"]
        kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 3 * d * h + 2 * d * kv + 3 * d * f


def param_count(cfg):
    """All parameters held: the layers, the embedding and the untied head."""
    return (sum(layer_params(cfg, k) for k in cfg["mixer_types"])
            + 2 * cfg["vocab_size"] * cfg["hidden_size"])


def positions_read(cfg, context):
    """Positions whose K and V a sparse layer reads for one query with
    ``context`` positions before and including it: all of them up to
    ``dense_len``, beyond it the selected blocks (initial, local, top-k)."""
    sp = cfg["sparse_attention"]
    if context <= sp["dense_len"]:
        return context
    blocks = sp["init_blocks"] + sp["window_size"] // sp["block_size"] + 1 \
        + sp["topk"]
    return min(context, blocks * sp["block_size"])


def mixer_flops(cfg, kind, context):
    """Forward FLOPs of one layer's mixer for one position beyond its
    projections, 2 a multiply-add. Lightning: the state's update ``k^T v``
    and the read ``q S``. Sparse: ``q k^T`` and ``p v`` over the positions
    read, and the scores against the compressed keys seen."""
    if kind == LINEAR:
        n, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
        return 2 * 2 * n * d * d
    n, d = cfg["num_attention_heads"], cfg["head_dim"]
    sp = cfg["sparse_attention"]
    flops = 2 * 2 * n * d * positions_read(cfg, context)
    if context > sp["dense_len"]:
        flops += 2 * n * d * (context // sp["kernel_stride"])
    return flops


def forward_flops(cfg, positions, context, with_head):
    """Forward FLOPs of ``positions`` new positions whose mixers see
    ``context`` positions each on average; ``with_head`` adds the product
    with the output head."""
    each = sum(2 * layer_params(cfg, k) + mixer_flops(cfg, k, int(context))
               for k in cfg["mixer_types"])
    if with_head:
        each += 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return positions * each


def linear_state_bytes(cfg):
    """The least a decode step moves for one live stream in one lightning
    layer: its float32 state read once and written once."""
    n, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    return 2 * 4 * n * d * d


def sparse_read_bytes(cfg, context, itemsize=2):
    """The least a decode step reads for one live stream in one sparse
    layer: K and V of the positions read, one key/value head's columns a
    selection, and the compressed keys it can see, once each."""
    kv, d = cfg["num_key_value_heads"], cfg["head_dim"]
    sp = cfg["sparse_attention"]
    nbytes = 2 * positions_read(cfg, context) * kv * d * itemsize
    if context > sp["dense_len"]:
        nbytes += (context // sp["kernel_stride"]) * kv * d * itemsize
    return nbytes


def count(cfg, kind):
    return sum(1 for k in cfg["mixer_types"] if k == kind)
