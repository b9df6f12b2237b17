"""Operations and bytes that SmallThinker's layers need, counted from the
configuration's shapes (``configs/smallthinker_21b.json``), the traffic and
the program's routing counters. No number here comes from the compiler's
cost analysis or from what the program moves: the counts are the least that
the work needs, so a share of the roofline computed from them cannot pass
100 %."""


def attention_params(cfg):
    """q, k, v and o of one layer."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 2 * d * h + 2 * d * kv


def expert_params(cfg):
    """The three matrices of one expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def layer_params(cfg):
    """All weights of one layer: attention, router, every expert."""
    return attention_params(cfg) \
        + cfg["hidden_size"] * cfg["moe_num_primary_experts"] \
        + cfg["moe_num_primary_experts"] * expert_params(cfg)


def param_count(cfg):
    """All parameters held: the layers, the embedding and the untied head."""
    return len(cfg["sliding_window_layout"]) * layer_params(cfg) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"]


def keys_seen(cfg, window_layer, context):
    """Keys that one query with ``context`` positions before and including
    it sees: all of them in a full layer, the window's at most in a window
    layer."""
    return min(context, cfg["sliding_window_size"]) if window_layer \
        else context


def position_flops(cfg, context):
    """Forward FLOPs of one position whose attention sees ``context``
    positions, all layers, 2 a multiply-add: attention's four products, the
    router, the experts a token really goes to (6 of 64), ``q k^T`` and
    ``p v`` over the keys seen."""
    n, d = cfg["num_attention_heads"], cfg["head_dim"]
    active = cfg["moe_num_active_primary_experts"]
    dense = 2 * (attention_params(cfg)
                 + cfg["hidden_size"] * cfg["moe_num_primary_experts"]
                 + active * expert_params(cfg))
    return sum(dense + 2 * 2 * n * d * keys_seen(cfg, w, int(context))
               for w in cfg["sliding_window_layout"])


def forward_flops(cfg, positions, context, with_head):
    """Forward FLOPs of ``positions`` new positions whose attention sees
    ``context`` positions each on average; ``with_head`` adds the product
    with the output head."""
    each = position_flops(cfg, context)
    if with_head:
        each += 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return positions * each


def experts_least_seconds(cfg, touched, assignments, peaks, itemsize=2):
    """The least time one layer's experts need in one step: the ``touched``
    experts' three matrices read once (bytes over the HBM peak) or the
    ``assignments``' products (FLOPs over the bf16 peak), whichever is the
    longer."""
    nbytes = touched * expert_params(cfg) * itemsize
    flops = assignments * 2 * expert_params(cfg)
    return max(nbytes / peaks["hbm_bytes_per_s"], flops / peaks["bf16_flops"])


def kv_read_bytes(cfg, context, itemsize=2):
    """The least a decode step reads for one live stream, all layers: K and
    V of the keys its query sees, once a layer."""
    row = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize
    return sum(row * keys_seen(cfg, w, context)
               for w in cfg["sliding_window_layout"])
