"""Operations and bytes that GLM-5.3-Flash's layers need, counted from the
configuration's shapes (``configs/glm_5_3_flash.json``), the traffic and the
program's counters. No number here comes from the compiler's cost analysis
or from what the program moves: the counts are the least that the work
needs, so a share of a peak computed from them cannot pass 100 %.

``flops_glm5`` counts the latent layer's products, the indexer's, the
router's and the experts'; here the layer kinds come from ``layer_types``
and ``mlp_layer_types``, the latent layer has no rotary part, its indexer
scores pooled blocks, and the linear layers (KDA) and the four residual
streams (mHC) are added."""
from . import flops_glm5 as G

expert_params = G.expert_params
expert_layers = G.expert_layers
experts_least_seconds = G.experts_least_seconds


def kda_layers(cfg):
    return sum(t == "linear_attention" for t in cfg["layer_types"])


def dsa_layers(cfg):
    return sum(t == "deepseek_sparse_attention" for t in cfg["layer_types"])


def dense_layers(cfg):
    return sum(t == "dense" for t in cfg["mlp_layer_types"])


def kda_width(cfg):
    lin = cfg["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"]


def kda_params(cfg):
    """The products of one KDA layer: q, k, v, o, the write strength and the
    low-rank decay and output gate (rank: the head size)."""
    lin, d = cfg["linear_attn_config"], cfg["hidden_size"]
    c, r = kda_width(cfg), lin["head_dim"]
    return 4 * d * c + d * lin["num_heads"] + 2 * (d * r + r * c)


def kda_position_flops(cfg):
    """The recurrence of one position of one KDA layer, 2 a multiply-add:
    the three products of a head's ``[D, D]`` state with a vector (the
    key's recall, the rank-one correction, the output) and the convolution
    of 4 taps over q, k and v. The least any form needs: a chunked form
    does more, to do it in parallel."""
    lin = cfg["linear_attn_config"]
    return 6 * lin["num_heads"] * lin["head_dim"] ** 2 \
        + 2 * lin["short_conv_kernel_size"] * 3 * kda_width(cfg)


def attention_params(cfg):
    """The latent layer's products: q_a, q_b, kv_a, kv_b, o (no rotary
    part: ``qk_rope_head_dim`` 0)."""
    return G.attention_params(cfg)


def indexer_params(cfg):
    return G.indexer_params(cfg)


def mhc_flops(cfg):
    """The two sublayers' coefficient products of one layer a position:
    ``n x hidden`` against ``n (n + 2)`` outputs each."""
    n = cfg["hc_mult"]
    return 2 * 2 * n * cfg["hidden_size"] * n * (n + 2)


def layer_params(cfg, mixer, ffn):
    """The matrices of one layer held here (the convolution's taps and the
    vectors, under 0.2 M a layer, left out)."""
    mix = kda_params(cfg) if mixer == "linear_attention" \
        else attention_params(cfg) + indexer_params(cfg)
    rest = G.dense_ffn_params(cfg) if ffn == "dense" else (
        G.router_params(cfg) + G.shared_params(cfg)
        + cfg["n_routed_experts"] * expert_params(cfg))
    n = cfg["hc_mult"]
    return mix + rest + 2 * n * cfg["hidden_size"] * n * (n + 2)


def param_count(cfg):
    """The matrices held: the layers', the embedding and the untied head
    over the rows of the vocabulary held."""
    return sum(layer_params(cfg, m, f) for m, f in
               zip(cfg["layer_types"], cfg["mlp_layer_types"])) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"]


def position_flops(cfg, context, assignments=None):
    """Forward FLOPs of one position whose query sees ``context``
    positions, all layers, 2 a multiply-add: the KDA layers' products and
    recurrence, the latent layers' products, the pooled index scores over
    the context's whole blocks and attention over the ``index_topk``
    positions read at most, each layer's mHC coefficients, the dense
    feed-forward or the router, the shared expert and ``assignments``
    routed experts (a layer; the expectation under a uniform router where
    none were counted)."""
    if assignments is None:
        assignments = G.expected_assignments(cfg)
    heads = cfg["num_attention_heads"]
    dsa = 2 * (attention_params(cfg) + indexer_params(cfg)) \
        + 2 * cfg["index_n_heads"] * cfg["index_head_dim"] \
        * (context // cfg["index_kpool"]) \
        + 2 * heads * (cfg["qk_head_dim"] + cfg["v_head_dim"]) \
        * min(context, cfg["index_topk"])
    kda = 2 * kda_params(cfg) + kda_position_flops(cfg)
    moe = 2 * (G.router_params(cfg) + G.shared_params(cfg)
               + assignments * expert_params(cfg))
    return dsa_layers(cfg) * dsa + kda_layers(cfg) * kda \
        + cfg["num_hidden_layers"] * mhc_flops(cfg) \
        + dense_layers(cfg) * 2 * G.dense_ffn_params(cfg) \
        + expert_layers(cfg) * moe


def forward_flops(cfg, positions, context, with_head, assignments=None):
    """Forward FLOPs of ``positions`` new positions whose queries see
    ``context`` positions each on average; ``with_head`` adds the product
    with the output head over the rows held."""
    each = position_flops(cfg, context, assignments)
    if with_head:
        each += 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return positions * each
