"""Between the benchmark and the program's BERT classifier
(``capture.text.BERTClassifier``): builds it as the configuration states,
hands it the benchmark's weights, and maps its trees back to the
benchmark's layout. Only this file and the runner import the program."""
import numpy as np

BLOCK = {"q": ("attn", "q"), "k": ("attn", "k"), "v": ("attn", "v"),
         "o": ("attn", "o"), "ffn_in": ("ffn_in",), "ffn_out": ("ffn_out",),
         "ln1": ("ln1",), "ln2": ("ln2",)}


def build(cfg, devices):
    """The classifier with its estimator on a ``(len(devices),)`` data mesh,
    not yet initialised."""
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from analytics_zoo_tpu.capture.text import BERTClassifier
    from analytics_zoo_tpu.keras.optimizers import AdamWeightDecay
    opt = cfg["optimizer"]
    bert = dict(vocab=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
                n_block=cfg["num_hidden_layers"],
                n_head=cfg["num_attention_heads"],
                max_position_len=cfg["max_position_embeddings"],
                intermediate_size=cfg["intermediate_size"],
                hidden_p_drop=cfg["hidden_dropout_prob"],
                attn_p_drop=cfg["attention_probs_dropout_prob"],
                initializer_range=cfg["initializer_range"],
                compute_dtype=getattr(jnp, cfg["compute_dtype"]))
    clf = BERTClassifier(
        cfg["num_labels"], bert_config=bert,
        dropout=cfg["classifier_dropout"],
        optimizer=AdamWeightDecay(
            opt["learning_rate"], beta1=opt["beta1"], beta2=opt["beta2"],
            epsilon=opt["epsilon"], weight_decay=opt["weight_decay"]))
    clf.model.get_estimator().mesh = Mesh(np.asarray(devices), ("data",))
    return clf


def _names(tree):
    bert = next(k for k, v in tree.items() if "word_emb" in v)
    head = next(k for k, v in tree.items() if k != bert and "kernel" in v)
    return bert, head


def _dig(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def to_program(weights, template):
    """The benchmark's ``weights`` as a tree shaped like the program's
    ``template`` (any tree of its parameters, or of their shapes)."""
    bert, head = _names(template)
    n = len([k for k in template[bert] if k.startswith("block_")])
    out = {k: weights[k] for k in ("word_emb", "pos_emb", "type_emb",
                                   "emb_ln", "pooler")}
    for i in range(n):
        block = {"attn": {}}
        for name, path in BLOCK.items():
            leaf = {k: v[i] for k, v in weights["blocks"][name].items()}
            (block["attn"] if path[0] == "attn" else block)[path[-1]] = leaf
        out[f"block_{i}"] = block
    return {bert: out, head: weights["classifier"]}


def to_bench(tree):
    """A tree of the program's (parameters, a gradient, Adam's moments) in
    the benchmark's layout, the blocks stacked."""
    import jax.numpy as jnp
    bert, head = _names(tree)
    src = tree[bert]
    n = len([k for k in src if k.startswith("block_")])
    blocks = {name: {leaf: jnp.stack([_dig(src[f"block_{i}"], path)[leaf]
                                      for i in range(n)])
                     for leaf in _dig(src["block_0"], path)}
              for name, path in BLOCK.items()}
    out = {k: src[k] for k in ("word_emb", "pos_emb", "type_emb", "emb_ln",
                               "pooler")}
    out["blocks"], out["classifier"] = blocks, tree[head]
    return out


def load(clf, weights, sample_tokens):
    """Hand the benchmark's weights to the classifier's estimator."""
    import jax
    from analytics_zoo_tpu.capture.text import bert_input_pack
    from analytics_zoo_tpu.keras.engine import init_model
    est = clf.model.get_estimator()
    x = bert_input_pack(sample_tokens)
    template = jax.eval_shape(
        lambda r: init_model(clf.model, r, x)[0], jax.random.PRNGKey(0))
    est.set_params(to_program(weights, template))
    est.opt_state = None


def first_gradient(clf, beta1):
    """The first gradient as the optimizer got it, from Adam's first moment
    after one step: ``mu = (1 - beta1) g``."""
    import jax
    mu = clf.model.get_estimator().opt_state[0].mu
    return jax.tree_util.tree_map(lambda m: m / (1.0 - beta1), to_bench(mu))


def parameters(clf):
    return to_bench(clf.model.get_estimator().params)


def fit(clf, tokens, labels, batch, epochs_more):
    """``epochs_more`` further epochs over the rows through the window's own
    call; returns the loss of each step."""
    est = clf.model.get_estimator()
    out = clf.fit(tokens, labels, batch_size=batch,
                  epochs=est.epoch - 1 + epochs_more)
    return [float(v) for v in out["loss_history"]]


def release(clf):
    est = clf.model.get_estimator()
    est.params = est.opt_state = None
    est._train_step = est._multi_step = None
