"""BERT task estimators (reference ``pyzoo/zoo/tfpark/text/estimator/``:
``bert_base.py:108`` BERTBaseEstimator, ``bert_classifier.py:57``,
``bert_ner.py:49``, ``bert_squad.py:77``) rebuilt over the native BERT layer.

Each wraps BERT + a task head into a compiled Keras model whose inputs are
the standard 4-tensor pack [token_ids, token_type_ids, position_ids,
attention_mask]."""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import numpy as np

from ..keras import Sequential
from ..keras.engine import Layer
from ..keras.layers import BERT, Dense, Dropout, Lambda


def bert_input_pack(token_ids: np.ndarray,
                    token_type_ids: Optional[np.ndarray] = None,
                    attention_mask: Optional[np.ndarray] = None):
    """Build the 4-array BERT input: defaults type ids to 0, positions to
    arange, mask to nonzero-token."""
    token_ids = np.asarray(token_ids)
    b, s = token_ids.shape
    if token_type_ids is None:
        token_type_ids = np.zeros((b, s), np.int32)
    if attention_mask is None:
        attention_mask = (token_ids != 0).astype(np.float32)
    positions = np.broadcast_to(np.arange(s, dtype=np.int32), (b, s)).copy()
    return [token_ids.astype(np.int32), np.asarray(token_type_ids, np.int32),
            positions, np.asarray(attention_mask, np.float32)]


class _BERTTask(Sequential):
    """Sequential over [BERT, head...] that still takes the 4-input pack."""


class _Head(Dense):
    """A task's head over the encoder: a ``Dense`` whose operations carry
    the scope name ``classifier`` on the device (docs/observability.md);
    the encoder names its own parts."""

    def call(self, params, state, inputs, *, training=False, rng=None):
        with jax.named_scope("classifier"):
            return super().call(params, state, inputs, training=training,
                                rng=rng)


def _make_bert(bert_config: Dict[str, Any]) -> BERT:
    defaults = dict(vocab=30522, hidden_size=768, n_block=12, n_head=12,
                    max_position_len=512, intermediate_size=3072,
                    output_all_block=False)
    defaults.update(bert_config or {})
    defaults["output_all_block"] = False
    return BERT(**defaults)


class BERTClassifier:
    """Sequence classification over the pooled output
    (≙ ``BERTClassifier``, bert_classifier.py:57)."""

    def __init__(self, num_classes: int, bert_config: Optional[Dict] = None,
                 dropout: float = 0.1, optimizer="adam"):
        bert = _make_bert(bert_config)
        self.model = _BERTTask([
            bert,
            Lambda(lambda outs: outs[-1], name="take_pooled"),
            Dropout(dropout),
            _Head(num_classes, activation="softmax", name="classifier"),
        ])
        self.model.compile(optimizer, "sparse_categorical_crossentropy",
                           metrics=["accuracy"])

    def fit(self, token_ids, labels, batch_size=32, epochs=1, **bert_inputs):
        x = bert_input_pack(token_ids, bert_inputs.get("token_type_ids"),
                            bert_inputs.get("attention_mask"))
        return self.model.fit(x, np.asarray(labels, np.float32),
                              batch_size=batch_size, nb_epoch=epochs)

    def predict(self, token_ids, batch_size=32, **bert_inputs):
        x = bert_input_pack(token_ids, bert_inputs.get("token_type_ids"),
                            bert_inputs.get("attention_mask"))
        return self.model.predict(x, batch_size=batch_size)

    def evaluate(self, token_ids, labels, batch_size=32, **bert_inputs):
        x = bert_input_pack(token_ids, bert_inputs.get("token_type_ids"),
                            bert_inputs.get("attention_mask"))
        return self.model.evaluate(x, np.asarray(labels, np.float32),
                                   batch_size=batch_size)


class BERTNER:
    """Token-level tagging over the last block states
    (≙ ``BERTNER``, bert_ner.py:49)."""

    def __init__(self, num_entities: int, bert_config: Optional[Dict] = None,
                 dropout: float = 0.1, optimizer="adam"):
        bert = _make_bert(bert_config)
        self.model = _BERTTask([
            bert,
            Lambda(lambda outs: outs[0], name="take_states"),
            Dropout(dropout),
            _Head(num_entities, activation="softmax", name="tagger"),
        ])
        self.model.compile(optimizer, "sparse_categorical_crossentropy")

    def fit(self, token_ids, tag_ids, batch_size=32, epochs=1, **bert_inputs):
        x = bert_input_pack(token_ids, bert_inputs.get("token_type_ids"),
                            bert_inputs.get("attention_mask"))
        return self.model.fit(x, np.asarray(tag_ids, np.float32),
                              batch_size=batch_size, nb_epoch=epochs)

    def predict(self, token_ids, batch_size=32, **bert_inputs):
        x = bert_input_pack(token_ids, bert_inputs.get("token_type_ids"),
                            bert_inputs.get("attention_mask"))
        return self.model.predict(x, batch_size=batch_size)


class _SQuADHead(Layer):
    """Start/end span logits from sequence states: Dense(2) split."""

    def __init__(self, name=None):
        super().__init__(name)

    def build(self, rng, input_shape):
        import jax
        hidden = input_shape[-1]
        k = jax.random.normal(rng, (hidden, 2)) * 0.02
        import jax.numpy as jnp
        return {"kernel": k, "bias": jnp.zeros((2,))}, {}

    def call(self, params, state, inputs, *, training=False, rng=None):
        import jax.numpy as jnp
        logits = inputs @ params["kernel"] + params["bias"]  # [b, s, 2]
        start, end = logits[..., 0], logits[..., 1]
        return [jnp.asarray(start), jnp.asarray(end)], state

    def compute_output_shape(self, input_shape):
        return [(input_shape[0], input_shape[1])] * 2


class BERTSQuAD:
    """Extractive QA span prediction (≙ ``BERTSQuAD``, bert_squad.py:77).
    Labels: [start_positions, end_positions]."""

    def __init__(self, bert_config: Optional[Dict] = None, optimizer="adam"):
        bert = _make_bert(bert_config)
        self.model = _BERTTask([
            bert,
            Lambda(lambda outs: outs[0], name="take_states"),
            _SQuADHead(name="squad_head"),
        ])

        def span_loss(y, y_pred):
            import jax.numpy as jnp
            from ..keras.objectives import (
                sparse_categorical_crossentropy_from_logits as ce)
            start_logits, end_logits = y_pred
            start_y, end_y = y[:, 0], y[:, 1]
            return 0.5 * (ce(start_y, start_logits) + ce(end_y, end_logits))

        self.model.compile(optimizer, span_loss)

    def fit(self, token_ids, spans, batch_size=32, epochs=1, **bert_inputs):
        x = bert_input_pack(token_ids, bert_inputs.get("token_type_ids"),
                            bert_inputs.get("attention_mask"))
        return self.model.fit(x, np.asarray(spans, np.float32),
                              batch_size=batch_size, nb_epoch=epochs)

    def predict(self, token_ids, batch_size=32, **bert_inputs):
        """Returns (start_logits, end_logits)."""
        x = bert_input_pack(token_ids, bert_inputs.get("token_type_ids"),
                            bert_inputs.get("attention_mask"))
        return self.model.predict(x, batch_size=batch_size)
