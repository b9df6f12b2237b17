"""Capture-style API (TFPark equivalent) + inference engine tests."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

RNG = jax.random.PRNGKey(0)


def linreg_data(n=64, d=4, noise=0.0, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.rand(n, d).astype(np.float32)
    w = np.arange(1, d + 1, dtype=np.float32)
    y = (x @ w + noise * rs.randn(n)).astype(np.float32)[:, None]
    return x, y


class TestGraphModel:
    def test_from_loss(self, ctx):
        from analytics_zoo_tpu.capture import GraphModel
        x, y = linreg_data()

        def init_params(rng, sample_x):
            return {"w": jnp.zeros((sample_x.shape[-1], 1)),
                    "b": jnp.zeros((1,))}

        def loss_fn(params, bx, by):
            pred = bx @ params["w"] + params["b"]
            return jnp.mean((pred - by) ** 2)

        gm = GraphModel.from_loss(loss_fn, init_params, optimizer="adam")
        hist = gm.fit(x, y, batch_size=16, epochs=30)
        assert hist["loss_history"][-1] < hist["loss_history"][0]
        res = gm.evaluate(x, y, batch_size=16)
        assert "loss" in res
        w = gm.get_weights()["w"]
        assert w.shape == (4, 1)

    def test_from_loss_per_example_exact_eval(self, ctx):
        """per_example_loss_fn makes ragged-size eval EXACT: batch 16 over
        37 rows (2 full batches + tail 5) must equal plain numpy."""
        from analytics_zoo_tpu.capture import GraphModel
        rs = np.random.RandomState(3)
        x = rs.randn(37, 4).astype(np.float32)
        y = rs.randn(37, 1).astype(np.float32)

        def init_params(rng, sample_x):
            return {"w": jnp.ones((sample_x.shape[-1], 1))}

        def loss_fn(params, bx, by):
            return jnp.mean((bx @ params["w"] - by) ** 2)

        def per_example(params, bx, by):
            return jnp.mean((bx @ params["w"] - by) ** 2, axis=-1)

        gm = GraphModel.from_loss(loss_fn, init_params,
                                  per_example_loss_fn=per_example)
        gm.predict  # built lazily; evaluate initializes
        res = gm.evaluate(x, y, batch_size=16)
        expect = float(np.mean((x @ np.ones((4, 1)) - y) ** 2))
        assert res["loss"] == pytest.approx(expect, abs=1e-6)

    def test_from_forward(self, ctx):
        from analytics_zoo_tpu.capture import GraphModel
        x, y = linreg_data()

        def init_params(rng, sample_x):
            return {"w": jax.random.normal(rng, (sample_x.shape[-1], 1)) * 0.1}

        def forward(params, bx):
            return bx @ params["w"]

        gm = GraphModel.from_forward(forward, init_params, loss="mse",
                                     optimizer="sgd")
        hist = gm.fit(x, y, batch_size=16, epochs=20)
        assert hist["loss_history"][-1] < hist["loss_history"][0]
        preds = gm.predict(x, batch_size=16)
        assert preds.shape == (64, 1)

    def test_from_flax(self, ctx):
        import flax.linen as nn
        from analytics_zoo_tpu.capture import GraphModel

        class MLP(nn.Module):
            @nn.compact
            def __call__(self, x):
                x = nn.Dense(8)(x)
                x = nn.relu(x)
                return nn.Dense(1)(x)

        x, y = linreg_data()
        gm = GraphModel.from_flax(MLP(), loss="mse", optimizer="adam")
        hist = gm.fit(x, y, batch_size=16, epochs=10)
        assert hist["loss_history"][-1] < hist["loss_history"][0]
        assert gm.predict(x, batch_size=16).shape == (64, 1)

    def test_checkpoint_roundtrip(self, ctx, tmp_path):
        from analytics_zoo_tpu.capture import GraphModel
        x, y = linreg_data()

        def init_params(rng, sx):
            return {"w": jnp.zeros((sx.shape[-1], 1))}

        gm = GraphModel.from_forward(lambda p, bx: bx @ p["w"], init_params)
        gm.fit(x, y, batch_size=16, epochs=5)
        p1 = gm.predict(x, batch_size=16)
        gm.save_checkpoint(str(tmp_path / "ckpt"))
        gm2 = GraphModel.from_forward(lambda p, bx: bx @ p["w"], init_params)
        gm2.fit(x, y, batch_size=16, epochs=1)  # init shapes
        gm2.load_checkpoint(str(tmp_path / "ckpt"))
        np.testing.assert_allclose(gm2.predict(x, batch_size=16), p1,
                                   atol=1e-5)


class TestFnEstimator:
    def test_modes(self, ctx):
        from analytics_zoo_tpu.capture import FnEstimator, ModeKeys
        x, y = linreg_data()

        def init_fn(rng, sx):
            return {"w": jnp.zeros((sx.shape[-1], 1))}

        def model_fn(params, features, labels, mode, rng):
            pred = features @ params["w"]
            if mode == ModeKeys.PREDICT:
                return pred
            return jnp.mean((pred - labels) ** 2)

        est = FnEstimator(model_fn, init_fn, optimizer="adam")
        h = est.train(lambda mode: (x, y), batch_size=16, epochs=20)
        assert h["loss_history"][-1] < h["loss_history"][0]
        res = est.evaluate(lambda mode: (x, y), batch_size=16)
        assert res["loss"] < h["loss_history"][0]
        preds = est.predict(lambda mode: x, batch_size=16)
        assert preds.shape == (64, 1)


class TestGAN:
    def test_gan_trains(self, ctx):
        from analytics_zoo_tpu.capture import GANEstimator
        rs = np.random.RandomState(0)
        real = (rs.randn(256, 2) * 0.3 + np.array([2.0, -1.0])).astype(
            np.float32)

        def gen_init(rng, noise):
            k1, k2 = jax.random.split(rng)
            return {"w1": jax.random.normal(k1, (noise.shape[-1], 16)) * 0.1,
                    "b1": jnp.zeros((16,)),
                    "w2": jax.random.normal(k2, (16, 2)) * 0.1,
                    "b2": jnp.zeros((2,))}

        def gen_fn(p, z):
            h = jax.nn.relu(z @ p["w1"] + p["b1"])
            return h @ p["w2"] + p["b2"]

        def disc_init(rng, x):
            k1, k2 = jax.random.split(rng)
            return {"w1": jax.random.normal(k1, (x.shape[-1], 16)) * 0.1,
                    "b1": jnp.zeros((16,)),
                    "w2": jax.random.normal(k2, (16, 1)) * 0.1}

        def disc_fn(p, x):
            h = jax.nn.relu(x @ p["w1"] + p["b1"])
            return h @ p["w2"]

        def g_loss(fake_logits):
            return jnp.mean(jax.nn.softplus(-fake_logits))

        def d_loss(real_logits, fake_logits):
            return jnp.mean(jax.nn.softplus(-real_logits)) + \
                jnp.mean(jax.nn.softplus(fake_logits))

        from analytics_zoo_tpu.keras import optimizers
        gan = GANEstimator(gen_fn, disc_fn, g_loss, d_loss, gen_init,
                           disc_init,
                           generator_optimizer=optimizers.Adam(1e-2),
                           discriminator_optimizer=optimizers.Adam(1e-2),
                           noise_dim=4, d_steps=1, g_steps=2)
        hist = gan.train(real, batch_size=64, steps=150)
        assert hist["iterations"] == 150
        samples = gan.generate(128)
        assert samples.shape == (128, 2)
        # generator should move toward the real mode at (2, -1) from ~N(0, .1)
        assert samples.mean(0)[0] > 0.8 and samples.mean(0)[1] < -0.3


class TestBERTEstimators:
    def test_bert_classifier(self, ctx):
        from analytics_zoo_tpu.capture import BERTClassifier
        rs = np.random.RandomState(1)
        tokens = rs.randint(1, 50, (16, 10))
        labels = rs.randint(0, 2, 16)
        clf = BERTClassifier(2, bert_config=dict(
            vocab=50, hidden_size=16, n_block=1, n_head=2,
            max_position_len=10, intermediate_size=32))
        h = clf.fit(tokens, labels, batch_size=8, epochs=1)
        assert h["iterations"] >= 1
        p = clf.predict(tokens, batch_size=8)
        assert p.shape == (16, 2)

    def test_bert_ner(self, ctx):
        from analytics_zoo_tpu.capture import BERTNER
        rs = np.random.RandomState(2)
        tokens = rs.randint(1, 40, (8, 6))
        tags = rs.randint(0, 3, (8, 6))
        ner = BERTNER(3, bert_config=dict(
            vocab=40, hidden_size=16, n_block=1, n_head=2,
            max_position_len=6, intermediate_size=32))
        ner.fit(tokens, tags, batch_size=8, epochs=1)
        p = ner.predict(tokens, batch_size=8)
        assert p.shape == (8, 6, 3)

    def test_bert_squad(self, ctx):
        from analytics_zoo_tpu.capture import BERTSQuAD
        rs = np.random.RandomState(3)
        tokens = rs.randint(1, 40, (8, 6))
        spans = np.stack([rs.randint(0, 6, 8), rs.randint(0, 6, 8)], 1)
        qa = BERTSQuAD(bert_config=dict(
            vocab=40, hidden_size=16, n_block=1, n_head=2,
            max_position_len=6, intermediate_size=32))
        qa.fit(tokens, spans, batch_size=8, epochs=1)
        start, end = qa.predict(tokens, batch_size=8)
        assert start.shape == (8, 6) and end.shape == (8, 6)


class TestInferenceModel:
    def _simple_forward(self):
        def forward(params, x):
            return x @ params["w"] + params["b"]
        params = {"w": jnp.asarray(np.eye(3, 2, dtype=np.float32)),
                  "b": jnp.ones((2,))}
        return forward, params

    def test_load_jax_and_bucketing(self, ctx):
        from analytics_zoo_tpu.inference import InferenceModel
        fwd, params = self._simple_forward()
        im = InferenceModel(concurrent_num=2).load_jax(fwd, params)
        x = np.random.rand(5, 3).astype(np.float32)  # pads to bucket 8
        y = im.predict(x)
        assert y.shape == (5, 2)
        np.testing.assert_allclose(y, x @ np.eye(3, 2) + 1, atol=1e-5)
        y2 = im.predict(np.random.rand(7, 3).astype(np.float32))
        assert y2.shape == (7, 2)  # same bucket (8) reused by jit's cache
        y3 = im.predict(np.random.rand(20, 3).astype(np.float32),
                        batch_size=8)
        assert y3.shape == (20, 2)

    def test_pool_concurrency(self, ctx):
        from analytics_zoo_tpu.inference import InferenceModel
        fwd, params = self._simple_forward()
        im = InferenceModel(concurrent_num=4).load_jax(fwd, params)
        batches = [np.random.rand(4, 3).astype(np.float32) for _ in range(8)]
        outs = im.predict_many(batches)
        assert len(outs) == 8 and all(o.shape == (4, 2) for o in outs)

    def test_quantize_bf16_int8(self, ctx):
        from analytics_zoo_tpu.inference import InferenceModel
        rs = np.random.RandomState(0)
        w = rs.randn(8, 4).astype(np.float32)

        def forward(params, x):
            return x @ params["w"]

        x = rs.rand(4, 8).astype(np.float32)
        ref = x @ w
        for dtype, tol in (("bf16", 0.1), ("int8", 0.2)):
            im = InferenceModel().load_jax(forward, {"w": jnp.asarray(w)})
            im.quantize(dtype)
            y = im.predict(x)
            np.testing.assert_allclose(y, ref, atol=tol)

    def test_load_zoo_model(self, ctx, tmp_path):
        from analytics_zoo_tpu.inference import InferenceModel
        from analytics_zoo_tpu.models import NeuralCF
        ncf = NeuralCF(10, 8, 2, user_embed=4, item_embed=4,
                       hidden_layers=[8], mf_embed=4)
        ncf.default_compile()
        rs = np.random.RandomState(0)
        x = np.stack([rs.randint(1, 11, 16), rs.randint(1, 9, 16)],
                     1).astype(np.float32)
        y = rs.randint(0, 2, 16).astype(np.float32)
        ncf.fit(x, y, batch_size=8, nb_epoch=1)
        path = str(tmp_path / "ncf")
        ncf.save_model(path)
        im = InferenceModel().load_zoo(path)
        p = im.predict(x)
        np.testing.assert_allclose(
            p, np.asarray(ncf.predict(x, batch_size=16)), atol=1e-5)

    def test_load_savedmodel(self, ctx, tmp_path):
        tf = pytest.importorskip("tensorflow")
        from analytics_zoo_tpu.inference import InferenceModel

        class M(tf.Module):
            @tf.function(input_signature=[
                tf.TensorSpec([None, 3], tf.float32)])
            def __call__(self, x):
                return {"out": 2.0 * x}

        path = str(tmp_path / "sm")
        tf.saved_model.save(M(), path)
        im = InferenceModel().load_savedmodel(path)
        x = np.random.rand(4, 3).astype(np.float32)
        np.testing.assert_allclose(im.predict(x), 2 * x, atol=1e-5)

    def test_savedmodel_stablehlo_roundtrip_serves_without_tf(self, ctx,
                                                              tmp_path):
        # the SERVED path must not need TF — export the
        # imported SavedModel to StableHLO buckets, then predict from the
        # artifact in a subprocess where importing tensorflow is a hard
        # error
        tf = pytest.importorskip("tensorflow")
        import subprocess
        import sys

        from analytics_zoo_tpu.inference import InferenceModel

        class M(tf.Module):
            @tf.function(input_signature=[
                tf.TensorSpec([None, 3], tf.float32)])
            def __call__(self, x):
                return {"out": 3.0 * x + 1.0}

        sm = str(tmp_path / "sm")
        tf.saved_model.save(M(), sm)
        art = str(tmp_path / "aot")
        x = np.random.RandomState(0).rand(4, 3).astype(np.float32)
        im = InferenceModel().load_savedmodel(sm)
        im.export_compiled(art, x, batch_sizes=(4,), platforms=("cpu",))
        np.save(str(tmp_path / "x.npy"), x)
        code = f"""
import sys
sys.modules["tensorflow"] = None  # any TF import now raises
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax; jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
import numpy as np
from analytics_zoo_tpu.inference import InferenceModel
x = np.load({str(tmp_path / 'x.npy')!r})
im = InferenceModel().load_compiled({art!r})
got = np.asarray(im.predict(x))
np.testing.assert_allclose(got, 3.0 * x + 1.0, atol=1e-5)
print("TF_FREE_SERVE_OK")
"""
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "TF_FREE_SERVE_OK" in proc.stdout

    def test_savedmodel_multi_output_artifact_keeps_keys(self, ctx,
                                                         tmp_path):
        # dict-output signatures must serve the SAME dict from the TF-free
        # artifact as from the live call_tf path
        tf = pytest.importorskip("tensorflow")
        from analytics_zoo_tpu.inference import InferenceModel

        class M(tf.Module):
            @tf.function(input_signature=[
                tf.TensorSpec([None, 3], tf.float32)])
            def __call__(self, x):
                return {"scores": 2.0 * x, "bias": x + 1.0}

        sm = str(tmp_path / "sm")
        tf.saved_model.save(M(), sm)
        x = np.random.RandomState(1).rand(4, 3).astype(np.float32)
        im = InferenceModel().load_savedmodel(sm)
        live = im.predict(x)
        assert set(live) == {"scores", "bias"}
        art = str(tmp_path / "art")
        im.export_compiled(art, x, batch_sizes=(4,), platforms=("cpu",))
        got = InferenceModel().load_compiled(art).predict(x)
        assert set(got) == {"scores", "bias"}
        np.testing.assert_allclose(got["scores"], 2.0 * x, atol=1e-5)
        np.testing.assert_allclose(got["bias"], x + 1.0, atol=1e-5)

    def test_reused_model_does_not_export_stale_savedmodel(self, ctx,
                                                           tmp_path):
        tf = pytest.importorskip("tensorflow")
        import jax.numpy as jnp

        from analytics_zoo_tpu.inference import InferenceModel

        class M(tf.Module):
            @tf.function(input_signature=[
                tf.TensorSpec([None, 3], tf.float32)])
            def __call__(self, x):
                return {"out": 9.0 * x}

        sm = str(tmp_path / "sm")
        tf.saved_model.save(M(), sm)
        im = InferenceModel().load_savedmodel(sm)
        im.load_jax(lambda p, x: x @ p["w"], {"w": jnp.eye(3)})
        x = np.random.RandomState(2).rand(2, 3).astype(np.float32)
        art = str(tmp_path / "art2")
        im.export_compiled(art, x, batch_sizes=(2,), platforms=("cpu",))
        got = np.asarray(InferenceModel().load_compiled(art).predict(x))
        np.testing.assert_allclose(got, x, atol=1e-5)  # NOT 9*x

    def test_load_torch(self, ctx, tmp_path):
        torch = pytest.importorskip("torch")
        from analytics_zoo_tpu.inference import InferenceModel

        class Net(torch.nn.Module):
            def forward(self, x):
                return x * 3.0

        path = str(tmp_path / "net.pt")
        torch.jit.script(Net()).save(path)
        im = InferenceModel().load_torch(path)
        x = np.random.rand(4, 3).astype(np.float32)
        np.testing.assert_allclose(im.predict(x), 3 * x, atol=1e-5)


class TestImportedModelServing:
    def test_load_onnx_into_pool(self, tmp_path):
        from test_net import _mlp_onnx
        rs = np.random.RandomState(0)
        data, (w1, b1, w2, b2) = _mlp_onnx(rs)
        path = tmp_path / "m.onnx"
        path.write_bytes(data)
        from analytics_zoo_tpu.inference import InferenceModel
        im = InferenceModel(concurrent_num=2).load_onnx(str(path))
        x = rs.randn(4, 4).astype(np.float32)
        out = np.asarray(im.predict(x))
        expected = np.maximum(x @ w1 + b1, 0) @ w2 + b2
        np.testing.assert_allclose(out, expected, rtol=1e-4, atol=1e-4)

    def test_load_caffe_into_pool(self, tmp_path):
        pt = tmp_path / "net.prototxt"
        pt.write_text("""
input: "data"
input_shape { dim: 1 dim: 1 dim: 4 dim: 4 }
layer { name: "p1" type: "Pooling" bottom: "data" top: "p1"
        pooling_param { pool: AVE kernel_size: 2 stride: 2 } }
""")
        from analytics_zoo_tpu.inference import InferenceModel
        im = InferenceModel().load_caffe(str(pt))
        x = np.arange(16, dtype=np.float32).reshape(1, 4, 4, 1)
        out = np.asarray(im.predict(x))
        assert out.shape == (1, 2, 2, 1)
        assert out[0, 0, 0, 0] == x[0, :2, :2, 0].mean()


class TestAOTExport:
    """Serialized ahead-of-time compiled artifacts (the OpenVINO IR role):
    export on one process, serve from the artifact with zero JIT compiles."""

    def _make_pool(self, ctx):
        import jax.numpy as jnp
        from analytics_zoo_tpu.inference import InferenceModel
        rs = np.random.RandomState(0)
        w = rs.randn(6, 3).astype(np.float32)

        def fwd(params, x):
            return jnp.tanh(x @ params["w"])

        return InferenceModel(concurrent_num=2).load_jax(
            fwd, {"w": jnp.asarray(w)}), w

    def test_export_load_roundtrip(self, ctx, tmp_path):
        from analytics_zoo_tpu.inference import InferenceModel
        pool, w = self._make_pool(ctx)
        x = np.random.RandomState(1).rand(20, 6).astype(np.float32)
        ref = np.asarray(pool.predict(x))
        path = str(tmp_path / "aot")
        pool.export_compiled(path, x[:1], batch_sizes=(4, 16, 32))
        served = InferenceModel(concurrent_num=2).load_compiled(path)
        out = np.asarray(served.predict(x))  # pads 20 -> bucket 32
        np.testing.assert_allclose(out, ref, atol=1e-5)
        # larger than the biggest bucket: chunked through bucket 32
        x_big = np.random.RandomState(2).rand(70, 6).astype(np.float32)
        out_big = np.asarray(served.predict(x_big))
        np.testing.assert_allclose(out_big, np.tanh(x_big @ w), atol=1e-5)

    def test_artifact_is_self_contained(self, ctx, tmp_path):
        import os
        pool, _ = self._make_pool(ctx)
        path = str(tmp_path / "aot")
        pool.export_compiled(path, np.zeros((1, 6), np.float32),
                             batch_sizes=(8,))
        files = sorted(os.listdir(path))
        assert files == ["aot_meta.json", "batch-8.stablehlo"]
        # params are frozen inside the artifact: nothing else needed
        assert os.path.getsize(os.path.join(path, "batch-8.stablehlo")) > 0

    def test_multi_input_and_empty_batch(self, ctx, tmp_path):
        import jax.numpy as jnp
        from analytics_zoo_tpu.inference import InferenceModel
        w = np.random.RandomState(3).randn(4, 2).astype(np.float32)

        def fwd(params, xs):  # list-of-inputs calling convention
            a, b = xs
            return (a + b) @ params["w"]

        pool = InferenceModel().load_jax(fwd, {"w": jnp.asarray(w)})
        ex = [np.zeros((1, 4), np.float32), np.zeros((1, 4), np.float32)]
        path = str(tmp_path / "aot_multi")
        pool.export_compiled(path, ex, batch_sizes=(4,))
        served = InferenceModel().load_compiled(path)
        a = np.random.RandomState(4).rand(3, 4).astype(np.float32)
        b = np.random.RandomState(5).rand(3, 4).astype(np.float32)
        np.testing.assert_allclose(np.asarray(served.predict([a, b])),
                                   (a + b) @ w, atol=1e-5)
        # empty batch trims to zero rows through the bucket-1..4 program
        empty = np.zeros((0, 4), np.float32)
        out = np.asarray(served.predict([empty, empty]))
        assert out.shape == (0, 2)
        # batch_size chunking still honored on the AOT path
        big_a = np.random.RandomState(6).rand(10, 4).astype(np.float32)
        big_b = np.random.RandomState(7).rand(10, 4).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(served.predict([big_a, big_b], batch_size=3)),
            (big_a + big_b) @ w, atol=1e-5)


class TestTransformerLM:
    def test_fit_and_cached_generation(self, ctx):
        from analytics_zoo_tpu.capture import TransformerLM
        V, S = 12, 16
        lm = TransformerLM(vocab_size=V, hidden=32, n_block=2, n_head=2,
                           max_len=64)
        rs = np.random.RandomState(0)
        starts = rs.randint(0, V, 256)
        data = (starts[:, None] + np.arange(S)[None]) % V  # cyclic counting
        r = lm.fit(data, batch_size=32, epochs=40)
        assert r["loss_history"][-1] < 0.1
        prompt = data[:2, :5]
        gen = lm.generate(prompt, max_new_tokens=6)
        expect = np.stack([(p[-1] + 1 + np.arange(6)) % V for p in prompt])
        np.testing.assert_array_equal(gen, expect)

    def test_generation_consistent_with_full_forward(self, ctx):
        """Prefill+cached decode must pick the same argmax as the full
        forward on an UNTRAINED model (exactness of the cache path)."""
        import jax.numpy as jnp
        from analytics_zoo_tpu.capture import TransformerLM
        lm = TransformerLM(vocab_size=9, hidden=16, n_block=2, n_head=2,
                           max_len=32, seed=3)
        rs = np.random.RandomState(1)
        prompt = rs.randint(0, 9, (2, 6))
        lm.fit(prompt.repeat(4, 0), batch_size=8, epochs=1)  # init params
        gen1 = lm.generate(prompt, max_new_tokens=1)[:, 0]
        logits = np.asarray(lm.logits(prompt))  # [B, S, V]
        full_next = logits[:, -1].argmax(-1)
        np.testing.assert_array_equal(gen1, full_next)
        # beam_size=1-equivalent best beam matches greedy on a peaked model
        beam = lm.generate(prompt, max_new_tokens=1, beam_size=3)[:, 0]
        np.testing.assert_array_equal(beam, full_next)

    def test_prompt_budget_enforced(self, ctx):
        from analytics_zoo_tpu.capture import TransformerLM
        lm = TransformerLM(vocab_size=5, hidden=16, n_block=1, n_head=2,
                           max_len=8)
        lm.fit(np.zeros((8, 8)), batch_size=8, epochs=1)
        with pytest.raises(ValueError, match="exceeds max_len"):
            lm.generate(np.zeros((1, 6), np.int32), max_new_tokens=4)
