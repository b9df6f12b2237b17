"""Golden-validated pretrained import (an earlier review's bar).

A torchvision-architecture ResNet-18 built in torch (the golden reference —
torch computes the expected activations at test time, which is strictly
stronger than frozen golden files: ANY layer-mapping error shows up as a
logit mismatch) is imported via ``net.load_torch_state_dict`` into the
native ``resnet(18, padding_mode="torch")`` graph. The probabilities must
match torch within 1e-4, BN statistics must transfer, and a freeze-backbone
fine-tune must leave imported backbone weights untouched.

Reference parity: ``models/image/imageclassification/ImageClassifier.scala:37``
loads published pretrained artifacts; the import path here is the TPU-native
equivalent.
"""
import numpy as np
import pytest

torch = pytest.importorskip("torch")
nn = torch.nn


@pytest.fixture(scope="module")
def imported():
    from analytics_zoo_tpu.net.torch_import import torchvision_resnet18
    torch.manual_seed(0)
    tm = torchvision_resnet18(num_classes=10)
    # a couple of train-mode passes give the BN running stats non-trivial
    # values, so a stats-transfer bug can't hide behind zeros/ones
    tm.train()
    with torch.no_grad():
        for i in range(2):
            tm(torch.randn(4, 3, 64, 64,
                           generator=torch.Generator().manual_seed(i)))
    tm.eval()

    from analytics_zoo_tpu.models.image.imageclassification import resnet
    from analytics_zoo_tpu.net import load_torch_state_dict
    model = resnet(18, num_classes=10, input_shape=(64, 64, 3),
                   padding_mode="torch")
    params, state = load_torch_state_dict(model, tm.state_dict())
    return tm, model, params, state


class TestGoldenResnet18Import:
    def test_probabilities_match_torch_1e4(self, ctx, imported):
        tm, model, params, state = imported
        rs = np.random.RandomState(7)
        x = rs.randn(3, 64, 64, 3).astype(np.float32)
        with torch.no_grad():
            logits = tm(torch.from_numpy(np.transpose(x, (0, 3, 1, 2))))
            want = torch.softmax(logits, dim=-1).numpy()
        y, _ = model.call(params, state, x, training=False)
        got = np.asarray(y, np.float32)
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-3)
        # log-domain comparison ≈ logit deltas (up to the softmax constant)
        np.testing.assert_allclose(np.log(got + 1e-12),
                                   np.log(want + 1e-12), atol=1e-3)

    def test_bn_stats_transferred(self, imported):
        tm, model, params, state = imported
        want = tm.bn1.running_mean.numpy()
        got = np.asarray(state["stem_bn"]["moving_mean"])
        np.testing.assert_allclose(got, want, atol=1e-6)
        assert float(np.abs(want).max()) > 1e-4, \
            "BN stats trivially zero — the fixture failed to train them"

    def test_wrong_mapping_fails(self, ctx, imported):
        # the golden check has teeth: corrupt ONE imported kernel and the
        # probabilities must diverge far beyond tolerance
        tm, model, params, state = imported
        import jax
        bad = jax.tree_util.tree_map(lambda x: x, params)
        k = np.asarray(bad["stage2_block1_sc_conv"]["kernel"]).copy()
        bad["stage2_block1_sc_conv"]["kernel"] = k[..., ::-1]
        rs = np.random.RandomState(7)
        x = rs.randn(2, 64, 64, 3).astype(np.float32)
        with torch.no_grad():
            want = torch.softmax(
                tm(torch.from_numpy(np.transpose(x, (0, 3, 1, 2)))),
                dim=-1).numpy()
        y, _ = model.call(bad, state, x, training=False)
        assert np.max(np.abs(np.asarray(y) - want)) > 1e-3

    def test_classifier_pretrained_with_label_map(self, ctx, imported,
                                                  tmp_path):
        # end-to-end zoo path: ImageClassifier.load_pretrained_torch +
        # a label map file feeding predict_image_set's labeled top-k
        tm, *_ = imported
        import json

        from analytics_zoo_tpu.feature.image import LocalImageSet
        from analytics_zoo_tpu.models import ImageClassifier
        labels = [f"class_{i}" for i in range(10)]
        (tmp_path / "labels.json").write_text(json.dumps(labels))
        clf = ImageClassifier("resnet18", num_classes=10,
                              input_shape=(64, 64, 3))
        clf.load_pretrained_torch(tm).with_label_map(
            str(tmp_path / "labels.json"))
        rs = np.random.RandomState(11)
        imgs = [rs.randint(0, 255, (64, 64, 3)).astype(np.uint8)
                for _ in range(3)]
        out = clf.predict_image_set(LocalImageSet(imgs), top_k=3)
        assert len(out) == 3 and all(len(r) == 3 for r in out)
        assert all(lbl in labels for r in out for lbl, _ in r)

    @pytest.mark.slow  # re-tiered: heaviest e2e sweep (tier-1 870s budget)
    def test_pretrained_save_load_keeps_geometry(self, ctx, imported,
                                                 tmp_path):
        # the padding geometry must survive save_model/load_model — a
        # reloaded torch-import would otherwise silently pad differently
        tm, *_ = imported
        from analytics_zoo_tpu.models import ImageClassifier
        clf = ImageClassifier("resnet18", num_classes=10,
                              input_shape=(64, 64, 3))
        clf.load_pretrained_torch(tm)
        rs = np.random.RandomState(13)
        x = rs.randn(2, 64, 64, 3).astype(np.float32)
        want = np.asarray(clf.predict(x))
        clf.save_model(str(tmp_path / "m"))
        clf2 = ImageClassifier.load_model(str(tmp_path / "m"))
        assert clf2.padding_mode == "torch"
        np.testing.assert_allclose(np.asarray(clf2.predict(x)), want,
                                   atol=1e-5)

    @pytest.mark.slow  # re-tiered: heaviest e2e sweep (tier-1 870s budget)
    def test_golden_import_bundles_to_remote(self, ctx, imported, tmp_path):
        # the golden torch import, shipped as ONE pretrained bundle over a
        # fake-remote scheme, reloads with labels + torch padding geometry
        # and reproduces the golden-validated predictions exactly
        tm, *_ = imported
        from fsspec.implementations.memory import MemoryFileSystem

        from analytics_zoo_tpu.common import file_io
        from analytics_zoo_tpu.models import ImageClassifier, ZooModel
        clf = ImageClassifier("resnet18", num_classes=10,
                              input_shape=(64, 64, 3),
                              labels=[f"class_{i}" for i in range(10)])
        clf.load_pretrained_torch(tm)
        rs = np.random.RandomState(17)
        x = rs.randn(2, 64, 64, 3).astype(np.float32)
        want = np.asarray(clf.predict(x))
        file_io.register_filesystem("goldfs", MemoryFileSystem())
        try:
            uri = "goldfs://zoo/resnet18-golden"
            clf.save_pretrained(uri)
            loaded = ZooModel.load_pretrained(uri)
            assert loaded.padding_mode == "torch"
            assert loaded.labels == [f"class_{i}" for i in range(10)]
            np.testing.assert_allclose(np.asarray(loaded.predict(x)), want,
                                       atol=1e-5)
        finally:
            file_io.unregister_filesystem("goldfs")

    def test_label_map_formats(self, tmp_path):
        import json

        from analytics_zoo_tpu.models import ImageClassifier
        (tmp_path / "zero.json").write_text(json.dumps(
            {"0": "a", "1": "b", "2": "c"}))
        (tmp_path / "one.json").write_text(json.dumps(
            {"1": "a", "2": "b", "3": "c"}))
        (tmp_path / "lines.txt").write_text("a\nb\nc\n")
        for f in ("zero.json", "one.json", "lines.txt"):
            assert ImageClassifier.load_label_map(
                str(tmp_path / f)) == ["a", "b", "c"], f
        (tmp_path / "gap.json").write_text(json.dumps({"0": "a", "5": "b"}))
        with pytest.raises(ValueError):
            ImageClassifier.load_label_map(str(tmp_path / "gap.json"))

    def test_freeze_backbone_finetune(self, ctx, imported):
        tm, model, params, state = imported
        from analytics_zoo_tpu.feature import FeatureSet
        model.compile(optimizer="sgd",
                      loss="sparse_categorical_crossentropy")
        est = model.get_estimator()
        est.set_params(params)
        est.set_model_state(state)
        model.freeze([n for n in params if n != "logits"])
        rs = np.random.RandomState(3)
        x = rs.randn(8, 64, 64, 3).astype(np.float32)
        y = rs.randint(0, 10, 8).astype(np.float32)
        before = {"stem": np.asarray(params["stem_conv"]["kernel"]).copy(),
                  "logits": np.asarray(params["logits"]["kernel"]).copy()}
        model.fit(FeatureSet.from_ndarrays(x, y), batch_size=8, nb_epoch=1)
        after = est.get_params()
        np.testing.assert_allclose(np.asarray(after["stem_conv"]["kernel"]),
                                   np.asarray(before["stem"]),
                                   err_msg="frozen backbone moved")
        assert np.max(np.abs(np.asarray(after["logits"]["kernel"])
                             - np.asarray(before["logits"]))) > 0, \
            "head did not train"
