"""Transfer learning + model import: ONNX loader, torch weights, freezing,
graph surgery (reference NetUtils.scala / onnx_loader.py behavior)."""
import struct

import numpy as np
import pytest

from analytics_zoo_tpu.net import Net, load_onnx, load_torch_state_dict

# ---------------------------------------------------------------------------
# minimal protobuf wire ENCODER (test-side twin of net/onnx_wire.py's decoder)
# ---------------------------------------------------------------------------


def _varint(v: int) -> bytes:
    out = b""
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out += bytes([b | 0x80])
        else:
            return out + bytes([b])


def _tag(fno: int, wt: int) -> bytes:
    return _varint((fno << 3) | wt)


def _len_field(fno: int, payload: bytes) -> bytes:
    return _tag(fno, 2) + _varint(len(payload)) + payload


def _str_field(fno: int, s: str) -> bytes:
    return _len_field(fno, s.encode())


def _int_field(fno: int, v: int) -> bytes:
    return _tag(fno, 0) + _varint(v & ((1 << 64) - 1))


def _float_field(fno: int, v: float) -> bytes:
    return _tag(fno, 5) + struct.pack("<f", v)


def _tensor(name: str, arr: np.ndarray) -> bytes:
    arr = np.asarray(arr)
    dt = {np.float32: 1, np.int64: 7, np.int32: 6}[arr.dtype.type]
    body = b"".join(_int_field(1, d) for d in arr.shape)
    body += _int_field(2, dt)
    body += _str_field(8, name)
    body += _len_field(9, arr.tobytes())
    return body


def _attr_int(name: str, v: int) -> bytes:
    return _str_field(1, name) + _int_field(3, v) + _int_field(20, 2)


def _attr_float(name: str, v: float) -> bytes:
    return _str_field(1, name) + _float_field(2, v) + _int_field(20, 1)


def _attr_ints(name: str, vs) -> bytes:
    body = _str_field(1, name)
    body += b"".join(_int_field(8, v) for v in vs)
    return body + _int_field(20, 7)


def _attr_tensor(name: str, arr: np.ndarray) -> bytes:
    return _str_field(1, name) + _len_field(5, _tensor("", arr)) \
        + _int_field(20, 4)


def _node(op: str, inputs, outputs, name: str = "", attrs=()) -> bytes:
    body = b"".join(_str_field(1, i) for i in inputs)
    body += b"".join(_str_field(2, o) for o in outputs)
    if name:
        body += _str_field(3, name)
    body += _str_field(4, op)
    body += b"".join(_len_field(5, a) for a in attrs)
    return body


def _value_info(name: str, shape) -> bytes:
    dims = b""
    for d in shape:
        if d is None:
            dims += _len_field(1, _str_field(2, "N"))
        else:
            dims += _len_field(1, _int_field(1, d))
    tensor_type = _int_field(1, 1) + _len_field(2, dims)
    return _str_field(1, name) + _len_field(2, _len_field(1, tensor_type))


def _graph(nodes, inputs, outputs, initializers) -> bytes:
    body = b"".join(_len_field(1, n) for n in nodes)
    body += _str_field(2, "g")
    body += b"".join(_len_field(5, t) for t in initializers)
    body += b"".join(_len_field(11, v) for v in inputs)
    body += b"".join(_len_field(12, v) for v in outputs)
    return body


def _model(graph: bytes) -> bytes:
    return (_int_field(1, 8) + _str_field(2, "testgen")
            + _len_field(7, graph)
            + _len_field(8, _str_field(1, "") + _int_field(2, 13)))


def _mlp_onnx(rs):
    w1 = rs.randn(4, 16).astype(np.float32)
    b1 = rs.randn(16).astype(np.float32)
    w2 = rs.randn(16, 3).astype(np.float32)
    b2 = rs.randn(3).astype(np.float32)
    nodes = [
        _node("Gemm", ["x", "w1", "b1"], ["h"], "fc1",
              attrs=[_attr_int("transB", 0)]),
        _node("Relu", ["h"], ["hr"], "relu1"),
        _node("Gemm", ["hr", "w2t", "b2"], ["y"], "fc2",
              attrs=[_attr_int("transB", 1)]),
    ]
    graph = _graph(
        nodes,
        inputs=[_value_info("x", [None, 4])],
        outputs=[_value_info("y", [None, 3])],
        initializers=[_tensor("w1", w1), _tensor("b1", b1),
                      _tensor("w2t", w2.T.copy()), _tensor("b2", b2)])
    return _model(graph), (w1, b1, w2, b2)


class TestOnnxMLP:
    def test_forward_matches_numpy(self):
        rs = np.random.RandomState(0)
        data, (w1, b1, w2, b2) = _mlp_onnx(rs)
        model, params, state = load_onnx(data)
        x = rs.randn(8, 4).astype(np.float32)
        import jax
        y, _ = model.call(params, state, x, training=False)
        expected = np.maximum(x @ w1 + b1, 0) @ w2 + b2
        np.testing.assert_allclose(np.asarray(y), expected, rtol=1e-4,
                                   atol=1e-4)

    def test_finetune_frozen_backbone(self):
        """An earlier review's 'done' bar: load ONNX MLP, freeze the
        backbone, fine-tune the head — backbone params must not move."""
        rs = np.random.RandomState(1)
        data, _ = _mlp_onnx(rs)
        model, params, state = load_onnx(data)
        model.compile(optimizer="adam", loss="mse")
        model.freeze(["fc1"])
        est = model.get_estimator()
        est.set_params(params)
        est.set_model_state(state)
        x = rs.randn(32, 4).astype(np.float32)
        y = rs.randn(32, 3).astype(np.float32)
        before = est.get_params()
        model.fit(x, y, batch_size=16, nb_epoch=2)
        after = est.get_params()
        np.testing.assert_array_equal(before["fc1"]["kernel"],
                                      after["fc1"]["kernel"])
        assert np.abs(after["fc2"]["kernel"]
                      - before["fc2"]["kernel"]).max() > 1e-6

    def test_unfreeze_resumes_updates(self):
        rs = np.random.RandomState(2)
        data, _ = _mlp_onnx(rs)
        model, params, state = load_onnx(data)
        model.compile(optimizer="sgd", loss="mse")
        model.freeze()  # everything
        est = model.get_estimator()
        est.set_params(params)
        x = rs.randn(16, 4).astype(np.float32)
        y = rs.randn(16, 3).astype(np.float32)
        before = est.get_params()
        r1 = model.fit(x, y, batch_size=16, nb_epoch=1)
        assert r1["iterations"] >= 1
        mid = est.get_params()
        np.testing.assert_array_equal(before["fc1"]["kernel"],
                                      mid["fc1"]["kernel"])
        np.testing.assert_array_equal(before["fc2"]["kernel"],
                                      mid["fc2"]["kernel"])
        model.unfreeze()
        # nb_epoch is a cumulative MaxEpoch trigger (BigDL semantics): the
        # first fit ended at epoch 2, so train up to epoch 2 now
        r2 = model.fit(x, y, batch_size=16, nb_epoch=2)
        assert r2["iterations"] >= 1
        after = est.get_params()
        assert np.abs(after["fc1"]["kernel"]
                      - mid["fc1"]["kernel"]).max() > 1e-8


class TestOnnxCNN:
    def _cnn_onnx(self, torch_model, h=8, w=8):
        """Hand-encode the ONNX equivalent of a small torch CNN, weights
        taken from the live module — validates conv layout conversion and
        the flatten→Gemm row permutation against torch's NCHW output."""
        sd = {k: v.detach().numpy() for k, v in torch_model.state_dict().items()}
        conv_w = sd["0.weight"]          # OIHW (8,3,3,3)
        conv_b = sd["0.bias"]
        bn_g, bn_b = sd["1.weight"], sd["1.bias"]
        bn_m, bn_v = sd["1.running_mean"], sd["1.running_var"]
        fc_w = sd["5.weight"]            # (5, 8*4*4) torch layout
        fc_b = sd["5.bias"]
        nodes = [
            _node("Conv", ["x", "conv_w", "conv_b"], ["c1"], "conv1", attrs=[
                _attr_ints("kernel_shape", [3, 3]),
                _attr_ints("strides", [1, 1]),
                _attr_ints("pads", [1, 1, 1, 1])]),
            _node("BatchNormalization",
                  ["c1", "bn_g", "bn_b", "bn_m", "bn_v"], ["b1"], "bn1",
                  attrs=[_attr_float("epsilon", 1e-5)]),
            _node("Relu", ["b1"], ["r1"], "relu1"),
            _node("MaxPool", ["r1"], ["p1"], "pool1", attrs=[
                _attr_ints("kernel_shape", [2, 2]),
                _attr_ints("strides", [2, 2])]),
            _node("Flatten", ["p1"], ["f1"], "flat1",
                  attrs=[_attr_int("axis", 1)]),
            _node("Gemm", ["f1", "fc_w", "fc_b"], ["y"], "fc1",
                  attrs=[_attr_int("transB", 1)]),
        ]
        graph = _graph(
            nodes,
            inputs=[_value_info("x", [None, 3, h, w])],
            outputs=[_value_info("y", [None, 5])],
            initializers=[
                _tensor("conv_w", conv_w), _tensor("conv_b", conv_b),
                _tensor("bn_g", bn_g), _tensor("bn_b", bn_b),
                _tensor("bn_m", bn_m), _tensor("bn_v", bn_v),
                _tensor("fc_w", fc_w), _tensor("fc_b", fc_b)])
        return _model(graph)

    def test_cnn_matches_torch(self):
        torch = pytest.importorskip("torch")
        nn = torch.nn
        torch.manual_seed(0)
        m = nn.Sequential(nn.Conv2d(3, 8, 3, padding=1), nn.BatchNorm2d(8),
                          nn.ReLU(), nn.MaxPool2d(2), nn.Flatten(),
                          nn.Linear(8 * 4 * 4, 5))
        m.eval()
        with torch.no_grad():  # fold some running stats in so BN is nontrivial
            m[1].running_mean.uniform_(-0.5, 0.5)
            m[1].running_var.uniform_(0.5, 1.5)
        data = self._cnn_onnx(m)
        model, params, state = load_onnx(data)
        x = np.random.RandomState(3).randn(4, 3, 8, 8).astype(np.float32)
        with torch.no_grad():
            expected = m(torch.from_numpy(x)).numpy()
        # our model is NHWC
        y, _ = model.call(params, state, np.transpose(x, (0, 2, 3, 1)),
                          training=False)
        np.testing.assert_allclose(np.asarray(y), expected, rtol=1e-3,
                                   atol=1e-4)


class TestOnnxNumericEdges:
    def test_averagepool_excludes_padding(self):
        """ONNX default count_include_pad=0: border windows divide by the
        number of REAL elements, not the full kernel area."""
        x = np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)
        nodes = [_node("AveragePool", ["x"], ["y"], "ap", attrs=[
            _attr_ints("kernel_shape", [3, 3]),
            _attr_ints("strides", [1, 1]),
            _attr_ints("pads", [1, 1, 1, 1])])]
        graph = _graph(nodes, inputs=[_value_info("x", [None, 1, 4, 4])],
                       outputs=[_value_info("y", [None, 1, 4, 4])],
                       initializers=[])
        model, params, state = load_onnx(_model(graph))
        y, _ = model.call(params, state, np.transpose(x, (0, 2, 3, 1)))
        # corner (0,0): mean of the 2x2 real block {0,1,4,5} = 2.5 (not /9)
        assert np.isclose(np.asarray(y)[0, 0, 0, 0], 2.5)
        # center (1,1): full 3x3 window mean
        assert np.isclose(np.asarray(y)[0, 1, 1, 0],
                          x[0, 0, 0:3, 0:3].mean())

    def test_reducemean_axes_follow_layout(self):
        """ReduceMean(axes=[2,3]) after a conv = spatial mean in NCHW; the
        NHWC-converted graph must reduce (1,2), yielding (N, C)."""
        rs = np.random.RandomState(7)
        conv_w = rs.randn(5, 3, 1, 1).astype(np.float32)
        fc_w = rs.randn(5, 2).astype(np.float32)
        nodes = [
            _node("Conv", ["x", "w"], ["c"], "conv", attrs=[
                _attr_ints("kernel_shape", [1, 1]),
                _attr_ints("strides", [1, 1])]),
            _node("ReduceMean", ["c"], ["g"], "gap", attrs=[
                _attr_ints("axes", [2, 3]), _attr_int("keepdims", 0)]),
            _node("MatMul", ["g", "fc"], ["y"], "head"),
        ]
        graph = _graph(nodes, inputs=[_value_info("x", [None, 3, 4, 4])],
                       outputs=[_value_info("y", [None, 2])],
                       initializers=[_tensor("w", conv_w),
                                     _tensor("fc", fc_w)])
        model, params, state = load_onnx(_model(graph))
        x = rs.randn(2, 3, 4, 4).astype(np.float32)
        y, _ = model.call(params, state, np.transpose(x, (0, 2, 3, 1)))
        # NCHW reference: 1x1 conv = einsum over channels, then spatial mean
        conv_ref = np.einsum("nchw,oc->nohw", x, conv_w[:, :, 0, 0])
        expected = conv_ref.mean(axis=(2, 3)) @ fc_w
        np.testing.assert_allclose(np.asarray(y), expected, rtol=1e-4,
                                   atol=1e-4)

    def test_clip_zero_min_survives_wire(self):
        """proto3 drops zero scalars from the wire; Clip(min=0) must still
        clip at zero (ReLU6 pattern)."""
        nodes = [_node("Clip", ["x"], ["y"], "clip", attrs=[
            _attr_float("min", 0.0), _attr_float("max", 6.0)])]
        graph = _graph(nodes, inputs=[_value_info("x", [None, 4])],
                       outputs=[_value_info("y", [None, 4])],
                       initializers=[])
        model, params, state = load_onnx(_model(graph))
        x = np.array([[-5.0, -0.5, 3.0, 9.0]], dtype=np.float32)
        y, _ = model.call(params, state, x)
        np.testing.assert_array_equal(np.asarray(y),
                                      [[0.0, 0.0, 3.0, 6.0]])


class TestOnnxExtendedOps:
    def test_shape_gather_concat_reshape_idiom(self):
        """The standard exporter flatten: Reshape(x, Concat(Gather(Shape(x),
        0), [-1])) must fold statically and flatten correctly."""
        rs = np.random.RandomState(0)
        w = rs.randn(5, 12, 2).astype(np.float32)  # conv-free: 3D input
        nodes = [
            _node("Shape", ["x"], ["shp"], "shape0"),
            _node("Gather", ["shp", "zero"], ["b"], "gather0",
                  attrs=[_attr_int("axis", 0)]),
            _node("Unsqueeze", ["b"], ["b1"], "unsq0",
                  attrs=[_attr_ints("axes", [0])]),
            _node("Concat", ["b1", "minus1"], ["tgt"], "cat0",
                  attrs=[_attr_int("axis", 0)]),
            _node("Reshape", ["x", "tgt"], ["flat"], "reshape0"),
            _node("Gemm", ["flat", "wT", "bias"], ["y"], "fc",
                  attrs=[_attr_int("transB", 1)]),
        ]
        fc_w = rs.randn(3, 10).astype(np.float32)
        fc_b = rs.randn(3).astype(np.float32)
        graph = _graph(
            nodes, inputs=[_value_info("x", [None, 5, 2])],
            outputs=[_value_info("y", [None, 3])],
            initializers=[_tensor("zero", np.asarray(0, np.int64)),
                          _tensor("minus1", np.asarray([-1], np.int64)),
                          _tensor("wT", fc_w), _tensor("bias", fc_b)])
        model, params, state = load_onnx(_model(graph))
        x = rs.randn(4, 5, 2).astype(np.float32)
        y, _ = model.call(params, state, x)
        expected = x.reshape(4, 10) @ fc_w.T + fc_b
        np.testing.assert_allclose(np.asarray(y), expected, rtol=1e-4,
                                   atol=1e-4)

    def test_elementwise_and_reductions(self):
        rs = np.random.RandomState(1)
        nodes = [
            _node("Abs", ["x"], ["a"], "abs0"),
            _node("Sqrt", ["a"], ["s"], "sqrt0"),
            _node("ReduceSum", ["s"], ["r"], "rsum",
                  attrs=[_attr_ints("axes", [1]), _attr_int("keepdims", 0)]),
            _node("Neg", ["r"], ["y"], "neg0"),
        ]
        graph = _graph(nodes, inputs=[_value_info("x", [None, 6])],
                       outputs=[_value_info("y", [None])], initializers=[])
        model, params, state = load_onnx(_model(graph))
        x = rs.randn(3, 6).astype(np.float32)
        y, _ = model.call(params, state, x)
        np.testing.assert_allclose(np.asarray(y),
                                   -np.sqrt(np.abs(x)).sum(axis=1),
                                   rtol=1e-5)

    def test_slice_split_minmax(self):
        rs = np.random.RandomState(2)
        nodes = [
            _node("Slice", ["x"], ["sl"], "slice0", attrs=[
                _attr_ints("starts", [1]), _attr_ints("ends", [5]),
                _attr_ints("axes", [1])]),
            _node("Split", ["sl"], ["p1", "p2"], "split0",
                  attrs=[_attr_int("axis", 1), _attr_ints("split", [2, 2])]),
            _node("Max", ["p1", "p2"], ["y"], "max0"),
        ]
        graph = _graph(nodes, inputs=[_value_info("x", [None, 6])],
                       outputs=[_value_info("y", [None, 2])], initializers=[])
        model, params, state = load_onnx(_model(graph))
        x = rs.randn(3, 6).astype(np.float32)
        y, _ = model.call(params, state, x)
        np.testing.assert_allclose(np.asarray(y),
                                   np.maximum(x[:, 1:3], x[:, 3:5]))

    def test_resize_nearest_nhwc(self):
        nodes = [
            _node("Conv", ["x", "w"], ["c"], "conv0", attrs=[
                _attr_ints("kernel_shape", [1, 1]),
                _attr_ints("strides", [1, 1])]),
            _node("Resize", ["c", "roi", "scales"], ["y"], "resize0",
                  attrs=[]),
        ]
        w = np.ones((2, 1, 1, 1), np.float32)
        graph = _graph(
            nodes, inputs=[_value_info("x", [None, 1, 2, 2])],
            outputs=[_value_info("y", [None, 2, 4, 4])],
            initializers=[_tensor("w", w),
                          _tensor("roi", np.zeros(0, np.float32)),
                          _tensor("scales",
                                  np.asarray([1, 1, 2, 2], np.float32))])
        model, params, state = load_onnx(_model(graph))
        x = np.arange(4, dtype=np.float32).reshape(1, 2, 2, 1)
        y, _ = model.call(params, state, x)
        assert np.asarray(y).shape == (1, 4, 4, 2)
        # nearest: each pixel repeats 2x2
        np.testing.assert_array_equal(np.asarray(y)[0, :2, :2, 0],
                                      np.full((2, 2), x[0, 0, 0, 0]))

    def test_strided_and_reversed_slice(self):
        rs = np.random.RandomState(3)
        nodes = [_node("Slice", ["x", "st", "en", "ax", "sp"], ["y"],
                       "slice0")]
        graph = _graph(
            nodes, inputs=[_value_info("x", [None, 6])],
            outputs=[_value_info("y", [None, 3])],
            initializers=[
                _tensor("st", np.asarray([0], np.int64)),
                _tensor("en", np.asarray([6], np.int64)),
                _tensor("ax", np.asarray([1], np.int64)),
                _tensor("sp", np.asarray([2], np.int64))])
        model, params, state = load_onnx(_model(graph))
        x = rs.randn(2, 6).astype(np.float32)
        y, _ = model.call(params, state, x)
        np.testing.assert_allclose(np.asarray(y), x[:, ::2])

    def test_expand_rank_extend(self):
        nodes = [_node("Expand", ["x", "tgt"], ["y"], "exp0")]
        graph = _graph(
            nodes, inputs=[_value_info("x", [None, 3])],
            outputs=[_value_info("y", [None, 2, 3])],
            initializers=[_tensor("tgt", np.asarray([2, 2, 3], np.int64))])
        model, params, state = load_onnx(_model(graph))
        x = np.asarray([[1.0, 2.0, 3.0], [4.0, 5.0, 6.0]], np.float32)
        y, _ = model.call(params, state, x)
        assert np.asarray(y).shape == (2, 2, 3)
        # right-aligned: the [2,3] input tiles along the new middle axis
        np.testing.assert_allclose(np.asarray(y)[0], x)
        np.testing.assert_allclose(np.asarray(y)[1], x)

    def test_max_with_constant(self):
        nodes = [_node("Max", ["x", "floor"], ["y"], "max0")]
        graph = _graph(
            nodes, inputs=[_value_info("x", [None, 3])],
            outputs=[_value_info("y", [None, 3])],
            initializers=[_tensor("floor",
                                  np.asarray([0.5], np.float32))])
        model, params, state = load_onnx(_model(graph))
        x = np.asarray([[-1.0, 0.7, 0.2]], np.float32)
        y, _ = model.call(params, state, x)
        np.testing.assert_allclose(np.asarray(y), [[0.5, 0.7, 0.5]])

    def test_prelu(self):
        nodes = [_node("PRelu", ["x", "slope"], ["y"], "prelu0")]
        graph = _graph(nodes, inputs=[_value_info("x", [None, 3])],
                       outputs=[_value_info("y", [None, 3])],
                       initializers=[_tensor(
                           "slope", np.asarray([0.1, 0.2, 0.3], np.float32))])
        model, params, state = load_onnx(_model(graph))
        x = np.asarray([[-1.0, -1.0, 2.0]], np.float32)
        y, _ = model.call(params, state, x)
        np.testing.assert_allclose(np.asarray(y), [[-0.1, -0.2, 2.0]],
                                   rtol=1e-5)


class TestGlove:
    def test_read_and_build(self, tmp_path):
        from analytics_zoo_tpu.keras.layers import WordEmbedding
        glove = tmp_path / "glove.txt"
        glove.write_text("the 0.1 0.2 0.3\ncat 0.4 0.5 0.6\nsat 0.7 0.8 0.9\n")
        table, index = WordEmbedding.read_glove(str(glove))
        assert table.shape == (4, 3)  # + padding row 0
        np.testing.assert_allclose(table[index["cat"]], [0.4, 0.5, 0.6])
        np.testing.assert_allclose(table[0], 0.0)

    def test_with_word_index(self, tmp_path):
        from analytics_zoo_tpu.keras.layers import WordEmbedding
        glove = tmp_path / "glove.txt"
        glove.write_text("the 0.1 0.2\ncat 0.4 0.5\n")
        table = WordEmbedding.read_glove(str(glove),
                                         {"cat": 1, "unknown": 2})
        assert table.shape == (3, 2)
        np.testing.assert_allclose(table[1], [0.4, 0.5])
        np.testing.assert_allclose(table[2], 0.0)  # missing word stays zero

    def test_multi_token_words_skipped_not_fatal(self, tmp_path):
        """glove.840B-style files contain '. . . 0.1 0.2' lines; loading
        must not abort (and once dim is known, the vector still parses)."""
        from analytics_zoo_tpu.keras.layers import WordEmbedding
        glove = tmp_path / "glove.txt"
        glove.write_text("the 0.1 0.2\n. . . 0.3 0.4\ncat 0.5 0.6\n")
        table, index = WordEmbedding.read_glove(str(glove))
        np.testing.assert_allclose(table[index["cat"]], [0.5, 0.6])
        np.testing.assert_allclose(table[index[". . ."]], [0.3, 0.4])

    def test_layer_from_glove(self, tmp_path):
        import jax
        from analytics_zoo_tpu.keras.layers import WordEmbedding
        glove = tmp_path / "glove.txt"
        glove.write_text("a 1 0\nb 0 1\n")
        layer = WordEmbedding.from_glove(str(glove), {"a": 1, "b": 2})
        params, state = layer.build(jax.random.PRNGKey(0), (None, 2))
        out, _ = layer.call(params, state, np.asarray([[1, 2]]))
        np.testing.assert_allclose(np.asarray(out),
                                   [[[1, 0], [0, 1]]])


class TestTorchImport:
    def test_mlp_state_dict(self):
        torch = pytest.importorskip("torch")
        nn = torch.nn
        torch.manual_seed(1)
        tm = nn.Sequential(nn.Linear(6, 12), nn.ReLU(), nn.Linear(12, 2))
        tm.eval()
        from analytics_zoo_tpu.keras import Sequential
        from analytics_zoo_tpu.keras.layers import Activation, Dense
        model = Sequential([Dense(12, name="d1"), Activation("relu"),
                            Dense(2, name="d2")])
        params, state = load_torch_state_dict(model, tm.state_dict())
        x = np.random.RandomState(4).randn(5, 6).astype(np.float32)
        import jax
        rng = jax.random.PRNGKey(0)
        _, st = model.build(rng, (None, 6))
        y, _ = model.call(params, st, x, training=False)
        with torch.no_grad():
            expected = tm(torch.from_numpy(x)).numpy()
        np.testing.assert_allclose(np.asarray(y), expected, rtol=1e-4,
                                   atol=1e-5)

    def test_cnn_state_dict_with_bn(self):
        torch = pytest.importorskip("torch")
        nn = torch.nn
        torch.manual_seed(2)
        tm = nn.Sequential(nn.Conv2d(3, 4, 3), nn.BatchNorm2d(4), nn.ReLU(),
                           nn.Flatten(), nn.Linear(4 * 6 * 6, 3))
        tm.eval()
        from analytics_zoo_tpu.keras import Sequential
        from analytics_zoo_tpu.keras.layers import (
            Activation, BatchNormalization, Convolution2D, Dense, Flatten)
        model = Sequential([
            Convolution2D(4, 3, 3, name="c1"), BatchNormalization(name="b1"),
            Activation("relu"), Flatten(), Dense(3, name="d1")])
        params, state = load_torch_state_dict(model, tm.state_dict())
        # NHWC flatten order differs from torch's NCHW: permute Dense rows
        h = w = 6
        perm = np.arange(4 * h * w).reshape(4, h, w).transpose(1, 2, 0)
        params["d1"]["kernel"] = params["d1"]["kernel"][perm.reshape(-1)]
        x = np.random.RandomState(5).randn(2, 3, 8, 8).astype(np.float32)
        y, _ = model.call(params, state, np.transpose(x, (0, 2, 3, 1)),
                          training=False)
        with torch.no_grad():
            expected = tm(torch.from_numpy(x)).numpy()
        np.testing.assert_allclose(np.asarray(y), expected, rtol=1e-3,
                                   atol=1e-4)


    def test_nested_container_paths(self):
        """Imported params must nest by container, matching build()'s tree."""
        torch = pytest.importorskip("torch")
        nn = torch.nn
        torch.manual_seed(3)
        tm = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 6),
                           nn.ReLU(), nn.Linear(6, 2))
        tm.eval()
        from analytics_zoo_tpu.keras import Sequential
        from analytics_zoo_tpu.keras.layers import Activation, Dense
        block = Sequential([Dense(8, name="b1"), Activation("relu"),
                            Dense(6, name="b2"), Activation("relu")],
                           name="block")
        model = Sequential([block, Dense(2, name="head")])
        params, state = load_torch_state_dict(model, tm.state_dict())
        assert set(params) == {"block", "head"}
        assert set(params["block"]) == {"b1", "b2"}
        import jax
        _, st = model.build(jax.random.PRNGKey(0), (None, 4))
        x = np.random.RandomState(8).randn(3, 4).astype(np.float32)
        y, _ = model.call(params, st, x, training=False)
        with torch.no_grad():
            expected = tm(torch.from_numpy(x)).numpy()
        np.testing.assert_allclose(np.asarray(y), expected, rtol=1e-4,
                                   atol=1e-5)


class TestGraphSurgery:
    def _model(self):
        from analytics_zoo_tpu.keras import Input, Model
        from analytics_zoo_tpu.keras.layers import Dense
        x = Input(shape=(4,))
        h1 = Dense(8, activation="relu", name="feat1")(x)
        h2 = Dense(6, activation="relu", name="feat2")(h1)
        y = Dense(2, name="head")(h2)
        return Model(x, y)

    def test_new_graph_truncates(self):
        import jax
        model = self._model()
        params, state = model.build(jax.random.PRNGKey(0))
        feat = model.new_graph("feat2")
        x = np.random.RandomState(6).randn(3, 4).astype(np.float32)
        y, _ = feat.call(params, state, x, training=False)
        assert np.asarray(y).shape == (3, 6)
        # embeddings from the truncated graph match the full graph's
        # intermediate (same layers, same params)
        full_out, _ = model.call(params, state, x, training=False)
        assert np.asarray(full_out).shape == (3, 2)

    def test_freeze_up_to(self):
        model = self._model()
        model.freeze_up_to("feat2")
        assert model.frozen_layers == frozenset({"feat1", "feat2"})
        assert model.trainable_param_names() == ["head"]

    def test_new_graph_preserves_frozen(self):
        model = self._model()
        model.freeze(["feat1"])
        feat = model.new_graph("feat2")
        assert "feat1" in feat.frozen_layers


class TestNetFacade:
    def test_load_roundtrip(self, tmp_path):
        from analytics_zoo_tpu.models import NeuralCF
        ncf = NeuralCF(20, 15, 2, user_embed=4, item_embed=4,
                       hidden_layers=[8], mf_embed=2)
        ncf._ensure_built()
        ncf.default_compile()
        path = str(tmp_path / "zoo")
        x = np.stack([np.random.randint(1, 20, 16),
                      np.random.randint(1, 15, 16)], 1).astype(np.float32)
        ncf.model.predict(x)  # force param init
        ncf.save_model(path)
        loaded = Net.load(path)
        assert type(loaded).__name__ == "NeuralCF"
