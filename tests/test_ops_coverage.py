"""Broad op correctness via the OpTest harness (NumPy reference + jit
parity + finite-difference gradients) — the reference's op-unit-test
methodology (`test/legacy_test/op_test.py`) over the TPU build's op surface.
Also locks the coverage number from tools/op_manifest.py.
"""

import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F

from op_test import OpTest

# the checkout of the system this repo was modelled on: not on every machine
needs_reference = pytest.mark.skipif(
    not os.path.isdir("/root/reference"),
    reason="reads /root/reference, which is not on this machine")

rng = np.random.default_rng(7)


def _f(*shape):
    return rng.normal(size=shape).astype("float32")


def _pos(*shape):
    return (rng.random(size=shape).astype("float32") + 0.1)


class TestUnaryOps(OpTest):
    CASES = [
        (paddle.exp, np.exp, _f(3, 4)),
        (paddle.log, np.log, _pos(3, 4)),
        (paddle.sqrt, np.sqrt, _pos(3, 4)),
        (paddle.rsqrt, lambda a: 1 / np.sqrt(a), _pos(3, 4)),
        (paddle.sin, np.sin, _f(3, 4)),
        (paddle.cos, np.cos, _f(3, 4)),
        (paddle.tan, np.tan, _f(3, 4) * 0.3),
        (paddle.asin, np.arcsin, np.clip(_f(3, 4) * 0.5, -0.9, 0.9)),
        (paddle.acos, np.arccos, np.clip(_f(3, 4) * 0.5, -0.9, 0.9)),
        (paddle.atan, np.arctan, _f(3, 4)),
        (paddle.sinh, np.sinh, _f(3, 4)),
        (paddle.cosh, np.cosh, _f(3, 4)),
        (paddle.tanh, np.tanh, _f(3, 4)),
        (paddle.asinh, np.arcsinh, _f(3, 4)),
        (paddle.acosh, np.arccosh, _pos(3, 4) + 1.1),
        (paddle.atanh, np.arctanh, np.clip(_f(3, 4) * 0.5, -0.9, 0.9)),
        (paddle.abs, np.abs, _f(3, 4) + 0.2),
        (paddle.square, np.square, _f(3, 4)),
        (paddle.reciprocal, lambda a: 1 / a, _pos(3, 4)),
        (paddle.sigmoid, lambda a: 1 / (1 + np.exp(-a)), _f(3, 4)),
        (paddle.expm1, np.expm1, _f(3, 4)),
        (paddle.log1p, np.log1p, _pos(3, 4)),
        (paddle.log2, np.log2, _pos(3, 4)),
        (paddle.log10, np.log10, _pos(3, 4)),
        (paddle.erf, None, _f(3, 4)),  # scipy-free: checked vs jax only
    ]

    @pytest.mark.parametrize("case", CASES, ids=lambda c: c[0].__name__)
    def test_unary(self, case):
        fn, ref, x = case
        if ref is None:
            import jax.scipy.special as jsp

            ref = lambda a: np.asarray(jsp.erf(a))  # noqa: E731
        self.check(fn, ref, [x])


class TestBinaryOps(OpTest):
    CASES = [
        (paddle.add, np.add),
        (paddle.subtract, np.subtract),
        (paddle.multiply, np.multiply),
        (paddle.divide, np.divide),
        (paddle.maximum, np.maximum),
        (paddle.minimum, np.minimum),
        (paddle.pow, None),
        (paddle.atan2, np.arctan2),
        (paddle.fmax, np.fmax),
        (paddle.fmin, np.fmin),
    ]

    @pytest.mark.parametrize("case", CASES, ids=lambda c: c[0].__name__)
    def test_binary(self, case):
        fn, ref = case
        x, y = _pos(3, 4), _pos(3, 4)
        if fn is paddle.pow:
            self.check(fn, np.power, [x, y])
        else:
            self.check(fn, ref, [x, y])


class TestReductions(OpTest):
    @pytest.mark.parametrize("fn,ref", [
        (paddle.sum, np.sum), (paddle.mean, np.mean),
        (paddle.max, np.max), (paddle.min, np.min),
        (paddle.prod, np.prod),
    ], ids=lambda f: getattr(f, "__name__", str(f)))
    def test_full_reduce(self, fn, ref):
        self.check(fn, ref, [_pos(3, 4)])

    def test_axis_reduce(self):
        self.check(lambda t: paddle.sum(t, axis=1),
                   lambda a: a.sum(axis=1), [_f(3, 4)])
        self.check(lambda t: paddle.mean(t, axis=0, keepdim=True),
                   lambda a: a.mean(axis=0, keepdims=True), [_f(3, 4)])

    def test_logsumexp_and_norms(self):
        self.check(paddle.logsumexp,
                   lambda a: np.log(np.exp(a).sum()), [_f(3, 4)])
        self.check(lambda t: paddle.linalg.norm(t),
                   lambda a: np.linalg.norm(a), [_f(3, 4)])
        self.check(lambda t: paddle.clip_by_norm(t, 0.5),
                   lambda a: a * min(1.0, 0.5 / np.linalg.norm(a)),
                   [_f(3, 4)])


class TestManipulation(OpTest):
    def test_reshape_transpose_concat(self):
        self.check(lambda t: paddle.reshape(t, [4, 3]),
                   lambda a: a.reshape(4, 3), [_f(3, 4)])
        self.check(lambda t: paddle.transpose(t, [1, 0]),
                   lambda a: a.T, [_f(3, 4)])
        self.check(lambda t: paddle.concat([t, t], axis=0),
                   lambda a: np.concatenate([a, a], 0), [_f(3, 4)])
        self.check(lambda t: paddle.stack([t, t], axis=0)[0],
                   lambda a: a, [_f(3, 4)])
        self.check(lambda t: paddle.flip(t, axis=[0]),
                   lambda a: a[::-1], [_f(3, 4)])
        self.check(lambda t: paddle.roll(t, 1, axis=0),
                   lambda a: np.roll(a, 1, 0), [_f(3, 4)])
        self.check(lambda t: paddle.squeeze(paddle.unsqueeze(t, 0), 0),
                   lambda a: a, [_f(3, 4)])
        self.check(lambda t: paddle.tile(t, [2, 1]),
                   lambda a: np.tile(a, (2, 1)), [_f(3, 4)])

    def test_gather_slice(self):
        idx = np.array([2, 0], "int32")
        self.check(lambda t, i: paddle.gather(t, i),
                   lambda a, i: a[i], [_f(4, 3), idx], grad_inputs=[0])
        self.check(lambda t: paddle.slice(t, [0], [1], [3]),
                   lambda a: a[1:3], [_f(4, 3)])
        self.check(lambda t, i: paddle.index_select(t, i, axis=0),
                   lambda a, i: a[i], [_f(4, 3), idx], grad_inputs=[0])

    def test_new_manipulation_ops(self):
        self.check(lambda t: paddle.diagonal(t),
                   lambda a: np.diagonal(a), [_f(4, 4)])
        self.check(lambda t: paddle.diag_embed(t),
                   lambda a: np.stack([np.diag(r) for r in a]), [_f(3, 4)])
        self.check(lambda t: paddle.fill_diagonal(t, 2.0),
                   lambda a: np.copyto(a.copy(), 2.0,
                                       where=np.eye(4, dtype=bool)) or
                   _fill_diag(a, 2.0), [_f(4, 4)])
        self.check(lambda t: paddle.unstack(t, axis=0)[1],
                   lambda a: a[1], [_f(3, 4)])
        self.check(lambda t: paddle.add_n([t, t]),
                   lambda a: a + a, [_f(3, 4)])
        self.check(lambda t: paddle.reduce_as(t, paddle.zeros([1, 4])),
                   lambda a: a.sum(0, keepdims=True), [_f(3, 4)])


def _fill_diag(a, v):
    out = a.copy()
    np.fill_diagonal(out, v)
    return out


class TestLinalg(OpTest):
    def test_matmuls(self):
        self.check(paddle.matmul, np.matmul, [_f(3, 4), _f(4, 5)])
        self.check(paddle.bmm, np.matmul, [_f(2, 3, 4), _f(2, 4, 5)])
        self.check(lambda i, x, y: paddle.baddbmm(i, x, y, beta=0.5,
                                                  alpha=2.0),
                   lambda i, x, y: 0.5 * i + 2.0 * np.matmul(x, y),
                   [_f(2, 3, 5), _f(2, 3, 4), _f(2, 4, 5)])
        self.check(paddle.dot, lambda a, b: (a * b).sum(-1),
                   [_f(4), _f(4)])
        self.check(paddle.outer, np.outer, [_f(3), _f(4)])

    def test_decompositions(self):
        a = _f(4, 4)
        self.check(lambda t: paddle.svdvals(t),
                   lambda x: np.linalg.svd(x, compute_uv=False), [a],
                   grad=False)
        spd = a @ a.T + 4 * np.eye(4, dtype="float32")
        self.check(lambda t: paddle.linalg.cholesky(t),
                   np.linalg.cholesky, [spd], grad=False, rtol=1e-4)
        self.check(lambda t: paddle.linalg.det(t),
                   np.linalg.det, [spd], grad=False, rtol=1e-4)
        self.check(lambda t: paddle.linalg.inverse(t),
                   np.linalg.inv, [spd], grad=False, rtol=1e-4)

    def test_special_functions(self):
        import scipy.special as sp

        self.check(paddle.gammaln, sp.gammaln, [_pos(3, 4) * 3])
        self.check(paddle.digamma, sp.digamma, [_pos(3, 4) * 3])
        self.check(paddle.i0e, sp.i0e, [_f(3, 4)])
        self.check(paddle.i1e, sp.i1e, [_f(3, 4)])
        self.check(paddle.gammaincc, sp.gammaincc,
                   [_pos(3) * 2, _pos(3) * 2], grad=False)
        self.check(lambda t: paddle.polygamma(t, 1),
                   lambda a: sp.polygamma(1, a), [_pos(3, 4) * 2],
                   grad=False)


class TestActivations(OpTest):
    @pytest.mark.parametrize("fn,ref", [
        (F.relu, lambda a: np.maximum(a, 0)),
        (F.gelu, None),
        (F.silu, lambda a: a / (1 + np.exp(-a))),
        (F.softplus, lambda a: np.log1p(np.exp(a))),
        (F.elu, lambda a: np.where(a > 0, a, np.expm1(a))),
        (F.leaky_relu, lambda a: np.where(a > 0, a, 0.01 * a)),
        (F.hardswish, None),
        (F.mish, None),
        (F.log_sigmoid, lambda a: -np.log1p(np.exp(-a))),
        (F.tanhshrink, lambda a: a - np.tanh(a)),
    ], ids=lambda f: getattr(f, "__name__", "ref"))
    def test_activation(self, fn, ref):
        x = _f(3, 4)
        if ref is None:
            import jax.numpy as jnp

            ref = lambda a: np.asarray(fn(paddle.to_tensor(a)).numpy())  # noqa: E731
        self.check(fn, ref, [x], atol=1e-5)

    def test_softmax_and_swiglu(self):
        def np_softmax(a):
            e = np.exp(a - a.max(-1, keepdims=True))
            return e / e.sum(-1, keepdims=True)

        self.check(F.softmax, np_softmax, [_f(3, 4)])
        self.check(F.log_softmax, lambda a: np.log(np_softmax(a)), [_f(3, 4)])
        self.check(F.swiglu,
                   lambda a: (a[..., :2] / (1 + np.exp(-a[..., :2])))
                   * a[..., 2:], [_f(3, 4)])


class TestNewSignalFft(OpTest):
    def test_fft_round_trip(self):
        x = _f(2, 16)
        self.check(lambda t: paddle.fft.irfft(paddle.fft.rfft(t)),
                   lambda a: a, [x], grad=False, rtol=1e-4, atol=1e-5)
        got = paddle.fft.fft(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(got, np.fft.fft(x), rtol=1e-4, atol=1e-4)

    def test_frame_overlap_add(self):
        x = _f(32)
        fr = paddle.signal.frame(paddle.to_tensor(x), 8, 8)  # no overlap
        np.testing.assert_allclose(
            fr.numpy(), x.reshape(4, 8).T, rtol=1e-6)
        back = paddle.signal.overlap_add(fr, 8)
        np.testing.assert_allclose(back.numpy(), x, rtol=1e-6)

    def test_stft_istft_round_trip(self):
        x = _f(2, 256)
        sp = paddle.signal.stft(paddle.to_tensor(x), 64)
        rec = paddle.signal.istft(sp, 64, length=256)
        np.testing.assert_allclose(rec.numpy(), x, rtol=1e-3, atol=1e-4)


class TestGeometric(OpTest):
    def test_segment_ops(self):
        data = _f(6, 3)
        seg = np.array([0, 0, 1, 1, 2, 2], "int32")
        np.testing.assert_allclose(
            paddle.geometric.segment_sum(
                paddle.to_tensor(data), paddle.to_tensor(seg)).numpy(),
            np.stack([data[:2].sum(0), data[2:4].sum(0), data[4:].sum(0)]),
            rtol=1e-5)
        np.testing.assert_allclose(
            paddle.geometric.segment_mean(
                paddle.to_tensor(data), paddle.to_tensor(seg)).numpy(),
            np.stack([data[:2].mean(0), data[2:4].mean(0),
                      data[4:].mean(0)]), rtol=1e-5)

    def test_send_u_recv_grad(self):
        x = _f(4, 3)
        src = np.array([0, 1, 2, 3], "int32")
        dst = np.array([1, 1, 0, 0], "int32")
        self.check(
            lambda t: paddle.geometric.send_u_recv(
                t, paddle.to_tensor(src), paddle.to_tensor(dst)),
            lambda a: np.stack([a[2] + a[3], a[0] + a[1], np.zeros(3),
                                np.zeros(3)]).astype("float32"),
            [x])


class TestQuantization(OpTest):
    def test_fake_quant_round_trip(self):
        w = _f(8, 4)
        out = paddle.quantization.fake_quantize_dequantize_abs_max(
            paddle.to_tensor(w))
        assert np.abs(out.numpy() - w).max() < np.abs(w).max() / 64

    def test_ste_gradient(self):
        wnp = _f(4, 4)
        w = paddle.to_tensor(wnp)
        w.stop_gradient = False
        out = paddle.quantization.fake_quantize_dequantize_abs_max(w)
        out.sum().backward()
        # straight-through: gradient 1 everywhere except the abs-max entry,
        # which sits exactly on the clip boundary (tie-subgradient 0.5)
        g = w.grad.numpy().ravel()
        k = np.argmax(np.abs(wnp).ravel())
        mask = np.ones(g.size, bool)
        mask[k] = False
        np.testing.assert_allclose(g[mask], 1.0, atol=1e-6)
        assert 0.0 <= g[k] <= 1.0

    def test_weight_only_linear(self):
        x, w = _f(2, 8), _f(8, 4)
        q, s = paddle.quantization.weight_quantize(paddle.to_tensor(w))
        out = paddle.quantization.weight_only_linear(
            paddle.to_tensor(x), q, weight_scale=s)
        np.testing.assert_allclose(out.numpy(), x @ w, rtol=0.1, atol=0.05)


class TestDistributionPkg(OpTest):
    def test_normal_logprob_entropy_kl(self):
        d = paddle.distribution.Normal(1.0, 2.0)
        v = 0.5
        expect = (-((v - 1.0) ** 2) / (2 * 4.0) - np.log(2.0)
                  - 0.5 * np.log(2 * np.pi))
        np.testing.assert_allclose(
            float(d.log_prob(paddle.to_tensor(v)).numpy()), expect,
            rtol=1e-5)
        same = paddle.distribution.Normal(1.0, 2.0)
        np.testing.assert_allclose(
            float(paddle.distribution.kl_divergence(d, same).numpy()), 0.0,
            atol=1e-7)

    def test_sampling_moments(self):
        paddle.seed(0)
        s = paddle.distribution.Normal(3.0, 0.5).sample([20000]).numpy()
        assert abs(s.mean() - 3.0) < 0.05 and abs(s.std() - 0.5) < 0.05
        c = paddle.distribution.Categorical(
            probs=paddle.to_tensor(np.array([0.2, 0.8], "float32")))
        draws = c.sample([10000]).numpy()
        assert abs(draws.mean() - 0.8) < 0.05


@needs_reference
def test_manifest_coverage_locked():
    """The checked-in coverage report must stay truthful and >= the bar."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "op_manifest", os.path.join(os.path.dirname(__file__), "..",
                                    "tools", "op_manifest.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    counts = {}
    for name in m.ref_ops():
        status, where = m.resolve(name, paddle, F)
        counts[status] = counts.get(status, 0) + 1
        assert not where.startswith("BROKEN"), (name, where)
    covered = (counts.get("implemented", 0) + counts.get("alias", 0)
               + counts.get("subsumed", 0))
    assert counts.get("todo", 0) == 0, counts
    # r5 op-tail sweep (VERDICT r4 item 7): FULL coverage of ops.yaml
    assert covered == 474, counts
    assert counts.get("skipped", 0) == 0, counts
    assert counts.get("implemented", 0) >= 327, counts


class TestR4AuditOps(OpTest):
    """Ops implemented in the r4 alias audit (VERDICT r3 item 6): value
    parity vs numpy + finite-difference grad checks where differentiable."""

    def test_sequence_mask(self):
        import paddle_tpu.nn.functional as F

        lens = np.array([2, 0, 5], "int64")
        out = F.sequence_mask(paddle.to_tensor(lens), maxlen=5, dtype="int32")
        expect = (np.arange(5)[None, :] < lens[:, None]).astype("int32")
        np.testing.assert_array_equal(out.numpy(), expect)

    def test_temporal_shift(self):
        import paddle_tpu.nn.functional as F

        x = np.random.default_rng(0).normal(
            size=(4, 8, 2, 2)).astype("float32")

        def ref(a):
            v = a.reshape(2, 2, 8, 2, 2)
            out = np.zeros_like(v)
            out[:, 1:, :2] = v[:, :-1, :2]      # shift from t-1
            out[:, :-1, 2:4] = v[:, 1:, 2:4]    # shift from t+1
            out[:, :, 4:] = v[:, :, 4:]
            return out.reshape(4, 8, 2, 2)

        self.check(lambda t: F.temporal_shift(t, seg_num=2), ref, [x],
                   name="temporal_shift")

    def test_max_unpool2d_roundtrip_and_grad(self):
        import paddle_tpu.nn.functional as F

        x = np.random.default_rng(1).normal(
            size=(2, 3, 8, 8)).astype("float32")
        t = paddle.to_tensor(x)
        t.stop_gradient = False
        out, idx = F.max_pool2d(t, 2, 2, return_mask=True)
        un = F.max_unpool2d(out, idx, 2, 2)
        assert un.shape == [2, 3, 8, 8]
        # every pooled max lands back at its argmax position
        u = un.numpy()
        np.testing.assert_allclose(np.sort(u[u != 0.0]),
                                   np.sort(out.numpy().ravel()), rtol=1e-6)
        # grad flows through pool+unpool to exactly the argmax positions
        un.sum().backward()
        g = t.grad.numpy()
        assert (g.sum(), (g != 0).sum()) == (out.numpy().size,
                                             out.numpy().size)

    def test_margin_cross_entropy_reduces_to_softmax(self):
        import paddle_tpu.nn.functional as F

        # margins (1, 0, 0) at scale s == plain softmax CE over s*cos
        rng = np.random.default_rng(2)
        x = np.tanh(rng.normal(size=(4, 6))).astype("float32")
        y = np.array([0, 2, 4, 5], "int64")
        loss = F.margin_cross_entropy(
            paddle.to_tensor(x), paddle.to_tensor(y), margin1=1.0,
            margin2=0.0, margin3=0.0, scale=8.0)
        z = 8.0 * x
        z = z - z.max(axis=1, keepdims=True)
        logp = z - np.log(np.exp(z).sum(axis=1, keepdims=True))
        expect = -logp[np.arange(4), y].mean()
        np.testing.assert_allclose(float(loss), expect, rtol=1e-5)

    def test_margin_cross_entropy_grad(self):
        import paddle_tpu.nn.functional as F

        rng = np.random.default_rng(3)
        x = np.tanh(rng.normal(size=(3, 5)) * 0.5).astype("float32")
        t = paddle.to_tensor(x)
        t.stop_gradient = False
        loss = F.margin_cross_entropy(t, paddle.to_tensor(
            np.array([0, 1, 2], "int64")))
        loss.backward()
        g = t.grad.numpy()
        assert np.isfinite(g).all() and (g != 0).any()

    def test_hsigmoid_loss_matches_manual_tree(self):
        import paddle_tpu.nn.functional as F

        rng = np.random.default_rng(4)
        x = rng.normal(size=(2, 4)).astype("float32")
        w = rng.normal(size=(3, 4)).astype("float32")  # custom 2-node paths
        pt = np.array([[0, 1], [0, 2]], "int64")
        pc = np.array([[0.0, 1.0], [1.0, 0.0]], "float32")
        y = np.array([0, 1], "int64")
        loss = F.hsigmoid_loss(paddle.to_tensor(x), paddle.to_tensor(y), 4,
                               paddle.to_tensor(w), path_table=pt,
                               path_code=pc)
        expect = []
        for b in range(2):
            tot = 0.0
            for d in range(2):
                logit = float(w[pt[y[b], d]] @ x[b])
                code = float(pc[y[b], d])
                tot += max(logit, 0) - logit * code + \
                    np.log1p(np.exp(-abs(logit)))
            expect.append(tot)
        np.testing.assert_allclose(loss.numpy().ravel(), expect, rtol=1e-5)

    def test_gather_tree_matches_reference_example(self):
        import paddle_tpu.nn.functional as F

        ids = np.array([[[2, 2], [6, 1]], [[3, 9], [6, 1]],
                        [[0, 1], [9, 0]]], "int64")
        parents = np.array([[[0, 0], [1, 1]], [[1, 0], [1, 0]],
                            [[0, 0], [0, 1]]], "int64")
        out = F.gather_tree(paddle.to_tensor(ids),
                            paddle.to_tensor(parents))
        expect = np.array([[[2, 2], [1, 6]], [[3, 3], [6, 1]],
                           [[0, 1], [9, 0]]], "int64")
        np.testing.assert_array_equal(out.numpy(), expect)

    def test_top_p_sampling_respects_nucleus(self):
        probs = np.array([[0.6, 0.3, 0.08, 0.02]] * 64, "float32")
        s, ids = paddle.top_p_sampling(
            paddle.to_tensor(probs),
            paddle.to_tensor(np.full((64,), 0.5, "float32")))
        assert (ids.numpy() == 0).all()  # p=0.5 keeps only the top token
        s, ids = paddle.top_p_sampling(
            paddle.to_tensor(probs),
            paddle.to_tensor(np.full((64,), 0.9, "float32")))
        assert set(np.unique(ids.numpy())) <= {0, 1}

    def test_edit_distance(self):
        d, n = paddle.edit_distance(
            paddle.to_tensor(np.array([[1, 5, 3, 4]], "int64")),
            paddle.to_tensor(np.array([[1, 2, 3]], "int64")),
            normalized=False,
            input_length=paddle.to_tensor(np.array([4], "int64")),
            label_length=paddle.to_tensor(np.array([3], "int64")))
        assert float(d.numpy()[0, 0]) == 2.0  # substitute 5->2, delete 4

    def test_llm_int8_linear(self):
        from paddle_tpu.quantization import llm_int8_linear, weight_quantize

        rng = np.random.default_rng(5)
        w = rng.normal(size=(16, 8)).astype("float32")
        x = rng.normal(size=(4, 16)).astype("float32")
        x[:, 3] = 40.0  # an outlier column
        qw, scale = weight_quantize(paddle.to_tensor(w))
        out = llm_int8_linear(paddle.to_tensor(x), qw, weight_scale=scale)
        np.testing.assert_allclose(out.numpy(), x @ w, rtol=0.05, atol=0.5)

    def test_moe_routing_utils(self):
        from paddle_tpu.incubate.distributed.models.moe import (
            assign_pos, limit_by_capacity, number_count,
            prune_gate_by_capacity)

        g = paddle.to_tensor(np.array([1, 0, 1, 1, 2], "int64"))
        np.testing.assert_array_equal(number_count(g, 3).numpy(), [1, 3, 1])
        pos = assign_pos(g, None).numpy()
        assert list(np.asarray(g.numpy())[pos]) == [0, 1, 1, 1, 2]
        lim = limit_by_capacity(
            paddle.to_tensor(np.array([1, 3, 1], "int64")),
            paddle.to_tensor(np.array([2, 2, 2], "int64")))
        np.testing.assert_array_equal(lim.numpy(), [1, 2, 1])
        pruned = prune_gate_by_capacity(
            g, paddle.to_tensor(np.array([1, 2, 1], "int64")))
        np.testing.assert_array_equal(pruned.numpy(), [1, 0, 1, -1, 2])

    def test_softmax_mask_fuse(self):
        import paddle_tpu.incubate as incubate

        x = np.random.default_rng(6).normal(size=(2, 3, 4)).astype("float32")
        m = np.where(np.arange(4)[None, None, :] < 2, 0.0,
                     -1e9).astype("float32")
        out = incubate.softmax_mask_fuse(paddle.to_tensor(x),
                                         paddle.to_tensor(m))
        assert np.allclose(out.numpy()[..., 2:], 0.0, atol=1e-6)
        ut = incubate.softmax_mask_fuse_upper_triangle(paddle.to_tensor(x))
        assert np.allclose(ut.numpy()[:, 0, 1:], 0.0, atol=1e-6)

    def test_flash_attn_variants_match_dense(self):
        import paddle_tpu.nn.functional as F

        rng = np.random.default_rng(7)
        qkv = rng.normal(size=(2, 8, 3, 2, 16)).astype("float32")
        out, _ = F.flash_attn_qkvpacked(paddle.to_tensor(qkv), causal=True)
        ref, _ = F.flash_attention(paddle.to_tensor(qkv[:, :, 0]),
                                   paddle.to_tensor(qkv[:, :, 1]),
                                   paddle.to_tensor(qkv[:, :, 2]),
                                   causal=True)
        np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=1e-5,
                                   atol=1e-6)
        # varlen: two sequences of lengths 4 and 6, parity per sequence
        tok = rng.normal(size=(10, 2, 16)).astype("float32")
        cu = np.array([0, 4, 10], "int32")
        vout, _ = F.flash_attn_unpadded(
            paddle.to_tensor(tok), paddle.to_tensor(tok),
            paddle.to_tensor(tok), paddle.to_tensor(cu),
            paddle.to_tensor(cu), 6, 6, causal=True)
        for i in range(2):
            seg = tok[cu[i]:cu[i + 1]][None]
            r, _ = F.flash_attention(paddle.to_tensor(seg),
                                     paddle.to_tensor(seg),
                                     paddle.to_tensor(seg), causal=True)
            np.testing.assert_allclose(vout.numpy()[cu[i]:cu[i + 1]],
                                       r.numpy()[0], rtol=1e-5, atol=1e-5)

    def test_tensor_inplace_rng(self):
        t = paddle.zeros([1000])
        t.uniform_(0.0, 1.0)
        a = t.numpy()
        assert 0.0 <= a.min() and a.max() <= 1.0 and a.std() > 0.2
        t.normal_(1.0, 2.0)
        assert abs(t.numpy().mean() - 1.0) < 0.3
        t.exponential_(2.0)
        assert abs(t.numpy().mean() - 0.5) < 0.1


@needs_reference
def test_op_schema_spine():
    """The schema registry (tools/op_schema.py — the TPU build's analogue
    of the reference's single-YAML codegen spine, SURVEY §2.3 L4): parses
    every ops.yaml entry and enforces signature conformance of every
    implemented op against the yaml argument list."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "op_schema", os.path.join(os.path.dirname(__file__), "..",
                                  "tools", "op_schema.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)

    schemas = m.load_schemas()
    assert len(schemas) == 474
    abs_s = schemas["abs"]
    assert [a[1] for a in abs_s.args] == ["x"]
    assert abs_s.backward == "abs_grad"
    assert abs_s.inplace == "x -> out"
    cs = schemas["cumsum"]
    assert [a[1] for a in cs.args] == ["x", "axis", "flatten", "exclusive",
                                       "reverse"]
    assert cs.args[1][2] == "-1"  # parsed default

    checked, violations = m.check_conformance(schemas)
    assert checked >= 280, checked
    assert not violations, violations


class TestR5OpTail:
    """The r5 skip-list sweep (VERDICT r4 item 7): beam_search +
    detection/sequence/recommendation tails, OpTest-style value parity."""

    def test_box_clip(self):
        b = paddle.to_tensor(np.array(
            [[-5., -5, 70, 40], [10, 10, 20, 20]], "float32"))
        info = paddle.to_tensor(np.array([60., 80, 1.0], "float32"))
        out = paddle.vision.ops.box_clip(b, info).numpy()
        np.testing.assert_allclose(out[0], [0, 0, 70, 40])  # w limit 79
        np.testing.assert_allclose(out[1], [10, 10, 20, 20])
        # grad flows (clip subgradient)
        t = paddle.to_tensor(np.array([[1., 1, 5, 5]], "float32"))
        t.stop_gradient = False
        paddle.vision.ops.box_clip(t, info).sum().backward()
        np.testing.assert_allclose(t.grad.numpy(), np.ones((1, 4)))

    def test_bipartite_match(self):
        d = paddle.to_tensor(np.array(
            [[0.9, 0.1, 0.3], [0.2, 0.8, 0.4]], "float32"))
        idx, dist = paddle.vision.ops.bipartite_match(d)
        np.testing.assert_array_equal(idx.numpy(), [0, 1, -1])
        np.testing.assert_allclose(dist.numpy(), [0.9, 0.8, 0.0])
        idx2, dist2 = paddle.vision.ops.bipartite_match(
            d, match_type="per_prediction", dist_threshold=0.35)
        np.testing.assert_array_equal(idx2.numpy(), [0, 1, 1])
        np.testing.assert_allclose(dist2.numpy(), [0.9, 0.8, 0.4])

    def test_collect_fpn_proposals(self):
        r1 = paddle.to_tensor(np.array([[0., 0, 1, 1], [1, 1, 2, 2]],
                                       "float32"))
        r2 = paddle.to_tensor(np.array([[2., 2, 3, 3]], "float32"))
        s1 = paddle.to_tensor(np.array([0.5, 0.9], "float32"))
        s2 = paddle.to_tensor(np.array([0.7], "float32"))
        rois, n = paddle.vision.ops.collect_fpn_proposals(
            [r1, r2], [s1, s2], post_nms_top_n=2)
        np.testing.assert_allclose(rois.numpy(),
                                   [[1, 1, 2, 2], [2, 2, 3, 3]])
        assert int(n.numpy()[0]) == 2

    def test_beam_search_step_and_decode(self):
        V = 4
        pre_ids = paddle.to_tensor(np.array([[1, 2]], "int64"))
        pre_sc = paddle.to_tensor(np.array([[-1.0, -2.0]], "float32"))
        step = np.full((1, 2, V), -10.0, "float32")
        step[0, 0, 2] = -1.5   # beam0 -> token 2: total -1.5
        step[0, 0, 3] = -2.5
        step[0, 1, 1] = -2.1   # beam1 -> token 1
        ids, sc, par = paddle.beam_search(
            pre_ids, pre_sc, None, paddle.to_tensor(step), beam_size=2,
            end_id=0)
        np.testing.assert_array_equal(ids.numpy(), [[2, 1]])
        np.testing.assert_allclose(sc.numpy(), [[-1.5, -2.1]])
        np.testing.assert_array_equal(par.numpy(), [[0, 1]])
        # finished beam keeps end_id at frozen score
        fin_pre = paddle.to_tensor(np.array([[0, 2]], "int64"))
        ids_f, sc_f, _ = paddle.beam_search(
            fin_pre, pre_sc, None, paddle.to_tensor(step), beam_size=2,
            end_id=0)
        assert 0 in ids_f.numpy()
        assert -1.0 in np.round(sc_f.numpy(), 5)
        # decode backtracks parents
        step_ids = paddle.to_tensor(np.array([[[5, 6]], [[7, 8]]], "int64"))
        parents = paddle.to_tensor(np.array([[[0, 1]], [[1, 0]]], "int64"))
        seqs = paddle.beam_search_decode(step_ids, parents).numpy()
        # final beam0 came from parent 1 at t=1: path [6, 7]
        np.testing.assert_array_equal(seqs[0, 0], [6, 7])
        np.testing.assert_array_equal(seqs[0, 1], [5, 8])

    def test_chunk_eval_iob(self):
        # 2 types, IOB: tags B0=0 I0=1 B1=2 I1=3 O=4
        lab = np.array([[0, 1, 4, 2, 3, 3]], "int64")
        inf = np.array([[0, 1, 4, 2, 4, 4]], "int64")  # second chunk wrong
        p, r, f1, ni, nl, nc = paddle.chunk_eval(
            paddle.to_tensor(inf), paddle.to_tensor(lab),
            chunk_scheme="IOB", num_chunk_types=2)
        assert int(ni.numpy()[0]) == 2 and int(nl.numpy()[0]) == 2
        assert int(nc.numpy()[0]) == 1
        np.testing.assert_allclose(p.numpy(), [0.5])
        np.testing.assert_allclose(f1.numpy(), [0.5])

    def test_crf_decoding_viterbi(self):
        # brute-force the argmax path over all 2^4 tag sequences
        import itertools

        rng2 = np.random.default_rng(3)
        em = rng2.normal(size=(1, 4, 2)).astype("float32")
        tr = rng2.normal(size=(4, 2)).astype("float32")
        path = paddle.crf_decoding(paddle.to_tensor(em),
                                   paddle.to_tensor(tr)).numpy()[0]

        def score(p):
            s = tr[0, p[0]] + em[0, 0, p[0]]
            for t in range(1, 4):
                s += tr[2 + p[t - 1], p[t]] + em[0, t, p[t]]
            return s + tr[1, p[-1]]

        best = max(itertools.product([0, 1], repeat=4), key=score)
        np.testing.assert_array_equal(path, best)

    def test_ctc_align(self):
        out, lens = paddle.ctc_align(
            paddle.to_tensor(np.array([[1, 1, 0, 1, 2, 0]], "int64")))
        np.testing.assert_array_equal(out.numpy()[0], [1, 1, 2, 0, 0, 0])
        assert int(lens.numpy()[0]) == 3

    def test_sequence_ops(self):
        x = paddle.to_tensor(np.arange(12, dtype="float32").reshape(1, 3, 4))
        np.testing.assert_allclose(
            paddle.sequence_pool(x, "MAX", lengths=[2]).numpy()[0],
            [4, 5, 6, 7])
        np.testing.assert_allclose(
            paddle.sequence_pool(x, "FIRST").numpy()[0], [0, 1, 2, 3])
        w = paddle.ones([12, 2])
        out = paddle.sequence_conv(x, w, context_length=3)
        assert out.shape == [1, 3, 2]
        # center window at t=1 sees all of t=0..2: sum of all x
        np.testing.assert_allclose(out.numpy()[0, 1, 0],
                                   np.arange(12).sum())
        img = paddle.to_tensor(np.arange(16, dtype="float32").reshape(1, 1, 4, 4))
        seq = paddle.im2sequence(img, (2, 2), (2, 2))
        assert seq.shape == [1, 4, 4]
        np.testing.assert_allclose(seq.numpy()[0, 0], [0, 1, 4, 5])

    def test_affine_channel_and_cvm(self):
        x = paddle.ones([1, 2, 2, 2])
        out = paddle.affine_channel(
            x, paddle.to_tensor(np.array([2., 3], "float32")),
            paddle.to_tensor(np.array([1., -1], "float32")))
        np.testing.assert_allclose(out.numpy()[0, 0], np.full((2, 2), 3.0))
        np.testing.assert_allclose(out.numpy()[0, 1], np.full((2, 2), 2.0))
        emb = paddle.ones([2, 5])
        c = paddle.to_tensor(np.array([[np.e - 1, np.e - 1]] * 2, "float32"))
        v = paddle.cvm(emb, c).numpy()
        np.testing.assert_allclose(v[:, 0], [1.0, 1.0], rtol=1e-6)
        np.testing.assert_allclose(v[:, 1], [0.0, 0.0], atol=1e-6)
        assert paddle.cvm(emb, c, use_cvm=False).shape == [2, 3]

    def test_dgc_family_and_dpsgd(self):
        g = paddle.to_tensor(np.array([3., 4], "float32"))
        clipped = paddle.dgc_clip_by_norm(g, max_norm=1.0).numpy()
        np.testing.assert_allclose(np.linalg.norm(clipped), 1.0, rtol=1e-6)
        u = paddle.zeros([4]); v = paddle.zeros([4])
        gg = paddle.to_tensor(np.array([1., -5, 2, 0.5], "float32"))
        nu, nv, kg, mask = paddle.dgc(u, v, gg, ratio=0.25)
        np.testing.assert_allclose(kg.numpy(), [0, -5, 0, 0])
        np.testing.assert_allclose(nv.numpy(), [1, 0, 2, 0.5])
        p0 = paddle.ones([4])
        pout, vel = paddle.dgc_momentum(p0, gg, paddle.zeros([4]),
                                        learning_rate=1.0, mu=0.9,
                                        current_step=0,
                                        rampup_begin_step=10)
        # pre-rampup: plain momentum step (v=g) -> p - lr*v
        np.testing.assert_allclose(pout.numpy(),
                                   p0.numpy() - gg.numpy(), rtol=1e-6)
        p = paddle.dpsgd(paddle.ones([4]), gg, learning_rate=0.1,
                         clip=1.0, sigma=0.0)
        assert np.all(np.isfinite(p.numpy()))

    def test_yolo_box_shapes_and_range(self):
        rng = np.random.default_rng(0)
        x = paddle.to_tensor(rng.normal(size=(2, 3 * 7, 4, 4)).astype("float32"))
        boxes, scores = paddle.vision.ops.yolo_box(
            x, paddle.to_tensor(np.array([[32., 32]] * 2, "float32")),
            anchors=[10, 13, 16, 30, 33, 23], class_num=2,
            downsample_ratio=8)
        assert boxes.shape == [2, 48, 4] and scores.shape == [2, 48, 2]
        b = boxes.numpy()
        assert b.min() >= 0 and b.max() <= 31  # clipped to the image
        s = scores.numpy()
        assert s.min() >= 0 and s.max() <= 1

    def test_matrix_and_multiclass_nms(self):
        bb = paddle.to_tensor(np.array(
            [[[0., 0, 10, 10], [0, 0, 10.5, 10.5], [50, 50, 60, 60]]],
            "float32"))
        sc = paddle.to_tensor(np.array([[[0.9, 0.8, 0.7]]], "float32"))
        out, n = paddle.vision.ops.multiclass_nms3(
            bb, sc, nms_threshold=0.5, background_label=-1)
        assert int(n.numpy()[0]) == 2  # near-duplicate suppressed
        np.testing.assert_allclose(sorted(out.numpy()[:, 1]), [0.7, 0.9])
        m_out, m_n = paddle.vision.ops.matrix_nms(
            bb, sc, score_threshold=0.1, post_threshold=0.0,
            background_label=-1)
        m = m_out.numpy()
        assert int(m_n.numpy()[0]) == 3
        # the overlapping det's score decays, the isolated one doesn't
        decayed = m[np.isclose(m[:, 2], 0).nonzero()[0]]
        assert (m[:, 1] <= 0.91).all() and len(decayed) == 2
        assert m[:, 1].min() < 0.7

    def test_generate_proposals_and_psroi(self):
        rng = np.random.default_rng(1)
        sc = paddle.to_tensor(rng.random((1, 2, 3, 3)).astype("float32"))
        bd = paddle.to_tensor(
            (rng.normal(0, 0.05, (1, 8, 3, 3))).astype("float32"))
        anchors = paddle.to_tensor(np.tile(
            np.array([[0., 0, 12, 12], [2, 2, 20, 20]], "float32"), (9, 1)))
        var = paddle.to_tensor(np.full((18, 4), 0.1, "float32"))
        rois, n = paddle.vision.ops.generate_proposals(
            sc, bd, paddle.to_tensor(np.array([[24., 24]], "float32")),
            anchors, var, pre_nms_top_n=10, post_nms_top_n=4,
            nms_thresh=0.5)
        assert rois.shape[1] == 4 and int(n.numpy()[0]) == rois.shape[0] <= 4
        r = rois.numpy()
        assert r.min() >= 0 and r.max() <= 23
        x = paddle.to_tensor(rng.normal(
            size=(1, 2 * 2 * 2, 6, 6)).astype("float32"))
        out = paddle.vision.ops.psroi_pool(
            x, paddle.to_tensor(np.array([[0., 0, 6, 6]], "float32")),
            np.array([1]), 2)
        assert out.shape == [1, 2, 2, 2]

    def test_fractional_max_pool(self):
        import paddle_tpu.nn.functional as F

        x = paddle.to_tensor(np.arange(36, dtype="float32").reshape(1, 1, 6, 6))
        o = F.fractional_max_pool2d(x, output_size=2, random_u=0.4)
        assert o.shape == [1, 1, 2, 2]
        assert float(o.numpy().max()) == 35.0  # bottom-right bin max
        o3 = F.fractional_max_pool3d(
            paddle.to_tensor(np.arange(27, dtype="float32").reshape(1, 1, 3, 3, 3)),
            output_size=2, random_u=0.6)
        assert o3.shape == [1, 1, 2, 2, 2]

    def test_ps_ftrl_rule(self):
        from paddle_tpu.distributed.ps import SparseTable

        t = SparseTable(dim=4, optimizer="ftrl", lr=0.5, l1=0.0, l2=0.0,
                        initializer="zeros")
        ids = np.array([1, 2], np.int64)
        g = np.ones((2, 4), np.float32)
        t.pull(ids)
        for _ in range(3):
            t.push(ids, g)
        rows = t.pull(ids, record_show=False)
        assert (rows < 0).all()  # descended against +grads
        st = t.state()
        assert "slot_z" in st and "slot_n" in st
        t2 = SparseTable(dim=4, optimizer="ftrl", lr=0.5,
                         initializer="zeros")
        t2.load_state(st)
        np.testing.assert_allclose(t2.pull(ids, record_show=False), rows)


def test_beam_search_remap_respects_finished():
    """The optional candidate remap must not resurrect a finished beam
    (review finding): a finished parent's selection stays end_id."""
    V = 3
    pre_ids = paddle.to_tensor(np.array([[0, 2]], "int64"))  # beam0 done
    pre_sc = paddle.to_tensor(np.array([[-0.5, -2.0]], "float32"))
    step = np.full((1, 2, V), -10.0, "float32")
    step[0, 1, 1] = -2.2
    remap = paddle.to_tensor(np.full((1, 2, V), 9, "int64"))
    ids, sc, par = paddle.beam_search(
        pre_ids, pre_sc, remap, paddle.to_tensor(step), beam_size=2,
        end_id=0)
    i, s, p = ids.numpy()[0], sc.numpy()[0], par.numpy()[0]
    # the finished beam's continuation is end_id at the frozen score
    fin = np.where(np.isclose(s, -0.5))[0]
    assert len(fin) == 1 and i[fin[0]] == 0, (i, s)
    live = np.where(np.isclose(s, -2.2))[0]
    assert len(live) == 1 and i[live[0]] == 9 and p[live[0]] == 1


def test_r5_review_semantics_fixes():
    """Review-driven semantics checks: yolo_box iou-aware channel layout,
    IOBES back-to-back chunks, anchored device-time attribution."""
    # iou_aware: A iou channels FIRST (reference GetIoUIndex), then conv
    rng2 = np.random.default_rng(5)
    A, C, H, W = 2, 1, 2, 2
    conv = rng2.normal(size=(1, A * (5 + C), H, W)).astype("float32")
    x_plain = paddle.to_tensor(conv)
    iou_ch = np.full((1, A, H, W), 50.0, "float32")  # sigmoid -> 1.0
    x_aware = paddle.to_tensor(np.concatenate([iou_ch, conv], axis=1))
    img = paddle.to_tensor(np.array([[16., 16]], "float32"))
    kw = dict(anchors=[4, 4, 8, 8], class_num=C, downsample_ratio=8)
    b0, s0 = paddle.vision.ops.yolo_box(x_plain, img, **kw)
    b1, s1 = paddle.vision.ops.yolo_box(x_aware, img, iou_aware=True,
                                        iou_aware_factor=0.0, **kw)
    # factor 0 + iou==1: scores and boxes must equal the plain decode
    np.testing.assert_allclose(b1.numpy(), b0.numpy(), rtol=1e-5)
    np.testing.assert_allclose(s1.numpy(), s0.numpy(), rtol=1e-4)

    # IOBES: E closes the chunk — [B0 E0 B0 E0] is TWO chunks
    lab = np.array([[0, 2, 0, 2]], "int64")  # B0=0 I0=1 E0=2 S0=3
    p, r, f1, ni, nl, nc = paddle.chunk_eval(
        paddle.to_tensor(lab), paddle.to_tensor(lab),
        chunk_scheme="IOBES", num_chunk_types=1)
    assert int(nl.numpy()[0]) == 2 and int(nc.numpy()[0]) == 2

    # anchored device attribution: relu must not absorb relu6
    from paddle_tpu.profiler.profiler_statistic import StatisticData

    data = StatisticData({"relu": [0.001], "relu6": [0.001]}, {}, [],
                         device_events={"jit_relu": [1.0],
                                        "jit_relu6": [2.0]},
                         device_total=3.0)
    np.testing.assert_allclose(data.device_for_op("relu"), 1.0)
    np.testing.assert_allclose(data.device_for_op("relu6"), 2.0)


@needs_reference
def test_op_schema_default_conformance():
    """Default-VALUE conformance against ops.yaml (r5: the drift class
    signature-name conformance can't catch — a wrapper silently shipping a
    different default). Divergences must be audited entries in
    _DEFAULT_DIVERGENCES with a reference-python justification."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "op_schema", os.path.join(os.path.dirname(__file__), "..",
                                  "tools", "op_schema.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    checked, violations = m.check_default_conformance()
    assert checked >= 280, checked
    assert not violations, violations


class TestR5OpTailBatch2:
    """Second op-tail sweep: PS recommendation, graph sampling, RNN-T,
    deformable conv, correlation — 471/474 covered."""

    def test_batch_fc_and_match_matrix(self):
        s, B, i, o = 2, 3, 4, 5
        x = paddle.to_tensor(_f(s, B, i))
        w = paddle.to_tensor(_f(s, i, o))
        b = paddle.to_tensor(_f(s, o))
        out = paddle.batch_fc(x, w, b)
        want = np.einsum("sbi,sio->sbo", x.numpy(), w.numpy()) \
            + b.numpy()[:, None]
        np.testing.assert_allclose(out.numpy(), want, rtol=1e-5)

        xm = paddle.to_tensor(_f(2, 3, 4))
        ym = paddle.to_tensor(_f(2, 5, 4))
        wm = paddle.to_tensor(_f(4, 2, 4))
        mm, tmp = paddle.match_matrix_tensor(xm, ym, wm, dim_t=2)
        want_mm = np.einsum("bid,dte,bje->btij", xm.numpy(), wm.numpy(),
                            ym.numpy())
        np.testing.assert_allclose(mm.numpy(), want_mm, rtol=1e-5)

    def test_rank_attention(self):
        # 2 instances; max_rank=2; param blocks distinguishable
        x = paddle.to_tensor(np.array([[1., 0], [0, 1]], "float32"))
        # inst 0: rank 1, neighbours: (rank 1 -> row 0), (rank 2 -> row 1)
        # inst 1: rank 2, one valid neighbour (rank 1 -> row 0)
        ro = paddle.to_tensor(np.array(
            [[1, 1, 0, 2, 1],
             [2, 1, 0, 0, 0]], "int64"))
        P = np.zeros((2 * 2 * 2, 1), "float32")
        # block (lower, faster) rows: block idx b -> rows [b*2, b*2+2)
        P[0:2, 0] = [1, 10]      # block (1,1): picks x -> 1*x0 + 10*x1
        P[2:4, 0] = [100, 1000]  # block (1,2)
        P[4:6, 0] = [7, 70]      # block (2,1)
        out = paddle.rank_attention(x, ro, paddle.to_tensor(P), max_rank=2)
        # inst0 = x[0] @ block(1,1) + x[1] @ block(1,2) = 1 + 1000
        # inst1 = x[0] @ block(2,1) = 7
        np.testing.assert_allclose(out.numpy(), [[1001.0], [7.0]])

    def test_tdm_and_class_center(self):
        # tree: rows [item, layer, parent, c0, c1]
        ti = np.array([[0, 0, 0, 0, 0],     # node 0 unused
                       [0, 0, 0, 2, 3],     # node 1: children 2, 3
                       [5, 1, 1, 0, 0],     # node 2: leaf (item 5)
                       [0, 1, 1, 4, 0],     # node 3: internal
                       [9, 2, 3, 0, 0]], "int64")
        child, leaf = paddle.tdm_child(
            paddle.to_tensor(np.array([1, 3], "int64")),
            paddle.to_tensor(ti), child_nums=2)
        np.testing.assert_array_equal(child.numpy(), [[2, 3], [4, 0]])
        np.testing.assert_array_equal(leaf.numpy(), [[1, 0], [1, 0]])

        travel = paddle.to_tensor(np.array([[1, 2]], "int64"))
        layer = paddle.to_tensor(np.array([1, 6, 2, 7, 8], "int64"))
        out, lab, mask = paddle.tdm_sampler(
            paddle.to_tensor(np.array([[5]], "int64")), travel, layer,
            neg_samples_num_list=[1, 1], layer_offset=[0, 2, 5], seed=3)
        o = out.numpy()[0]
        assert o[0] == 1 and o[2] == 2          # positives in place
        assert o[1] in (6,) and o[3] in (7, 8)  # negatives != positive
        np.testing.assert_array_equal(lab.numpy()[0], [1, 0, 1, 0])

        rl, centers = paddle.class_center_sample(
            paddle.to_tensor(np.array([3, 7, 3], "int64")),
            num_classes=10, num_samples=5, fix_seed=True, seed=0)
        c = centers.numpy()
        assert 3 in c and 7 in c and len(c) == 5
        np.testing.assert_array_equal(
            rl.numpy(), [np.where(c == 3)[0][0], np.where(c == 7)[0][0],
                         np.where(c == 3)[0][0]])

    def test_merge_selected_rows(self):
        from paddle_tpu.ops.legacy_ps import SelectedRows

        sr = SelectedRows([2, 0, 2], np.array([[1., 1], [2, 2], [3, 3]],
                                              "float32"), height=4)
        m = paddle.merge_selected_rows(sr)
        np.testing.assert_array_equal(m.rows, [0, 2])
        np.testing.assert_allclose(m.value.numpy(), [[2, 2], [4, 4]])

    def test_correlation_value_parity(self):
        rng2 = np.random.default_rng(1)
        a = rng2.normal(size=(1, 3, 6, 6)).astype("float32")
        b = rng2.normal(size=(1, 3, 6, 6)).astype("float32")
        out = paddle.vision.ops.correlation(
            paddle.to_tensor(a), paddle.to_tensor(b), pad_size=1,
            max_displacement=1).numpy()[0]  # [9, 6, 6]
        # direct per-displacement check: channel 4 is (dy, dx) = (0, 0),
        # channel 5 is (0, +1)
        np.testing.assert_allclose(out[4], (a[0] * b[0]).mean(0), rtol=1e-5)
        ap = np.pad(a[0], ((0, 0), (1, 1), (1, 1)))
        bp = np.pad(b[0], ((0, 0), (1, 1), (1, 1)))
        want = (ap * np.roll(bp, -1, axis=2)).mean(0)[1:7, 1:7]
        np.testing.assert_allclose(out[5], want, rtol=1e-5, atol=1e-6)

    def test_deform_conv2d_zero_offset_is_conv(self):
        import jax

        rng2 = np.random.default_rng(2)
        x = paddle.to_tensor(rng2.normal(size=(2, 4, 6, 6)).astype("float32"))
        w = paddle.to_tensor(rng2.normal(0, 0.2, (5, 4, 3, 3)).astype("float32"))
        off = paddle.zeros([2, 18, 4, 4])
        out = paddle.vision.ops.deform_conv2d(x, off, w)
        ref = jax.lax.conv_general_dilated(
            x.numpy(), w.numpy(), (1, 1), "VALID",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        np.testing.assert_allclose(out.numpy(), np.asarray(ref), rtol=2e-4,
                                   atol=1e-4)
        # v2 modulation at 0.5 halves the zero-offset output
        m = paddle.ones([2, 9, 4, 4]) * 0.5
        out2 = paddle.vision.ops.deform_conv2d(x, off, w, mask=m)
        np.testing.assert_allclose(out2.numpy(), 0.5 * np.asarray(ref),
                                   rtol=2e-4, atol=1e-4)

    def test_graph_sampling(self):
        row = paddle.to_tensor(np.array([1, 2, 3, 0, 0], "int64"))
        colptr = paddle.to_tensor(np.array([0, 3, 4, 5, 5], "int64"))
        out, cnt = paddle.geometric.graph_sample_neighbors(
            row, colptr, paddle.to_tensor(np.array([0, 3], "int64")),
            sample_size=2)
        assert cnt.numpy().tolist() == [2, 0]
        assert set(out.numpy()) <= {1, 2, 3}
        w = paddle.to_tensor(np.array([1., 1000., 1, 1, 1], "float32"))
        hits = 0
        for _ in range(10):
            o2, _ = paddle.geometric.weighted_sample_neighbors(
                row, colptr, w,
                paddle.to_tensor(np.array([0], "int64")), sample_size=1)
            hits += int(o2.numpy()[0] == 2)
        assert hits >= 8  # weight-1000 edge dominates
        s, d, si, rx = paddle.geometric.graph_khop_sampler(
            row, colptr, paddle.to_tensor(np.array([0], "int64")),
            sample_sizes=[-1, -1])
        assert si.numpy().tolist() == [0, 1, 2, 3]
        assert rx.numpy().tolist() == [0]
        # edges are (neighbor -> frontier) in local ids
        assert d.numpy()[:3].tolist() == [0, 0, 0]

    def test_warprnnt_brute_force(self):
        import itertools

        rng2 = np.random.default_rng(4)
        T, U, V = 3, 2, 4
        logits = rng2.normal(size=(1, T, U + 1, V)).astype("float32")
        lab = np.array([[1, 2]], "int64")

        def lsm(v):
            m = v.max(-1, keepdims=True)
            return v - m - np.log(np.exp(v - m).sum(-1, keepdims=True))

        lp = lsm(logits)[0]
        tot = -np.inf
        for perm in set(itertools.permutations("b" * (T - 1) + "e" * U)):
            t = u = 0
            sc = 0.0
            for mv in perm:
                if mv == "b":
                    sc += lp[t, u, 0]
                    t += 1
                else:
                    sc += lp[t, u, lab[0, u]]
                    u += 1
            sc += lp[T - 1, U, 0]
            tot = np.logaddexp(tot, sc)
        got = F.warprnnt(paddle.to_tensor(logits), paddle.to_tensor(lab),
                         paddle.to_tensor(np.array([T], "int64")),
                         paddle.to_tensor(np.array([U], "int64")))
        np.testing.assert_allclose(float(got.numpy()[0]), -tot, rtol=1e-5)

    def test_read_and_decode(self, tmp_path):
        import io

        from PIL import Image

        img = Image.fromarray(
            (np.arange(64).reshape(8, 8) * 4).astype(np.uint8), "L")
        buf = io.BytesIO()
        img.save(buf, format="JPEG")
        p = str(tmp_path / "t.jpg")
        open(p, "wb").write(buf.getvalue())
        raw = paddle.vision.ops.read_file(p)
        assert raw.numpy().dtype == np.uint8 and raw.shape[0] > 0
        dec = paddle.vision.ops.decode_jpeg(raw)
        assert dec.shape == [1, 8, 8]


def test_final_three_ops():
    """The last skips: pyramid_hash, yolo_box_head, yolo_box_post —
    coverage is now 474/474."""
    rng2 = np.random.default_rng(6)
    # pyramid_hash: deterministic, correct chunk structure
    w = paddle.to_tensor(rng2.normal(size=(64 + 4, 1)).astype("float32"))
    x = paddle.to_tensor(np.array([3, 7, 7, 2], "int64"))
    out = paddle.pyramid_hash(x, w, num_emb=8, space_len=64,
                              pyramid_layer=2, rand_len=4)
    # n-grams: len2 x3 + len3 x2 = 5 terms
    assert out.shape == [5, 8]
    out2 = paddle.pyramid_hash(x, w, num_emb=8, space_len=64,
                               pyramid_layer=2, rand_len=4)
    np.testing.assert_allclose(out.numpy(), out2.numpy())  # deterministic
    # identical n-grams hash identically: terms (7,7) appear once, but
    # x[1:3] == [7,7] ... use a repeated sequence
    xr = paddle.to_tensor(np.array([5, 5, 5], "int64"))
    o3 = paddle.pyramid_hash(xr, w, num_emb=8, space_len=64,
                             pyramid_layer=1, rand_len=4)
    np.testing.assert_allclose(o3.numpy()[0], o3.numpy()[1])

    # yolo_box_head: sigmoid on xy/obj/cls, w/h untouched
    xh = paddle.to_tensor(rng2.normal(size=(1, 2 * 7, 3, 3)).astype("float32"))
    oh = paddle.vision.ops.yolo_box_head(xh, anchors=[1, 2, 3, 4],
                                         class_num=2).numpy()
    f_in = xh.numpy().reshape(1, 2, 7, 3, 3)
    f_out = oh.reshape(1, 2, 7, 3, 3)
    np.testing.assert_allclose(f_out[:, :, 2:4], f_in[:, :, 2:4])  # raw wh
    np.testing.assert_allclose(f_out[:, :, 4],
                               1 / (1 + np.exp(-f_in[:, :, 4])), rtol=1e-5)

    # yolo_box_post: three levels -> packed detections + counts
    def head(hw):
        return paddle.to_tensor(
            rng2.normal(0, 0.5, (1, 3 * 7, hw, hw)).astype("float32"))

    out, n = paddle.vision.ops.yolo_box_post(
        head(2), head(4), head(8),
        paddle.to_tensor(np.array([[64., 64]], "float32")),
        paddle.to_tensor(np.array([1.0], "float32")),
        anchors0=[10, 13, 16, 30, 33, 23],
        anchors1=[10, 13, 16, 30, 33, 23],
        anchors2=[10, 13, 16, 30, 33, 23],
        class_num=2, conf_thresh=0.3, downsample_ratio0=32,
        downsample_ratio1=16, downsample_ratio2=8)
    o = out.numpy()
    assert o.ndim == 2 and o.shape[1] == 6
    assert int(n.numpy()[0]) == o.shape[0]
    if len(o):
        assert set(np.unique(o[:, 0])) <= {0.0, 1.0}  # labels
