"""Fused chunked linear + cross-entropy (models/llama_functional.py).

The `loss_chunk` path used to be a remat trick around full-vocab logits;
it is now a custom_vjp that streams [b, chunk, vocab] tiles and stores
d(hidden)/d(lm_head) as forward residuals, so the [b, s, vocab] logits
tensor never exists in forward OR backward and the backward never
re-runs the vocab matmul. These tests pin:

- loss parity vs the unchunked `parallel_cross_entropy` reference
  (f32 exact-ish, bf16 loose), any chunk size incl. s % chunk != 0;
- gradient parity vs jax autodiff of the unchunked composite, plus the
  OpTest-style central finite-difference probe check;
- the memory claim itself: no [b, s, vocab]-shaped intermediate in the
  fwd+bwd jaxpr (the CPU-verifiable form of the HLO evidence);
- the vocab-parallel regression: mp_axis used to be silently ignored by
  the chunked path (head sharded over 'mp' gave a local-shard loss);
  fused CE under shard_map must match the unsharded reference with
  grads taken INSIDE the shard_map (the engine's pattern).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import llama_functional as lf

from op_test import OpTest

ARGS = lf.LlamaArgs(vocab_size=160, hidden_size=48, intermediate_size=128,
                    num_layers=2, num_heads=4, num_kv_heads=4,
                    rope_theta=10000.0, rms_eps=1e-6, use_flash=False)


def _inputs(b=2, s=24, dtype=jnp.float32, seed=0):
    kh, kw, kl = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = (jax.random.normal(kh, (b, s, ARGS.hidden_size)) * 0.5).astype(dtype)
    head = (jax.random.normal(kw, (ARGS.hidden_size, ARGS.vocab_size))
            * 0.05).astype(dtype)
    labels = jax.random.randint(kl, (b, s), 0, ARGS.vocab_size)
    return h, head, labels


def _ref_loss(h, head, labels):
    logits = h @ head
    return lf.parallel_cross_entropy(logits, labels, ARGS, None, 1)


class TestFusedCEParity:
    @pytest.mark.parametrize("chunk", [8, 13, 24, 64])
    def test_loss_matches_unchunked_f32(self, chunk):
        """Any chunk size, including odd remainders (24 % 13 = 11) and
        chunk > s."""
        h, head, labels = _inputs()
        ref = _ref_loss(h, head, labels)
        got = lf.fused_linear_cross_entropy(h, head, labels, ARGS,
                                            None, 1, chunk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("chunk", [8, 13])
    def test_grads_match_autodiff_f32(self, chunk):
        h, head, labels = _inputs()
        ref_dh, ref_dw = jax.grad(_ref_loss, argnums=(0, 1))(h, head, labels)
        dh, dw = jax.grad(
            lambda a, w: lf.fused_linear_cross_entropy(
                a, w, labels, ARGS, None, 1, chunk),
            argnums=(0, 1))(h, head)
        np.testing.assert_allclose(np.asarray(dh), np.asarray(ref_dh),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(ref_dw),
                                   rtol=1e-5, atol=1e-6)

    def test_cotangent_scaling(self):
        """bwd must scale by the incoming cotangent, not assume g=1."""
        h, head, labels = _inputs()
        g1 = jax.grad(lambda a: lf.fused_linear_cross_entropy(
            a, head, labels, ARGS, None, 1, 8))(h)
        g3 = jax.grad(lambda a: 3.0 * lf.fused_linear_cross_entropy(
            a, head, labels, ARGS, None, 1, 8))(h)
        np.testing.assert_allclose(np.asarray(g3), 3 * np.asarray(g1),
                                   rtol=1e-6, atol=1e-7)

    def test_fd_gradcheck(self):
        """OpTest-style central finite differences on random coordinates
        of h and lm_head (op_test.py check_grad's numeric jacobian)."""
        t = OpTest()
        h, head, labels = _inputs(b=1, s=8)
        fused = jax.jit(lambda a, w: lf.fused_linear_cross_entropy(
            a, w, labels, ARGS, None, 1, 4))
        grads = jax.grad(fused, argnums=(0, 1))(h, head)
        rng = np.random.default_rng(0)
        for i, x in enumerate((h, head)):
            g = np.asarray(grads[i], dtype="float64")
            flat = np.asarray(x, dtype="float64").ravel()
            probes = rng.choice(flat.size, size=t.n_probe, replace=False)
            for j in probes:
                delta = np.zeros_like(flat)
                delta[j] = t.fd_eps
                xp = jnp.asarray((flat + delta).reshape(x.shape),
                                 dtype=x.dtype)
                xm = jnp.asarray((flat - delta).reshape(x.shape),
                                 dtype=x.dtype)
                args_p = (xp, head) if i == 0 else (h, xp)
                args_m = (xm, head) if i == 0 else (h, xm)
                fd = (float(fused(*args_p)) - float(fused(*args_m))) \
                    / (2 * t.fd_eps)
                np.testing.assert_allclose(
                    g.ravel()[j], fd, rtol=t.grad_rtol, atol=t.grad_atol,
                    err_msg=f"fused CE grad[{i}][{j}]")

    def test_bf16_dtypes_and_parity(self):
        """Loss accumulates in f32 regardless of input dtype; grads come
        back in the params' bf16."""
        h, head, labels = _inputs(dtype=jnp.bfloat16)
        loss, (dh, dw) = jax.value_and_grad(
            lambda a, w: lf.fused_linear_cross_entropy(
                a, w, labels, ARGS, None, 1, 8), argnums=(0, 1))(h, head)
        assert loss.dtype == jnp.float32
        assert dh.dtype == jnp.bfloat16 and dw.dtype == jnp.bfloat16
        ref = _ref_loss(h.astype(jnp.float32), head.astype(jnp.float32),
                        labels)
        np.testing.assert_allclose(float(loss), float(ref), rtol=2e-2)

    def test_under_jit_and_remainder(self):
        h, head, labels = _inputs(s=21)
        got = jax.jit(lambda a, w: lf.fused_linear_cross_entropy(
            a, w, labels, ARGS, None, 1, 8))(h, head)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(_ref_loss(h, head, labels)),
                                   rtol=1e-6, atol=1e-6)


class TestNoLogitsBuffer:
    def test_no_full_logits_intermediate_in_jaxpr(self):
        """The acceptance claim, in its CPU-checkable form: the fwd+bwd
        jaxpr of the fused loss contains NO [b, s, vocab] value anywhere
        (the scan works on [b, chunk, vocab] tiles) — checked with the
        shared analysis walker, which descends into custom_vjp/scan/
        shard_map subjaxprs. The unchunked reference trips this check,
        proving the probe has teeth."""
        from paddle_tpu.analysis import buffer_audit

        b, s = 2, 64
        h, head, labels = _inputs(b=b, s=s)

        bsv = (b, s, ARGS.vocab_size)
        fused = jax.make_jaxpr(jax.value_and_grad(
            lambda a, w: lf.fused_linear_cross_entropy(
                a, w, labels, ARGS, None, 1, 16), argnums=(0, 1)))(h, head)
        assert not buffer_audit.has_shape(fused, bsv), \
            "fused CE materialized a [b, s, vocab] buffer"

        ref = jax.make_jaxpr(jax.value_and_grad(
            lambda a, w: _ref_loss(a, w, labels), argnums=(0, 1)))(h, head)
        assert buffer_audit.has_shape(ref, bsv), \
            "probe lost its teeth: unchunked path shows no logits buffer"
        # and the rule form reports provenance for the offending site
        v = buffer_audit.check_forbidden_shape(ref, bsv, "unchunked_ref",
                                               "full-logits")
        assert v and all(x.rule == "buffer.forbidden-shape" for x in v)


class TestVocabParallel:
    """The mp_axis regression: chunked loss used to ignore vocab sharding."""

    def _sharded(self, chunk, s=24, check_vma=False):
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        mp = 2
        h, head, labels = _inputs(s=s)
        mesh = Mesh(np.array(jax.devices()[:mp]), ("mp",))

        def local(h_, head_, labels_):
            # the engine takes value_and_grad INSIDE shard_map (per-rank
            # cotangent 1.0) — replicate that exact pattern
            return jax.value_and_grad(
                lambda a, w: lf.fused_linear_cross_entropy(
                    a, w, labels_, ARGS, "mp", mp, chunk),
                argnums=(0, 1))(h_, head_)

        loss, (dh, dw) = shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(None, "mp"), P()),
            out_specs=(P(), (P(), P(None, "mp"))),
            check_vma=check_vma)(h, head, labels)
        return (h, head, labels), loss, dh, dw

    @pytest.mark.parametrize("chunk,check_vma", [(8, False), (13, False),
                                                 (8, True)])
    def test_matches_unsharded_reference(self, chunk, check_vma):
        (h, head, labels), loss, dh, dw = self._sharded(
            chunk, check_vma=check_vma)
        ref_loss, (ref_dh, ref_dw) = jax.value_and_grad(
            lambda a, w: _ref_loss(a, w, labels), argnums=(0, 1))(h, head)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(dh), np.asarray(ref_dh),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(ref_dw),
                                   rtol=1e-5, atol=1e-6)

    def test_typed_mesh_cotangents(self):
        """Under shard_map(check_vma=True) — how the hybrid engine runs —
        the custom vjp's cotangents must carry their primals' types: a
        hidden state typed VARYING over mp (the sequence-parallel
        all_gather's output) gets this rank's partial, not the psum; a
        head replicated over dp gets the dp-sum of the ranks' grads. The
        scan carries must be typed too, or tracing fails outright."""
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        dp = mp = 2
        h, head, labels = _inputs()
        mesh = Mesh(np.array(jax.devices()[:dp * mp]).reshape(dp, mp),
                    ("dp", "mp"))

        def local(h_, head_, labels_):
            h_ = jax.lax.pcast(h_, "mp", to="varying")
            loss, (dh, dw) = jax.value_and_grad(
                lambda a, w: lf.fused_linear_cross_entropy(
                    a, w, labels_, ARGS, "mp", mp, 8),
                argnums=(0, 1))(h_, head_)
            return (jax.lax.pmean(loss, "dp"), jax.lax.psum(dh, "mp") / dp,
                    dw / dp)

        loss, dh, dw = shard_map(
            local, mesh=mesh,
            in_specs=(P("dp"), P(None, "mp"), P("dp")),
            out_specs=(P(), P("dp"), P(None, "mp")))(h, head, labels)
        ref_loss, (ref_dh, ref_dw) = jax.value_and_grad(
            lambda a, w: _ref_loss(a, w, labels), argnums=(0, 1))(h, head)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(dh), np.asarray(ref_dh),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(ref_dw),
                                   rtol=1e-5, atol=1e-6)

    def test_forward_and_loss_honors_mp_axis(self):
        """forward_and_loss(loss_chunk=...) must route mp_axis/mp_degree
        into the fused CE — the silent-ignore bug put the OLD remat trick
        on the local vocab shard only. Detect by sharding the head and
        checking the chunked loss equals the unchunked mp-aware loss."""
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        mp = 2
        h, head, labels = _inputs()
        mesh = Mesh(np.array(jax.devices()[:mp]), ("mp",))

        def chunked(h_, head_):
            return lf.fused_linear_cross_entropy(
                h_, head_, labels, ARGS, "mp", mp, 8)

        def unchunked(h_, head_):
            return lf.parallel_cross_entropy(h_ @ head_, labels, ARGS,
                                             "mp", mp)

        run = lambda f: shard_map(  # noqa: E731
            f, mesh=mesh, in_specs=(P(), P(None, "mp")), out_specs=P(),
            check_vma=False)(h, head)
        np.testing.assert_allclose(float(run(chunked)),
                                   float(run(unchunked)),
                                   rtol=1e-6, atol=1e-6)
