"""Fused linear + cross-entropy (models/llama_functional.py).

`fused_linear_cross_entropy` is a custom_vjp that works on [T, Vb] blocks
of the logits: a token tile of T rows (the whole micro-batch where the
budget allows) against a block of Vb vocabulary columns, T * Vb bounded by
b * chunk * vocab_local (`ce_blocking`). Forward sweeps the head's blocks
twice (pass 1: every token's log-sum-exp; pass 2: the block's logits
re-formed, both gradients formed) and stores d(hidden)/d(lm_head) as
residuals, so the [b, s, vocab] logits exist in no pass, each block of the
head's gradient is formed and written once, and backward only scales.
These tests pin:

- loss parity vs the unblocked `parallel_cross_entropy` reference
  (f32 exact-ish, bf16 loose) and gradient parity vs jax autodiff of the
  unblocked composite, over every kind of blocking the code can reach
  (`BLOCKINGS`), plus the OpTest-style central finite-difference probe;
- the memory claims themselves, in the fwd+bwd jaxpr (the CPU-verifiable
  form of the HLO evidence): no [b, s, vocab] value, no float32 [hidden,
  vocab_local] loop carry, nothing but d_head above the chunk's budget;
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


# (s, chunk) -> what `ce_blocking(2, s, 160, chunk)` makes of it: every kind
# of blocking the code can reach at the tests' sizes
BLOCKINGS = {
    (24, 24): (48, 160, 1),   # one block holds the vocabulary: one pass
    (24, 64): (48, 160, 1),   # chunk > s
    (24, 12): (48, 80, 2),    # even blocks
    (24, 6): (48, 40, 4),
    (24, 8): (48, 53, 4),     # a short last block (of ONE column)
    (24, 13): (48, 86, 2),    # s % chunk != 0, short last block
    (21, 8): (42, 60, 3),     # s % chunk != 0
    (24, 2): (24, 26, 7),     # a token tile smaller than b * s
    (24, 1): (16, 20, 8),     # three token tiles, even blocks
}


@pytest.mark.parametrize("case", sorted(BLOCKINGS))
def test_blockings_under_test(case):
    """The parity cases below are the blockings they claim to be."""
    s, chunk = case
    assert lf.ce_blocking(2, s, ARGS.vocab_size, chunk) == BLOCKINGS[case]


@pytest.mark.parametrize("b,s,vocab,chunk", [
    (1, 4096, 92544, 128), (2, 4096, 46272, 128), (8, 1024, 32000, 128),
    (1, 32768, 32000, 128), (2, 24, 160, 8), (3, 7, 11, 2), (1, 1, 5, 128)])
def test_blocking_keeps_the_chunks_budget(b, s, vocab, chunk):
    """T divides b * s, the blocks cover the vocabulary, a block is whole
    lanes where it holds one, and the live block is never above
    b * chunk * vocab elements (`loss_chunk`'s meaning since it exists)."""
    t, vb, nb = lf.ce_blocking(b, s, vocab, chunk)
    assert (b * s) % t == 0 and 1 <= vb <= vocab
    assert nb == -(-vocab // vb)
    assert t * vb <= b * min(chunk, s) * vocab
    assert vb % 128 == 0 or vb < 128 or vb == vocab


def test_blocking_of_the_training_cell():
    """internlm2 at 1 x 4,096: the micro-batch is one tile, so each block
    of the head's gradient is formed once a micro-batch (the 128-token
    chunks formed all of it 32 times)."""
    assert lf.ce_blocking(1, 4096, 92544, 128) == (4096, 2816, 33)


class TestFusedCEParity:
    @pytest.mark.parametrize("s,chunk", sorted(BLOCKINGS))
    def test_loss_matches_unchunked_f32(self, s, chunk):
        """Any blocking: one block, even blocks, a short last block,
        several token tiles, odd remainders (24 % 13 = 11), chunk > s."""
        h, head, labels = _inputs(s=s)
        ref = _ref_loss(h, head, labels)
        got = lf.fused_linear_cross_entropy(h, head, labels, ARGS,
                                            None, 1, chunk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("s,chunk", sorted(BLOCKINGS))
    def test_grads_match_autodiff_f32(self, s, chunk):
        h, head, labels = _inputs(s=s)
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


def _fused_jaxpr(b, s, chunk):
    h, head, labels = _inputs(b=b, s=s)
    return jax.make_jaxpr(jax.value_and_grad(
        lambda a, w: lf.fused_linear_cross_entropy(
            a, w, labels, ARGS, None, 1, chunk), argnums=(0, 1)))(h, head)


class TestNoLogitsBuffer:
    def test_no_full_logits_intermediate_in_jaxpr(self):
        """The acceptance claim, in its CPU-checkable form: the fwd+bwd
        jaxpr of the fused loss contains NO [b, s, vocab] value anywhere
        (both passes work on [T, Vb] blocks: a token tile against a block
        of the vocabulary) — checked with the shared analysis walker, which
        descends into custom_vjp/scan/shard_map subjaxprs. The unblocked
        reference trips this check, proving the probe has teeth."""
        from paddle_tpu.analysis import buffer_audit

        b, s = 2, 64
        h, head, labels = _inputs(b=b, s=s)

        bsv = (b, s, ARGS.vocab_size)
        fused = _fused_jaxpr(b, s, 16)
        assert not buffer_audit.has_shape(fused, bsv), \
            "fused CE materialized a [b, s, vocab] buffer"
        assert not buffer_audit.has_shape(fused, (b * s, ARGS.vocab_size))

        ref = jax.make_jaxpr(jax.value_and_grad(
            lambda a, w: _ref_loss(a, w, labels), argnums=(0, 1)))(h, head)
        assert buffer_audit.has_shape(ref, bsv), \
            "probe lost its teeth: unchunked path shows no logits buffer"
        # and the rule form reports provenance for the offending site
        v = buffer_audit.check_forbidden_shape(ref, bsv, "unchunked_ref",
                                               "full-logits")
        assert v and all(x.rule == "buffer.forbidden-shape" for x in v)

    @pytest.mark.parametrize("s,chunk", [(24, 8), (24, 13), (24, 2)])
    def test_no_f32_head_gradient_is_a_loop_carry(self, s, chunk):
        """A loop's carry is read and written every iteration: the head's
        gradient accumulated in float32 across a scan is [hidden,
        vocab_local] of traffic a trip. Each block of it is formed once
        and leaves the scan as an output. The design this replaced (a scan
        over token chunks that adds `bch,bcv->hv` to its carry) trips the
        probe: its teeth."""
        from paddle_tpu.analysis import buffer_audit

        hv = (ARGS.hidden_size, ARGS.vocab_size)
        assert buffer_audit.check_forbidden_carry(
            _fused_jaxpr(2, s, chunk), hv, "float32", "fused") == []

        h, head, labels = _inputs(s=s)

        def chunk_scan(h, head):
            def body(d_head, h_c):
                dl = jax.nn.softmax(h_c @ head)
                return d_head + jnp.einsum("bch,bcv->hv", h_c, dl), None

            return jax.lax.scan(body, jnp.zeros(hv, jnp.float32),
                                jnp.swapaxes(h.reshape(2, -1, 8, hv[0]),
                                             0, 1))[0]

        v = buffer_audit.check_forbidden_carry(
            jax.make_jaxpr(chunk_scan)(h, head), hv, "float32", "chunks")
        assert v and v[0].rule == "buffer.forbidden-carry"

    @pytest.mark.parametrize("s,chunk", [(24, 8), (24, 13), (21, 8)])
    def test_nothing_but_d_head_is_above_the_chunks_budget(self, s, chunk):
        """`chunk` bounds what is live: no value the fwd+bwd program writes
        holds more than b * chunk * vocab elements, but the head's
        gradient in its forms (a block, the blocks as the scan stacks them,
        the same re-laid as [hidden, vocab]). At sizes where the hidden states
        themselves are under that budget."""
        from paddle_tpu.analysis import buffer_audit

        b, hidden, vocab = 2, ARGS.hidden_size, ARGS.vocab_size
        budget = b * chunk * vocab
        _, vb, nb = lf.ce_blocking(b, s, vocab, chunk)
        nfull = vocab // vb
        d_head_forms = {(hidden, vb), (nfull, hidden, vb), (hidden, nfull, vb),
                        (hidden, nfull * vb), (hidden, vocab)}
        big = {tuple(aval.shape) for _, aval, _, _ in
               buffer_audit.intermediates(_fused_jaxpr(b, s, chunk))
               if int(np.prod(aval.shape)) > budget}
        assert big and big <= d_head_forms, big - d_head_forms


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

    # ce_blocking(2, 24, 80, chunk): 24 -> (48, 80, 1) one pass; 12 -> (48,
    # 40, 2) even blocks; 13 -> (48, 43, 2) short last block; 8 -> (24, 53,
    # 2) two token tiles; 1 -> (16, 10, 8) three
    @pytest.mark.parametrize("check_vma", [False, True])
    @pytest.mark.parametrize("chunk", [1, 8, 12, 13, 24])
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


class TestScopeNames:
    """`pt.ce_epilogue` names the fused epilogue's operations in the forward
    rule, the backward rule and the block scans' bodies, and the unfused
    path's cross entropy; `pt.ce_stats` / `pt.ce_grads` inside it split the
    forward rule into its two passes (PERF.md section 3)."""

    @pytest.mark.parametrize("path", ["fused_grad", "fused_primal",
                                      "unfused"])
    def test_lowered_text_carries_ce_epilogue(self, path):
        h, head, labels = _inputs()
        if path == "fused_grad":
            fn = jax.grad(lambda h, w: lf.fused_linear_cross_entropy(
                h, w, labels, ARGS, None, 1, 8), argnums=(0, 1))
        elif path == "fused_primal":
            fn = lambda h, w: lf.fused_linear_cross_entropy(  # noqa: E731
                h, w, labels, ARGS, None, 1, 8)
        else:
            fn = lambda h, w: lf.parallel_cross_entropy(  # noqa: E731
                h @ w, labels, ARGS)
        text = jax.jit(fn).lower(h, head).as_text(debug_info=True)
        if path == "fused_grad":
            # the two passes' scan bodies and the backward rule's scaling
            assert "jvp(pt.ce_epilogue)/pt.ce_stats/while/body" in text
            assert "jvp(pt.ce_epilogue)/pt.ce_grads/while/body" in text
            assert "transpose(jvp(pt.ce_epilogue))/mul" in text
        elif path == "fused_primal":
            assert "pt.ce_epilogue/pt.ce_stats/while/body" in text
            assert "pt.ce_grads" not in text
        else:
            assert "pt.ce_epilogue/" in text
