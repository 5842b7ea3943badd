"""Ring attention (context parallelism) on the 8-device CPU mesh: the
sequence-sharded ring must match single-device attention exactly (fwd and
grads), causal and non-causal."""

import jax

from jax import shard_map as _shard_map
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.distributed.ring_attention import ring_attention
from paddle_tpu.nn.functional.flash_attention import _sdpa_reference


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("cp",))


def _qkv(b=2, s=64, h=4, d=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)  # noqa: E731
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("cp", [2, 4, 8])
def test_ring_matches_single_device(causal, cp):
    q, k, v = _qkv()
    mesh = _mesh(cp)
    ring = jax.jit(_shard_map(
        lambda q, k, v: ring_attention(q, k, v, "cp", causal=causal),
        mesh=mesh, in_specs=(P(None, "cp"),) * 3, out_specs=P(None, "cp"),
        check_vma=True))
    out = ring(q, k, v)
    ref = _sdpa_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_grads_match_single_device(causal):
    q, k, v = _qkv(seed=3)
    mesh = _mesh(4)

    def ring_loss(q, k, v):
        sm = _shard_map(
            lambda q, k, v: ring_attention(q, k, v, "cp", causal=causal),
            mesh=mesh, in_specs=(P(None, "cp"),) * 3,
            out_specs=P(None, "cp"), check_vma=True)
        return (sm(q, k, v) ** 2).sum()

    def ref_loss(q, k, v):
        return (_sdpa_reference(q, k, v, causal=causal) ** 2).sum()

    g1 = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=1e-4, err_msg=f"d{name}")


def test_ring_gqa():
    """GQA kv heads ride the ring unchanged (no repeat)."""
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 64, 8, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 64, 2, 16)), jnp.float32)
    mesh = _mesh(4)
    out = jax.jit(_shard_map(
        lambda q, k, v: ring_attention(q, k, v, "cp", causal=True),
        mesh=mesh, in_specs=(P(None, "cp"),) * 3, out_specs=P(None, "cp"),
        check_vma=True))(q, k, v)
    ref = _sdpa_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=1e-5)


# -- Ulysses (all-to-all) sequence parallelism ------------------------------

from paddle_tpu.distributed.ring_attention import ulysses_attention  # noqa: E402


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("sp", [2, 4])
def test_ulysses_matches_single_device(causal, sp):
    """Seq-sharded all-to-all attention == dense single-device attention."""
    q, k, v = _qkv()
    mesh = Mesh(np.asarray(jax.devices()[:sp]), ("sp",))
    uly = jax.jit(_shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "sp", causal=causal),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=True))
    out = uly(q, k, v)
    ref = _sdpa_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_ulysses_grads_match_single_device():
    q, k, v = _qkv(s=32)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("sp",))

    def uly_loss(q, k, v):
        sm = _shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, "sp", causal=True),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=True)
        return (sm(q, k, v) ** 2).sum()

    def ref_loss(q, k, v):
        return (_sdpa_reference(q, k, v, causal=True) ** 2).sum()

    gu = jax.jit(jax.grad(uly_loss, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(gu, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-5,
                                   atol=5e-5, err_msg=name)


def test_ulysses_rejects_indivisible_heads():
    q, k, v = _qkv(h=3)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("sp",))
    with pytest.raises(Exception, match="divisible"):
        jax.jit(_shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, "sp"),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=True))(q, k, v)
