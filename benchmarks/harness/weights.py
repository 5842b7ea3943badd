"""Seeded weights, made on the device in the type they are served in.

The layout is the one the program's entry points take: `embedding`
[vocab, h], `layers/*` stacked on a leading layer axis with every matrix
stored [in, out], `final_norm`, `lm_head` [h, vocab]. One layer's leaves,
by name and shape, are the family's (`family.layer_shapes(arch)`, see
`harness/spec.py`); a leaf whose published init differs from the rules here
is in the family's `leaf_init(arch)` as name -> (mean, std). Each layer's
values depend only on (seed, layer index), so the reference can make one
layer at a time and get the same numbers.
"""

import functools

import jax
import jax.numpy as jnp

INIT_STD = 0.02      # the published initializer_range of both models
NORM_JITTER = 0.05   # norm weights 1 + 0.05 N(0,1): a dropped norm weight shows


def _key(seed):
    # seeds run past 2**31; fold the high and low halves in separately
    k = jax.random.key(0)
    k = jax.random.fold_in(k, seed & 0xFFFF)
    return jax.random.fold_in(k, (seed >> 16) & 0xFFFFFFFF)


def _leaf(key, shape, dtype, init=None):
    x = jax.random.normal(key, shape, jnp.float32)
    if init is not None:
        return (init[0] + init[1] * x).astype(dtype)
    if len(shape) == 1:
        return (1.0 + NORM_JITTER * x).astype(dtype)
    return (INIT_STD * x).astype(dtype)


def make_layer(arch_key, seed_key, index, dtype):
    """One layer's weights; `arch_key` is a hashable tuple of (name, shape,
    stated init or None), sorted by name: a leaf's fold-in is its place."""
    k = jax.random.fold_in(seed_key, index)
    return {name: _leaf(jax.random.fold_in(k, j), shape, dtype, init)
            for j, (name, shape, init) in enumerate(arch_key)}


def _arch_key(family, arch):
    inits = getattr(family, "leaf_init", lambda arch: {})(arch)
    return tuple((name, tuple(shape), inits.get(name)) for name, shape
                 in sorted(family.layer_shapes(arch).items()))


def make_outer(arch, seed_key, dtype):
    """Embedding, final norm and head."""
    v, h = arch["vocab_size"], arch["hidden_size"]
    k = jax.random.fold_in(seed_key, 1 << 20)
    return {"embedding": _leaf(jax.random.fold_in(k, 0), (v, h), dtype),
            "final_norm": _leaf(jax.random.fold_in(k, 1), (h,), dtype),
            "lm_head": _leaf(jax.random.fold_in(k, 2), (h, v), dtype)}


def make_params(family, arch, seed, dtype=jnp.bfloat16, out_shardings=None):
    """The whole tree in one jitted call on the device."""
    akey, n_layers = _arch_key(family, arch), arch["num_hidden_layers"]

    def build(seed_key):
        layers = jax.vmap(lambda i: make_layer(akey, seed_key, i, dtype))(
            jnp.arange(n_layers))
        out = make_outer(arch, seed_key, dtype)
        out["layers"] = layers
        return out

    return jax.jit(build, out_shardings=out_shardings)(_key(seed))


@functools.lru_cache(maxsize=None)
def _layer_fn(akey, dtype):
    return jax.jit(lambda seed_key, i: make_layer(akey, seed_key, i, dtype))


def layer_params(family, arch, seed, index, dtype=jnp.bfloat16):
    """Layer `index` alone: equal to make_params(...)['layers'][*][index]."""
    return _layer_fn(_arch_key(family, arch), dtype)(_key(seed),
                                                     jnp.int32(index))


@functools.lru_cache(maxsize=None)
def _stacked_leaf_fn(akey, name, n_layers, dtype):
    return jax.jit(lambda seed_key: jax.vmap(
        lambda i: make_layer(akey, seed_key, i, dtype)[name])(
            jnp.arange(n_layers)))


def leaves(family, arch, seed, dtype=jnp.bfloat16):
    """(`layers/<name>` or outer name, array) one leaf at a time, each equal
    to make_params's and each materialized in `dtype` by a call of its own
    (inside one fused program XLA may skip the rounding to `dtype`)."""
    akey, n = _arch_key(family, arch), arch["num_hidden_layers"]
    for name, _, _ in akey:
        yield f"layers/{name}", _stacked_leaf_fn(akey, name, n, dtype)(
            _key(seed))
    yield from outer_params(arch, seed, dtype).items()


def outer_params(arch, seed, dtype=jnp.bfloat16):
    """Embedding, final norm and head alone: equal to make_params's."""
    return _outer_fn(arch["vocab_size"], arch["hidden_size"], dtype)(
        _key(seed))


@functools.lru_cache(maxsize=None)
def _outer_fn(vocab, hidden, dtype):
    arch = {"vocab_size": vocab, "hidden_size": hidden}
    return jax.jit(lambda k: make_outer(arch, k, dtype))
