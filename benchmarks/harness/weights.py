"""Seeded weights, made on the device in the type they are served in.

The layout is the one the program's entry points take: `embedding`
[vocab, h], `layers/*` stacked on a leading layer axis with every matrix
stored [in, out], `final_norm`, `lm_head` [h, vocab]. One layer's leaves,
by name and shape, are the family's (`family.layer_shapes(arch)`, see
`harness/spec.py`); a leaf whose published init differs from the rules here
is in the family's `leaf_init(arch)` as name -> (mean, std). Each layer's
values depend only on (seed, layer index), so the reference can make one
layer at a time and get the same numbers.

A family whose layers are of several KINDS, with leaves that differ in name
or shape, states `layer_kinds(arch)`: one name a layer, which is also the
key of that kind's stack in the tree (`layers`, `dense_layers`, ...). Its
`layer_shapes(arch)` and `leaf_init(arch)` are then keyed by kind first,
and each kind is stacked apart, `[layers of that kind, ...]` in layer
order. A layer's values still depend only on (seed, the layer's index in
the whole stack, the leaf's place among its kind's sorted names). A family
that states no `layer_kinds` has one kind, `layers`.
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


def states_kinds(family):
    return getattr(family, "layer_kinds", None) is not None


def layer_kinds(family, arch):
    """The kind of every layer, in layer order."""
    if not states_kinds(family):
        return ("layers",) * arch["num_hidden_layers"]
    kinds = tuple(family.layer_kinds(arch))
    if len(kinds) != arch["num_hidden_layers"]:
        raise ValueError(f"layer_kinds names {len(kinds)} layers, the "
                         f"configuration has {arch['num_hidden_layers']}")
    return kinds


def _arch_keys(family, arch):
    """kind -> that kind's hashable `arch_key` (see `make_layer`)."""
    shapes = family.layer_shapes(arch)
    inits = getattr(family, "leaf_init", lambda arch: {})(arch)
    if not states_kinds(family):
        shapes, inits = {"layers": shapes}, {"layers": inits}
    return {kind: tuple((name, tuple(shape), inits.get(kind, {}).get(name))
                        for name, shape in sorted(of_kind.items()))
            for kind, of_kind in shapes.items()}


def _stacks(family, arch):
    """A hashable ((kind, arch_key, its layers' indices), ...): what to
    stack, in the order the kinds first appear."""
    kinds, akeys = layer_kinds(family, arch), _arch_keys(family, arch)
    return tuple((kind, akeys[kind],
                  tuple(i for i, k in enumerate(kinds) if k == kind))
                 for kind in dict.fromkeys(kinds))


def make_outer(arch, seed_key, dtype):
    """Embedding, final norm and head."""
    v, h = arch["vocab_size"], arch["hidden_size"]
    k = jax.random.fold_in(seed_key, 1 << 20)
    return {"embedding": _leaf(jax.random.fold_in(k, 0), (v, h), dtype),
            "final_norm": _leaf(jax.random.fold_in(k, 1), (h,), dtype),
            "lm_head": _leaf(jax.random.fold_in(k, 2), (h, v), dtype)}


def _stack(akey, seed_key, indices, dtype, name=None):
    """The layers `indices` of one kind on a leading axis: every leaf, or
    the leaf `name` alone."""
    def one(i):
        layer = make_layer(akey, seed_key, i, dtype)
        return layer if name is None else layer[name]

    return jax.vmap(one)(jnp.asarray(indices, jnp.int32))


def make_params(family, arch, seed, dtype=jnp.bfloat16, out_shardings=None):
    """The whole tree in one jitted call on the device."""
    stacks = _stacks(family, arch)

    def build(seed_key):
        out = make_outer(arch, seed_key, dtype)
        for kind, akey, indices in stacks:
            out[kind] = _stack(akey, seed_key, indices, dtype)
        return out

    return jax.jit(build, out_shardings=out_shardings)(_key(seed))


@functools.lru_cache(maxsize=None)
def _layer_fn(akey, dtype):
    return jax.jit(lambda seed_key, i: make_layer(akey, seed_key, i, dtype))


def layer_params(family, arch, seed, index, dtype=jnp.bfloat16):
    """Layer `index` alone, the leaves of its own kind: equal to its row of
    make_params(...)[<its kind>][*]."""
    # a family that states no kinds may ask for an index past its layers
    # (the same leaves under another fold of the seed)
    kind = (layer_kinds(family, arch)[index] if states_kinds(family)
            else "layers")
    return _layer_fn(_arch_keys(family, arch)[kind], dtype)(
        _key(seed), jnp.int32(index))


@functools.lru_cache(maxsize=None)
def _stacked_leaf_fn(akey, name, indices, dtype):
    return jax.jit(lambda seed_key: _stack(akey, seed_key, indices, dtype,
                                           name))


def leaves(family, arch, seed, dtype=jnp.bfloat16):
    """(`<kind>/<name>` or outer name, array) one leaf at a time, each equal
    to make_params's and each materialized in `dtype` by a call of its own
    (inside one fused program XLA may skip the rounding to `dtype`)."""
    for kind, akey, indices in _stacks(family, arch):
        for name, _, _ in akey:
            yield f"{kind}/{name}", _stacked_leaf_fn(
                akey, name, indices, dtype)(_key(seed))
    yield from outer_params(arch, seed, dtype).items()


def outer_params(arch, seed, dtype=jnp.bfloat16):
    """Embedding, final norm and head alone: equal to make_params's."""
    return _outer_fn(arch["vocab_size"], arch["hidden_size"], dtype)(
        _key(seed))


@functools.lru_cache(maxsize=None)
def _outer_fn(vocab, hidden, dtype):
    arch = {"vocab_size": vocab, "hidden_size": hidden}
    return jax.jit(lambda k: make_outer(arch, k, dtype))
