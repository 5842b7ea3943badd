"""The plain reference's machinery, for any decoder-only language model on
the two engines: embedding, a stack of layers, a final RMS norm and a head,
in `jax.numpy`, float32, matmul precision `highest`.

The layer itself is the family's (`family.decoder_layer(x, w, arch, mm)`,
see `harness/spec.py`), written from the published equations. No kernel, no
cache, no batching tricks, and nothing of the program: the weights are made
here from the seed (`harness.weights`), one layer upcast at a time. `mm` is
the one matrix multiplication every weight goes through; the control swaps
it for a lower precision (`fp8_mm`).

Served model:  `served_logits` runs each prompt with its served tokens once
and returns the logits at the served positions (right where tokens are
chosen left to right; a family that chooses them otherwise brings its own).
Training:      `TrainReference` follows the first steps of AdamW training
layer by layer (forward keeps the layer inputs, backward recomputes one
layer at a time and updates it at once), so it fits where the program did.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import weights

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 1024     # queries a layer attends at once, to bound its score
                   # matrix: sequences come padded to a multiple of it


def f32_mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def _fake_fp8(x):
    """Round to float8 e4m3 (3 mantissa bits) under one scale for the whole
    tensor that puts its largest magnitude at the format's top, back in f32."""
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)
    s = jnp.max(jnp.abs(x)) / top
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def fp8_mm(x, w):
    """x [..., k] @ w [k, n] with both operands rounded to fp8, forward and
    backward: the precision below bfloat16, the control's arithmetic."""
    return f32_mm(_fake_fp8(x), _fake_fp8(w))


def _fp8_mm_fwd(x, w):
    return fp8_mm(x, w), (x, w)


def _fp8_mm_bwd(res, dy):
    x, w = res
    x2, dy2 = x.reshape(-1, x.shape[-1]), dy.reshape(-1, dy.shape[-1])
    dyq = _fake_fp8(dy2)
    dx = f32_mm(dyq, _fake_fp8(w).T).reshape(x.shape)
    return dx, f32_mm(_fake_fp8(x2).T, dyq)


fp8_mm.defvjp(_fp8_mm_fwd, _fp8_mm_bwd)


# ---------------------------------------------------------------------------
# one layer of the family, jitted
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _f32(w):
    return jax.tree.map(lambda a: a.astype(jnp.float32), w)


def _frozen(arch):
    """The configuration as a hashable key that `json.loads` gives back
    whole, nested groups included."""
    return json.dumps(arch, sort_keys=True)


def _call(layer, arch, mm, kind):
    """A family's `decoder_layer` as (x, w) -> y; a family of several kinds
    (`weights.layer_kinds`) is told the layer's kind."""
    if kind is None:
        return lambda x, w: layer(x, w, arch, mm)
    return lambda x, w: layer(x, w, arch, mm, kind)


def _kinds(family, arch):
    """The kind to hand each layer's `decoder_layer`: None for a family
    that states no kinds."""
    if not weights.states_kinds(family):
        return (None,) * arch["num_hidden_layers"]
    return weights.layer_kinds(family, arch)


@functools.lru_cache(maxsize=None)
def _layer_fwd(layer, frozen, mm, kind=None):
    """`layer` is a family's `decoder_layer`; the jitted function takes x
    [b, s, h] float32 and w, one layer's weights in any float type."""
    call = _call(layer, json.loads(frozen), mm, kind)
    return jax.jit(lambda x, w: call(x, _f32(w)))


@functools.lru_cache(maxsize=None)
def _layer_bwd(layer, frozen, mm, kind=None):
    call = _call(layer, json.loads(frozen), mm, kind)

    def bwd(x, w, dy):
        _, vjp = jax.vjp(call, x, _f32(w))
        return vjp(dy)

    return jax.jit(bwd, donate_argnums=(2,))


# ---------------------------------------------------------------------------
# a served model: logits at the served positions
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _head_fn(eps, mm):
    def head(x, norm_w, head_w):
        x = rms_norm(x, norm_w.astype(jnp.float32), eps)
        return mm(x, head_w.astype(jnp.float32))

    return jax.jit(head)


HEAD_ROWS = 128    # served positions go through the head this many a call


def served_rows(head, x, n, m):
    """The logits [m, vocab] (numpy) at the positions where the m served
    tokens were chosen after a prompt of n: rows n - 1 .. n + m - 2 of the
    last layer's output x [s, h], through `head(rows)` HEAD_ROWS at a call,
    so that the head is one program whatever a request's length (the last
    call's spare rows repeat x's last row and are dropped)."""
    out = []
    for a in range(0, m, HEAD_ROWS):
        rows = np.minimum(n - 1 + a + np.arange(HEAD_ROWS), x.shape[0] - 1)
        out.append(np.asarray(head(x[jnp.asarray(rows)])))
    return np.concatenate(out)[:m]


def pad_rows(multiple, *arrays):
    """Each array with zero rows appended up to a whole multiple of rows: a
    reference that cuts its keys to whole buckets of positions compiles a
    program a bucket count and none a request's own length."""
    extra = -arrays[0].shape[0] % multiple
    if not extra:
        return arrays
    return tuple(jnp.pad(a, ((0, extra),) + ((0, 0),) * (a.ndim - 1))
                 for a in arrays)


def served_logits(family, arch, seed, requests, mm=f32_mm):
    """For each (prompt, tokens, ...) of `requests`, the reference logits
    [len(tokens), vocab] at the positions where the server chose `tokens`
    after `prompt`: one full forward over the prompt with its served
    tokens, padded up to a multiple of Q_BLOCK (padding sits after
    everything it could influence). Layer by layer over all the requests,
    so each layer's weights are made once."""
    outer = weights.outer_params(arch, seed)
    xs = []
    for prompt, tokens, *_ in requests:
        seq = np.concatenate([np.asarray(prompt), np.asarray(tokens)[:-1]])
        ids = np.zeros(-(-len(seq) // Q_BLOCK) * Q_BLOCK, np.int32)
        ids[:len(seq)] = seq
        xs.append(outer["embedding"][jnp.asarray(ids)]
                  .astype(jnp.float32)[None])
    for i, kind in enumerate(_kinds(family, arch)):
        fwd = _layer_fwd(family.decoder_layer, _frozen(arch), mm, kind)
        w = weights.layer_params(family, arch, seed, i)
        for j, x in enumerate(xs):
            xs[j] = fwd(x, w)
    head = _head_fn(arch["rms_norm_eps"], mm)
    out = []
    for (prompt, tokens, *_), x in zip(requests, xs):
        out.append(served_rows(
            lambda rows: head(rows, outer["final_norm"], outer["lm_head"]),
            x[0], len(prompt), len(tokens)))
    return out


def served_gap(logits, tokens):
    """How far the chosen token's logit lies below the reference's best, at
    each position: 0 where the server chose the reference's first token."""
    logits = np.asarray(logits, np.float32)
    chosen = logits[np.arange(len(tokens)), np.asarray(tokens)]
    return logits.max(-1) - chosen


# ---------------------------------------------------------------------------
# training: the first AdamW steps, layer by layer
# ---------------------------------------------------------------------------

HEAD_CHUNK = 2048      # tokens whose logits exist at once


@functools.lru_cache(maxsize=None)
def _head_loss_grad(eps, mm):
    def loss_sum(x, norm_w, head_w, labels):
        logits = mm(rms_norm(x, norm_w, eps), head_w)
        lse = jax.nn.logsumexp(logits, axis=-1)
        true = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - true)

    def fn(x, norm_w, head_w, labels):
        return jax.value_and_grad(loss_sum, argnums=(0, 1, 2))(
            x, norm_w.astype(jnp.float32), head_w.astype(jnp.float32),
            labels)

    return jax.jit(fn)


@functools.partial(jax.jit, static_argnames=("hp",), donate_argnums=(0, 2, 3))
def _adamw(p, g, m, v, step, hp):
    """The update of Loshchilov & Hutter's AdamW in float32; the weight is
    stored back in its own (bfloat16) type, as the configuration states.
    Returns the squared norm of the gradient as well."""
    lr, b1, b2, eps, wd = hp
    g = g.astype(jnp.float32)
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat = m / (1 - b1 ** step)
    vhat = v / (1 - b2 ** step)
    p32 = p.astype(jnp.float32)
    p32 = p32 - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p32)
    return p32.astype(p.dtype), m, v, jnp.sum(g * g)


@jax.jit
def _delta_sq(p, p0):
    d = p.astype(jnp.float32) - p0.astype(jnp.float32)
    return jnp.sum(d * d)


class TrainReference:
    """Follows the program's first training steps on the same batches.

    `hp` = (lr, beta1, beta2, eps, weight_decay). Weights are kept in the
    type the configuration trains in (bfloat16) and the moments in float32;
    all arithmetic is float32 at `highest`.
    """

    def __init__(self, family, arch, seed, hp, mm=f32_mm,
                 dtype=jnp.bfloat16):
        self.family, self.arch, self.seed = family, arch, seed
        self.hp, self.mm, self.dtype = tuple(hp), mm, dtype
        self.n_layers = arch["num_hidden_layers"]
        self.kinds = weights.layer_kinds(family, arch)
        self.layers = [weights.layer_params(family, arch, seed, i, dtype)
                       for i in range(self.n_layers)]
        self.outer = weights.outer_params(arch, seed, dtype)
        zeros = functools.partial(jax.tree.map,
                                  lambda a: jnp.zeros(a.shape, jnp.float32))
        self.m = {"layers": [zeros(w) for w in self.layers],
                  "outer": zeros(self.outer)}
        self.v = {"layers": [zeros(w) for w in self.layers],
                  "outer": zeros(self.outer)}
        self.step = 0
        self.grad_sq = None      # leaf -> squared gradient norm, last step

    def _update(self, group, idx, name, g, sq):
        store = self.layers[idx] if group == "layers" else self.outer
        m = self.m[group][idx] if group == "layers" else self.m[group]
        v = self.v[group][idx] if group == "layers" else self.v[group]
        store[name], m[name], v[name], gsq = _adamw(
            store[name], g, m[name], v[name], jnp.float32(self.step),
            self.hp)
        key = f"{self.kinds[idx]}/{name}" if group == "layers" else name
        sq[key] = sq.get(key, 0.0) + gsq

    def train_step(self, ids, labels):
        """ids, labels [B, s] int32. Returns the mean loss (a float)."""
        arch, mm, frozen = self.arch, self.mm, _frozen(self.arch)
        self.step += 1
        ids, labels = jnp.asarray(ids), jnp.asarray(labels)
        B, s = ids.shape
        layer, told = self.family.decoder_layer, _kinds(self.family, arch)
        xs = [self.outer["embedding"][ids].astype(jnp.float32)]
        for w, kind in zip(self.layers, told):
            xs.append(_layer_fwd(layer, frozen, mm, kind)(xs[-1], w))

        # head and loss in chunks of rows; d(final_norm), d(lm_head) add up
        h = arch["hidden_size"]
        x_last = xs.pop().reshape(B * s, h)
        flat_labels = labels.reshape(B * s)
        head = _head_loss_grad(arch["rms_norm_eps"], mm)
        inv_n = 1.0 / (B * s)
        loss, dxs, dnorm, dhead = 0.0, [], 0.0, 0.0
        for a in range(0, B * s, HEAD_CHUNK):
            ls, (dx, dn, dh) = head(
                x_last[a:a + HEAD_CHUNK], self.outer["final_norm"],
                self.outer["lm_head"], flat_labels[a:a + HEAD_CHUNK])
            loss, dnorm, dhead = loss + ls, dnorm + dn, dhead + dh
            dxs.append(dx)
        del x_last
        dy = (jnp.concatenate(dxs) * inv_n).reshape(B, s, h)
        del dxs
        sq = {}
        self._update("outer", None, "final_norm", dnorm * inv_n, sq)
        self._update("outer", None, "lm_head", dhead * inv_n, sq)
        del dnorm, dhead

        for i in reversed(range(self.n_layers)):
            dy, dw = _layer_bwd(layer, frozen, mm, told[i])(
                xs.pop(), self.layers[i], dy)
            for name in sorted(dw):
                self._update("layers", i, name, dw.pop(name), sq)
        demb = jnp.zeros(self.outer["embedding"].shape, jnp.float32)
        demb = demb.at[ids].add(dy)
        self._update("outer", None, "embedding", demb, sq)
        self.grad_sq = {k: float(x) for k, x in sq.items()}
        return float(loss) * inv_n

    def grad_norms(self):
        """Leaf -> norm of the last step's gradient (a stacked leaf of the
        program, `<kind>/<name>`, is all that kind's layers together)."""
        return {k: math.sqrt(x) for k, x in self.grad_sq.items()}

    def delta_norms(self):
        """Leaf -> norm of (weights now - weights at the seed)."""
        out = {}
        for i, w in enumerate(self.layers):
            w0 = weights.layer_params(self.family, self.arch, self.seed, i,
                                      self.dtype)
            for name in w:
                key = f"{self.kinds[i]}/{name}"
                out[key] = out.get(key, 0.0) + float(_delta_sq(w[name],
                                                               w0[name]))
        o0 = weights.outer_params(self.arch, self.seed, self.dtype)
        for name in self.outer:
            out[name] = float(_delta_sq(self.outer[name], o0[name]))
        return {k: math.sqrt(x) for k, x in out.items()}
