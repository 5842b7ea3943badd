"""Kernel block-size autotuner: measure-and-cache tile picks per shape.

Motivation (ISSUE 16 / ROADMAP 3): the Pallas kernels shipped one fixed
tile default each — flash attention `block_q=512, block_k=1024` — picked
on early shapes and never revisited. FlashAttention-2 showed the block
shape is a per-(shape, dtype, chip) decision: at s=1024 a causal q-block
only needs the k-blocks at or left of its diagonal, so `block_k=1024`
(the whole sequence) streams and masks tiles the MXU never needed, while
`block_k=512` halves the wasted MACs of the first q-block.

Resolution order for a `get_blocks(kernel, shape, dtype, defaults)` call:

  1. env override `PADDLE_TUNE_BLOCKS` — a JSON dict
     {kernel: {param: int}} applied last, so a sweep can pin any pick
     without touching the cache (and a bad cache entry can be escaped).
  2. on-disk JSON cache, keyed (kernel, shape-bucket, dtype, chip) —
     written by `measure_and_cache` (opt-in: PADDLE_KERNEL_AUTOTUNE=1 on
     a real TPU backend; tracing-time measurement compiles and times each
     candidate on synthetic inputs, the FA2 "run all tile shapes once"
     strategy).
  3. deterministic fallback table below — the CPU/interpret answer and
     the TPU answer until a measurement lands. `tools/perf_sweep.py
     --blocks` dumps the (block_q, block_k) timing grid that feeds it.
  4. the caller's `defaults` (the historical fixed tiles).

Every resolved pick is recorded as a gauge in the observability registry
(`kernel_block{kernel=...,param=...}`), so `bench.py --telemetry-out`
artifacts carry the blocks each run actually used and stay diffable.

Shape keys are BUCKETED to the floor power of two (seq 1536 shares seq
1024's entry): tile efficiency is set by tile-alignment regimes, not
exact sizes, and bucketing keeps the cache from fragmenting across every
sequence length a serving mix produces.
"""

from __future__ import annotations

import json
import os
import threading

_CACHE_ENV = "PADDLE_TUNING_CACHE"
_OVERRIDE_ENV = "PADDLE_TUNE_BLOCKS"
_AUTOTUNE_ENV = "PADDLE_KERNEL_AUTOTUNE"

_lock = threading.Lock()
_mem_cache = None  # {key_str: {param: int}} mirror of the on-disk file
_measured_this_process = set()  # keys measured live (cold) in this process

# ---------------------------------------------------------------------------
# deterministic fallback table
# ---------------------------------------------------------------------------
# (kernel, seq-bucket) -> blocks. Entries are the analytic picks pending a
# hardware grid (tools/perf_sweep.py --blocks): causal flash wants
# block_k <= block_q so the first diagonal q-block streams no fully-masked
# k-tile; 512x512 is jax's own TPU flash default and keeps the dkv
# kernel's q/dO stream within the VMEM budget at head_dim 128. The `None`
# bucket is the kernel's any-shape row.
_FALLBACK = {
    ("flash_fwd", 1024): {"block_q": 512, "block_k": 512},
    ("flash_fwd", 2048): {"block_q": 512, "block_k": 512},
    ("flash_fwd", None): {"block_q": 512, "block_k": 512},
    ("flash_bwd", 1024): {"block_q": 512, "block_k": 512},
    ("flash_bwd", 2048): {"block_q": 512, "block_k": 512},
    ("flash_bwd", None): {"block_q": 512, "block_k": 512},
    # rms_norm rows-per-grid-step (kept at the measured value; the kernel
    # is a recorded negative result and dispatched nowhere by default)
    ("rms_norm", None): {"rows": 256},
    # int8 dequant-matmul tiles (r6 measured shapes)
    ("dequant_matmul", None): {"block_m": 256, "block_n": 512,
                               "block_k": 512},
    # decode attention k-stream block over the padded cache length
    ("decode_attention", None): {"block_k": 512},
}


def bucket(n):
    """Floor power-of-two shape bucket (1024 for 1024..2047); 0 for n<=0."""
    n = int(n)
    if n <= 0:
        return 0
    b = 1
    while b * 2 <= n:
        b *= 2
    return b


def _chip():
    try:
        import jax

        devs = jax.devices()
        return devs[0].device_kind.replace(" ", "_") if devs else "cpu"
    except Exception:
        return "unknown"


def _backend():
    try:
        import jax

        return jax.default_backend()
    except Exception:
        return "cpu"


# the in-checkout cache directory `_platform_setup.CACHE_DIR` names (that
# module sits outside the package, so the path is rebuilt here; a test pins
# the two together). Never $HOME: what runs must follow from the checkout.
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_path():
    return (os.environ.get(_CACHE_ENV)
            or os.path.join(_CACHE_DIR, "kernel_tuning.json"))


def _load_cache():
    global _mem_cache
    with _lock:
        if _mem_cache is not None:
            return _mem_cache
        try:
            with open(cache_path()) as f:
                _mem_cache = json.load(f)
        except (OSError, ValueError):
            _mem_cache = {}
        return _mem_cache


def _store_cache(key, blocks):
    path = cache_path()
    with _lock:
        cache = dict(_mem_cache or {})
        cache[key] = blocks
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(cache, f, indent=2, sort_keys=True)
                f.write("\n")
            os.replace(tmp, path)
        except OSError:
            pass  # read-only FS: keep the in-memory copy only
        globals()["_mem_cache"] = cache


def clear_memory_cache():
    """Testing hook: drop the in-process mirror so the next get_blocks
    re-reads the on-disk file (and env)."""
    global _mem_cache
    with _lock:
        _mem_cache = None
    _measured_this_process.clear()


def _cache_key(kernel, shape, dtype):
    skey = ",".join(f"{k}={bucket(v)}" for k, v in sorted(shape.items()))
    return f"{kernel}|{skey}|{dtype}|{_chip()}"


def _env_override(kernel):
    raw = os.environ.get(_OVERRIDE_ENV)
    if not raw:
        return {}
    try:
        table = json.loads(raw)
    except ValueError:
        import warnings

        warnings.warn(f"{_OVERRIDE_ENV} is not valid JSON; ignoring")
        return {}
    out = table.get(kernel, {})
    return {k: int(v) for k, v in out.items()} if isinstance(out, dict) else {}


def _fallback(kernel, shape):
    seq = shape.get("seq") or shape.get("seq_q") or shape.get("rows")
    row = _FALLBACK.get((kernel, bucket(seq) if seq else None))
    if row is None:
        row = _FALLBACK.get((kernel, None), {})
    return dict(row)


def _record(kernel, blocks, source):
    """Chosen blocks -> registry gauges, so --telemetry-out artifacts show
    what every run actually compiled with."""
    try:
        from paddle_tpu.observability import global_registry

        reg = global_registry()
        for param, val in blocks.items():
            reg.set_gauge("kernel_block", int(val),
                          labels={"kernel": kernel, "param": param})
        reg.inc("kernel_tuning_lookups", labels={"kernel": kernel,
                                                 "source": source})
    except Exception:
        pass  # telemetry must never break a kernel call


def autotune_enabled():
    return (os.environ.get(_AUTOTUNE_ENV, "0") not in ("", "0")
            and _backend() == "tpu")


def measure_and_cache(kernel, shape, dtype, candidates, measure):
    """Time every candidate dict with `measure(blocks) -> seconds` and cache
    the winner under (kernel, shape-bucket, dtype, chip). Candidates that
    raise are skipped (a tile may not lower at some shape); if all fail the
    fallback row wins. Returns the winning blocks dict."""
    key = _cache_key(kernel, shape, dtype)
    best, best_t = None, float("inf")
    for cand in candidates:
        try:
            t = measure(dict(cand))
        except Exception:
            continue
        if t < best_t:
            best, best_t = dict(cand), t
    if best is None:
        best = _fallback(kernel, shape)
    _store_cache(key, best)
    _measured_this_process.add(key)
    return best


def get_blocks(kernel, shape, dtype, defaults, measure=None, candidates=None):
    """Resolve tile sizes for one kernel call site.

    kernel: site name ('flash_fwd', 'flash_bwd', 'rms_norm', ...).
    shape: dict of the shape dims that decide the pick (bucketed for the
        cache key), e.g. {'seq_q': 1024, 'seq_k': 1024, 'head_dim': 128}.
    dtype: jnp dtype (itemsize drives VMEM residency).
    defaults: the call site's historical fixed tiles — the last resort.
    measure/candidates: optional live-measurement hook, used only when
        PADDLE_KERNEL_AUTOTUNE=1 and the backend is a real TPU.

    Returns a dict with every key of `defaults` present.
    """
    dtype = str(jnp_name(dtype))
    key = _cache_key(kernel, shape, dtype)
    cache = _load_cache()
    source = "fallback"
    if key in cache:
        blocks, source = dict(cache[key]), "cache"
    elif (measure is not None and candidates and autotune_enabled()
          and key not in _measured_this_process):
        blocks = measure_and_cache(kernel, shape, dtype, candidates, measure)
        source = "measured"
    else:
        blocks = _fallback(kernel, shape)
    out = dict(defaults)
    out.update({k: int(v) for k, v in blocks.items() if k in defaults})
    env = _env_override(kernel)
    if env:
        out.update({k: v for k, v in env.items() if k in defaults})
        source = "env"
    _record(kernel, out, source)
    return out


def jnp_name(dtype):
    """'bfloat16' from jnp.bfloat16 / np.dtype / str alike."""
    try:
        import numpy as np

        return np.dtype(dtype).name
    except TypeError:
        return getattr(dtype, "__name__", str(dtype))
