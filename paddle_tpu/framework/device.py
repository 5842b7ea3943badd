"""Device API (reference: `python/paddle/device/__init__.py:284` set_device).

`paddle.set_device('tpu')` maps device strings onto jax devices and sets the
jax default device, which every subsequently created buffer lands on.
"""

import jax

_CANON = {"gpu": "tpu", "cuda": "tpu", "xpu": "tpu"}
_current = None


def _platform_of(name):
    name = name.split(":")[0].lower()
    name = _CANON.get(name, name)
    return name


def _resolve_device(name):
    """The jax device a 'tpu' / 'tpu:2' / 'cpu' string names. Raises when
    the platform is absent or the index is out of range — a request for the
    chip must never come back as the CPU, or as another chip."""
    plat = _platform_of(name)
    idx = int(name.split(":")[1]) if ":" in name else 0
    try:
        devs = jax.devices(plat)
    except RuntimeError as e:
        raise RuntimeError(
            f"set_device({name!r}): no {plat!r} backend in this process "
            f"(jax.devices() = {jax.devices()})") from e
    if not 0 <= idx < len(devs):
        raise ValueError(
            f"set_device({name!r}): index {idx} out of range, "
            f"{len(devs)} {plat} device(s) present")
    return devs[idx]


def set_device(device):
    global _current
    dev = _resolve_device(device)
    jax.config.update("jax_default_device", dev)
    _current = device if ":" in device else f"{_platform_of(device)}:0"
    return dev


def get_device():
    if _current is not None:
        return _current
    d = jax.devices()[0]
    plat = d.platform if d.platform != "cpu" else "cpu"
    return f"{plat}:{d.id}"


def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_custom_device(device_type=None):
    return True


def get_all_custom_device_type():
    return ["tpu"]


def device_count():
    return jax.device_count()


def synchronize(device=None):
    # XLA dispatch is async; block on all live arrays via a trivial barrier
    import jax.numpy as jnp

    jnp.zeros(()).block_until_ready()


class Event:
    """Minimal stream event facade (XLA manages streams internally)."""

    def record(self, stream=None):
        pass

    def synchronize(self):
        synchronize()

    def query(self):
        return True


class Stream:
    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass


def current_stream(device=None):
    return Stream()


# -- memory stats (reference paddle.device.cuda.{max_,}memory_allocated /
#    phi/core/memory/stats.cc) over PJRT's per-device accounting ------------

def _mem_stats(device=None):
    """Accepts a jax Device, an int device id, or a 'tpu:0'/'gpu:0' style
    string (reference paddle.device.cuda API conventions)."""
    import jax

    if device is None:
        dev = jax.devices()[0]
    elif isinstance(device, int):
        dev = jax.devices()[min(device, len(jax.devices()) - 1)]
    elif isinstance(device, str):
        dev = _resolve_device(device)  # canonical platform + index handling
    else:
        dev = device
    try:
        return dev.memory_stats() or {}
    except Exception:  # backends without PJRT memory stats (some CPU paths)
        return {}


def memory_allocated(device=None):
    """Bytes currently allocated on the device (PJRT bytes_in_use)."""
    return int(_mem_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None):
    s = _mem_stats(device)
    return int(s.get("peak_bytes_in_use", s.get("bytes_in_use", 0)))


def memory_reserved(device=None):
    """Bytes the allocator holds beyond live buffers. PJRT only reports
    this on backends with a reserving allocator; elsewhere reserved ==
    allocated (we do NOT report bytes_limit — that is the HBM budget, not
    a reservation)."""
    s = _mem_stats(device)
    return int(s.get("bytes_reserved", s.get("bytes_in_use", 0)))


def max_memory_reserved(device=None):
    s = _mem_stats(device)
    return int(s.get("peak_bytes_reserved",
                     s.get("peak_bytes_in_use", s.get("bytes_in_use", 0))))


def empty_cache():
    """Compat: PJRT frees buffers on release; nothing to flush."""


class cuda:
    """paddle.device.cuda compat namespace routed at the TPU (reference
    `python/paddle/device/cuda/__init__.py`)."""

    Stream = Stream
    Event = Event
    current_stream = staticmethod(current_stream)
    synchronize = staticmethod(synchronize)
    memory_allocated = staticmethod(memory_allocated)
    max_memory_allocated = staticmethod(max_memory_allocated)
    memory_reserved = staticmethod(memory_reserved)
    max_memory_reserved = staticmethod(max_memory_reserved)
    empty_cache = staticmethod(empty_cache)

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def stream_guard(stream):
        import contextlib

        return contextlib.nullcontext()
