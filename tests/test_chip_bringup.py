"""Bring-up contracts that hold without a chip: nothing on the device path
falls back quietly, the compile cache can be placed from outside, and the
launchers keep jax out of any process that starts a chip-holding child.
`chip_smoke.py` is the other half — it only runs where there is a TPU."""

import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *argv], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)


@pytest.fixture
def restore_default_device():
    from paddle_tpu.framework import device

    yield
    jax.config.update("jax_default_device", None)
    device._current = None


def test_set_device_tpu_raises_without_a_tpu():
    import paddle_tpu as paddle

    for name in ("tpu", "tpu:0", "gpu"):   # 'gpu' canonicalizes to the tpu
        with pytest.raises(RuntimeError, match="no 'tpu' backend"):
            paddle.set_device(name)


def test_set_device_index_out_of_range_raises(restore_default_device):
    import paddle_tpu as paddle

    cpus = jax.devices("cpu")
    assert paddle.set_device(f"cpu:{len(cpus) - 1}") == cpus[-1]
    with pytest.raises(ValueError, match="out of range"):
        paddle.set_device(f"cpu:{len(cpus)}")


def test_unknown_device_kind_raises_in_the_one_peak_table():
    from paddle_tpu.observability import hardware as hw

    assert hw.peak_flops_for("TPU v5 lite") == 197e12
    assert hw.peak_hbm_bw_for("TPU v5 lite") == 819e9
    # no bare "v5" row handing a v5p peak to whatever says v5
    for kind in ("TPU v5", "TPU v99", "cpu"):
        with pytest.raises(KeyError, match="not in the peak table"):
            hw.peak_flops_for(kind)
        with pytest.raises(KeyError, match="not in the peak table"):
            hw.peak_hbm_bw_for(kind)
    assert hw.detect_peak_flops() is None   # on the CPU there is no MFU


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(monkeypatch):
    import _platform_setup as ps
    from paddle_tpu.kernels import tuning

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert ps.configure_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before   # jax reads the env

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert ps.configure_compile_cache() == ps.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == ps.CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ps.CACHE_DIR == os.path.join(REPO, ".jax_cache")   # fixed, no pid
    monkeypatch.delenv("PADDLE_TUNING_CACHE", raising=False)
    assert os.path.dirname(tuning.cache_path()) == ps.CACHE_DIR


def test_chip_smoke_refuses_the_cpu_before_importing_the_model():
    out = _run("-X", "importtime", "chip_smoke.py")
    assert out.returncode == 2
    assert "needs a TPU" in out.stderr and "CpuDevice" in out.stderr
    assert out.stdout == ""            # no result line, no device metric
    assert "paddle_tpu" not in out.stderr   # -X importtime lists every import


def test_bench_parent_stays_off_jax_and_fails_without_a_chip():
    out = _run("-c", "import sys, bench; "
                     "print('jax' in sys.modules, 'paddle_tpu' in sys.modules)")
    assert out.stdout.split() == ["False", "False"], out.stderr
    out = _run("bench.py")
    assert out.returncode != 0
    assert out.stdout == ""            # no record, no metric under any name
    assert "no TPU" in out.stderr
