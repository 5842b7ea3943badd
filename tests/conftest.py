"""Test config: run on a virtual 8-device CPU mesh (mirrors the reference's
fake-device test rig, `test/custom_runtime/test_custom_cpu_plugin.py:27-47`:
a CPU masquerading as the accelerator drives the same code paths). The chip
itself is checked by `chip_smoke.py`, not by this suite.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _platform_setup import force_cpu_platform  # noqa: E402

force_cpu_platform(8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: soak/arrival-trace tests excluded from the tier-1 run")


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle

    paddle.seed(1234)
    np.random.seed(1234)
    yield


@pytest.fixture(autouse=True)
def _profiler_dumps_to_tmp(tmp_path, monkeypatch):
    """Route every profiler/xprof dump through tmp_path: Profiler's default
    log_dir resolves PADDLE_PROFILER_LOG_DIR, so no test run litters
    ./profiler_log into the working tree."""
    monkeypatch.setenv("PADDLE_PROFILER_LOG_DIR",
                       str(tmp_path / "profiler_log"))
    yield
