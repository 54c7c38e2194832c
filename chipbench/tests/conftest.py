"""The benchmark's own tests run on the CPU backend with four virtual
devices; both have to be fixed before jax is imported."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402


@pytest.fixture
def tiny(monkeypatch):
    """The tiny cells of ``tests/tiny`` in place of BENCHMARK.json's."""
    from chipbench import spec
    here = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
    monkeypatch.setattr(spec, "SEARCH", [here] + spec.SEARCH)
    monkeypatch.setattr(spec, "BENCHMARK_FILE",
                        os.path.join(here, "BENCHMARK.json"))
    return here
