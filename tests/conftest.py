"""Test configuration: run JAX on a virtual 8-device CPU mesh.

This is the TPU-native analogue of the reference's "multi-node without a
cluster" gap (SURVEY.md §4): all sharding/collective paths are exercised
on host devices via --xla_force_host_platform_device_count.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

import jax
import pytest

# Keep default 32-bit types: that is what runs on TPU.
jax.config.update("jax_platforms", "cpu")


@pytest.fixture(autouse=True)
def _prep_store_in_tmp(tmp_path_factory, monkeypatch, request):
    """Every test gets a preparation store (lux_tpu/prepstore.py) of
    its own under pytest's temporary directory: a test whose graph is
    large enough to engage it neither writes into the checkout nor
    loads what another test prepared."""
    monkeypatch.setenv("LUX_PREP_STORE_DIR", str(
        tmp_path_factory.getbasetemp() / "prep_store"
        / str(abs(hash(request.node.nodeid)))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: minutes-long builds/sweeps, deselected by tier-1 "
        "(-m 'not slow')")
