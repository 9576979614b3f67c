"""Test harness: simulate an 8-device TPU topology on CPU.

Mirrors the reference's strategy of testing distributed behavior in-process on
a local-mode SparkSession (``core/src/test/.../base/SparkSessionFactory.scala``);
here an 8-device virtual CPU mesh stands in for a TPU slice
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""

import os

# Tests run on the CPU. The variable is SET, not popped: jax.config does not
# propagate to subprocesses, and a test-spawned child that imports jax with
# JAX_PLATFORMS unset does default discovery and loads the TPU library, which
# one process at a time may hold. Set in os.environ, every child of every
# test inherits it and is CPU-only by construction.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def tmp_save(tmp_path):
    return str(tmp_path / "stage_save")
