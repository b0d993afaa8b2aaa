"""Shared fixtures.  NOTE: no XLA_FLAGS here -- tests see the real single
CPU device; multi-device behaviour is tested via subprocesses
(tests/test_distributed.py) and the dry-run launcher owns its own flags."""
import dataclasses
import os

import pytest

# Tests never write JAX's persistent compilation cache: not in this process,
# nor in the launcher subprocesses that inherit the environment.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"


@pytest.fixture
def f32(request):
    return None


def f32_cfg(cfg):
    """Run smoke configs in f32 on CPU (bf16 matmuls are slow + noisy)."""
    return dataclasses.replace(cfg, param_dtype="float32",
                               compute_dtype="float32")
