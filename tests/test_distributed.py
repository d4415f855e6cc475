"""Multi-device distribution tests.

These need XLA_FLAGS=--xla_force_host_platform_device_count=8, which must
be set before jax initializes — so each case runs tests/_dist_prog.py in a
subprocess through the shared ``run_prog`` fixture (tests/conftest.py)."""
import os

import pytest

_PROG = os.path.join(os.path.dirname(__file__), "_dist_prog.py")


@pytest.mark.parametrize("case", ["dense", "oracle", "variants", "multipod"])
def test_distributed(case, run_prog):
    run_prog(_PROG, case)
