import importlib.util
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# CI backend matrix: REPRO_PLAN_BACKEND selects the PlanBackend lane this
# suite run exercises (numpy | jax | jax_x64).  The parity suites
# (test_plan_scan.py and friends) pick it up through the fixture below and
# compare the lane's backend against the numpy oracle, so every backend
# stays bit-honest under the same property tests.
ENV_PLAN_BACKEND = os.environ.get("REPRO_PLAN_BACKEND", "").strip()


@pytest.fixture(scope="session")
def plan_backend_name() -> str:
    """The backend name selected for this run ("numpy" when unset)."""
    return ENV_PLAN_BACKEND or "numpy"


@pytest.fixture(scope="session")
def plan_backend(plan_backend_name):
    """The PlanBackend under test for this CI matrix lane."""
    from repro.core.planning_backend import get_backend
    try:
        return get_backend(plan_backend_name)
    except ImportError:
        pytest.skip(f"backend {plan_backend_name!r} needs jax, "
                    "which is not installed")

# Property-based tests use hypothesis (declared in pyproject's [test]
# extra).  Hermetic environments without it fall back to the in-repo
# deterministic subset so the six property-test modules still collect
# and run.
if importlib.util.find_spec("hypothesis") is None:
    sys.path.insert(0, os.path.dirname(__file__))
    from _hypothesis_fallback import install as _install_hypothesis_fallback
    _install_hypothesis_fallback()

# NOTE: do NOT set XLA_FLAGS / device counts here — smoke tests and benches
# must see the real single CPU device.  Multi-device tests spawn
# subprocesses with their own XLA_FLAGS (see test_dryrun_small.py).
