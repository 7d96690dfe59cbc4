"""Shared test configuration.

Registers hypothesis profiles so CI runs the property suites
deterministically (fixed seed via ``derandomize``, no wall-clock deadline
on shared runners).  Select with ``HYPOTHESIS_PROFILE=ci``; the default
profile only disables the deadline.  A missing hypothesis install keeps
everything importable — the property suites importorskip on their own.

The ``rans_engine`` fixture sends rANS jobs to the device engine or to the
numpy machine, whatever their size.
"""
import contextlib
import os
import sys

import pytest

try:
    from hypothesis import HealthCheck, settings

    settings.register_profile("default", settings(deadline=None))
    settings.register_profile(
        "ci",
        settings(
            deadline=None,
            derandomize=True,  # fixed example stream: CI failures reproduce
            print_blob=True,
            suppress_health_check=[HealthCheck.too_slow],
        ),
    )
    settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "default"))
except ImportError:  # property suites importorskip hypothesis themselves
    pass


@pytest.fixture
def rans_engine():
    """``with rans_engine("device"):`` runs every rANS job of K or more
    symbols a plane on the device engine, ``with rans_engine("numpy"):``
    every job on the numpy machine; the size thresholds of
    ``core.entropy._rans_device`` return when the block ends."""
    from repro.core import entropy

    @contextlib.contextmanager
    def engine(mode: str):
        with pytest.MonkeyPatch.context() as m:
            m.setattr(entropy, "_RANS_DEVICE_MIN", {"device": 0, "numpy": sys.maxsize}[mode])
            m.setattr(entropy, "_RANS_DEVICE_RAGGED_MIN_CPU", 0)
            yield

    return engine
