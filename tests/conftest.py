# NOTE: do NOT set --xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device (the 512-device flag is dryrun.py-only).
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _span_recorder_off():
    """The serving path's span recorder (repro.serve.spans) is process-wide:
    a test that turns it on, or loads a benchmark reader that does, leaves
    it off for the next test."""
    yield
    mod = sys.modules.get("repro.serve.spans")
    if mod is not None:
        mod.disable()
