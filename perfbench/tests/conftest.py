"""perfbench's own tests: CPU only, small sizes, no chip.

The CPU backend is given five devices before JAX is imported: a ring
placement runs on a real mesh of the first four, and builds its graph on
the fifth (``graphs.host_device``), which no mesh holds."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=5").strip()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
