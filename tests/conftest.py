"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's distributed-in-one-box testing strategy
(tests/unit/common.py): multi-device behavior is exercised on a single host via
XLA's host-platform device virtualization. The suite never touches an
accelerator: what the chip does is checked by ``chip_smoke.py`` on the chip,
and what the Pallas kernels lower to by ``test_kernels_tpu_lowering.py``.

    JAX_PLATFORMS=cpu python -m pytest tests/ -x -q        (or ``make test``)
"""

import os
import sys

# Hard-set (not setdefault): a TPU host exports its own platform, and a test
# process that grabbed the chip would starve the one process allowed to hold it.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
