"""Test configuration: force an 8-virtual-device CPU platform.

The reference tests multi-GPU behaviour with real GPUs
(tests/multi_gpu_tests.sh); we instead exercise the identical SPMD code
paths on a virtual CPU mesh — XLA compiles the same collectives, so
sharding correctness transfers to real TPU slices.

The platform/device-count override must run before any backend use,
which import time guarantees (flexflow_tpu.comm.compat
.force_cpu_devices).  Tests never touch a chip; ``chip_smoke.py`` does.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from flexflow_tpu.comm.compat import force_cpu_devices  # noqa: E402

force_cpu_devices(8)

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from flexflow_tpu.parallel.mesh import build_mesh

    return build_mesh(jax.devices()[:8])
