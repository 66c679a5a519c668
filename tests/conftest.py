import os
import sys

# NOTE: never set --xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device (the dry-run sets 512 itself).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import pytest  # noqa: E402

from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.parallel.sharding import Sharder  # noqa: E402


@pytest.fixture(scope="session")
def mesh():
    return make_test_mesh()


@pytest.fixture(scope="session")
def sharder(mesh):
    return Sharder(mesh)
