"""Test configuration.

Force JAX onto a virtual 8-device CPU platform so multi-chip sharding logic
(mesh construction, shard_map kernels, collective layouts) is exercised
hermetically without TPU hardware — and so a test run on a machine that
has a chip never claims it.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

from protocol_tpu.utils.platform import force_host_cpu  # noqa: E402

force_host_cpu(8)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running (tier-1 runs with -m 'not slow')",
    )
