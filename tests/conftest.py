"""Test configuration.

Device tests run on a simulated 8-device CPU mesh (SURVEY §4: the TPU analog
of "multi-node without a real cluster").  The env vars must be set before JAX
initializes its backends, hence here, before any test module imports jax.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Eight virtual CPU devices, whatever the ambient environment points JAX
# at: tests exercise the virtual mesh; chip_smoke.py is what runs on the
# real chip.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(
    [
        flag
        for flag in os.environ.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in flag
    ]
    + ["--xla_force_host_platform_device_count=8"]
)

# Tests build embedders without checkpoints on purpose (random-init +
# hash tokenizer on the tiny config); opt into the synthetic-params gate
# that production startup refuses (serve/__main__.py::build_embedder).
# The refusal itself is tested by deleting this var (test_gateway.py).
os.environ.setdefault("LWC_ALLOW_RANDOM_PARAMS", "1")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running; excluded from the tier-1 gate"
    )
    config.addinivalue_line(
        "markers",
        "chaos: seeded fault-injection suite (scripts/chaos.sh); also "
        "marked slow so tier-1 (-m 'not slow') never pays for it",
    )
    config.addinivalue_line(
        "markers",
        "soak: sustained-load / overload scenarios (scripts/chaos.sh "
        "overload+SIGTERM); always also marked slow",
    )
