"""Test configuration.

Forces JAX onto the host CPU platform with 8 virtual devices BEFORE jax is
first imported anywhere in the test session — the standard JAX fake-cluster
trick (SURVEY.md §4) — so mesh/pjit/collective tests run without TPU
hardware. Bench and real-TPU runs do not go through this file.
"""

import os

# Force (not setdefault): a machine with a chip pre-sets JAX_PLATFORMS to
# it, but tests must run on the virtual CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Keep single-core CI boxes responsive — but stop at level 2 (INFO +
# WARNING suppressed, ERROR kept): GSPMD's "Involuntary full
# rematerialization" diagnostic is an E-level line that level 3 now
# SWALLOWS on this XLA version (the old "the warning bypasses level-3
# filtering" observation rotted), which silently blinded every
# SPMD-log-cleanliness assertion and its canary.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")


def pytest_configure(config):
    # The serving-subsystem marker (select with `-m serving`). Serving
    # unit tests are CPU-safe and thread-free in tier 1; the threaded
    # batcher paths (async coalescing, QPS soak) additionally carry
    # `slow` and stay out of the tier-1 run.
    config.addinivalue_line(
        "markers",
        "serving: dynamic-batching inference subsystem tests",
    )
    # Deterministic fault-injection / recovery tests (select with
    # `-m chaos` — the CI chaos step runs exactly this subset on CPU).
    # Fast single-fault legs run in tier 1; multi-restart soaks
    # additionally carry `slow`.
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection and recovery tests",
    )
