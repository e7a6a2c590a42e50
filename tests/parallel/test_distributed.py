"""`initialize_distributed` hardening (loud config errors for explicit
topology without a coordinator, no second initialize) and the
compile-cache placement rule."""

import pytest

from zookeeper_tpu.core import configure
from zookeeper_tpu.parallel import (
    DistributedRuntime,
    enable_compile_cache,
    initialize_distributed,
)


def test_explicit_topology_without_coordinator_rejected():
    with pytest.raises(ValueError, match="coordinator_address"):
        initialize_distributed(num_processes=4)
    with pytest.raises(ValueError, match="coordinator_address"):
        initialize_distributed(process_id=1)


def test_runtime_component_surfaces_the_same_error():
    runtime = DistributedRuntime()
    configure(runtime, {"num_processes": 8}, name="rt_bad")
    with pytest.raises(ValueError, match="coordinator_address"):
        runtime.initialize()


def test_already_initialized_short_circuits(monkeypatch):
    """An initialized runtime makes initialize_distributed a no-op —
    it must not call jax.distributed.initialize again."""
    import jax

    monkeypatch.setattr(
        jax.distributed, "is_initialized", lambda: True, raising=False
    )

    def boom(**kwargs):  # pragma: no cover - must not run
        raise AssertionError("initialize called despite initialized state")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    initialize_distributed()


def test_compile_cache_placed_from_outside_is_untouched(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set the program sets no path of
    its own: jax reads the variable itself."""
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/outside")

    def boom(*args, **kwargs):  # pragma: no cover - must not run
        raise AssertionError("config touched despite the env placement")

    monkeypatch.setattr(jax.config, "update", boom)
    assert enable_compile_cache() == "/somewhere/outside"


def test_compile_cache_default_is_one_fixed_checkout_path(monkeypatch):
    import os

    import jax

    from zookeeper_tpu.parallel.distributed import COMPILE_CACHE_DIR

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = {}
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: seen.__setitem__(k, v)
    )
    repo = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    assert COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")
    assert enable_compile_cache() == COMPILE_CACHE_DIR
    assert enable_compile_cache() == COMPILE_CACHE_DIR  # same every call
    assert seen == {"jax_compilation_cache_dir": COMPILE_CACHE_DIR}


def test_single_host_tpu_env_never_calls_autodetect(monkeypatch):
    """A TPU environment that names one host (the sealed v5e machine:
    TPU_WORKER_HOSTNAMES=localhost) must not reach jax's auto-detection,
    which would query a metadata server that is not there."""
    import jax

    def boom(**kwargs):  # pragma: no cover - must not run
        raise AssertionError("auto-detection ran on a single-host env")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    monkeypatch.delenv("MEGASCALE_COORDINATOR_ADDRESS", raising=False)
    initialize_distributed()
    # Several hosts, or a multislice coordinator: detection still runs.
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "host-0,host-1")
    with pytest.raises(AssertionError, match="auto-detection ran"):
        initialize_distributed()
