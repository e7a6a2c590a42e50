"""Multi-host (pod) runtime initialization.

The distributed communication backend (SURVEY.md §2.5): collectives are
XLA-compiled from sharding annotations and ride ICI within a pod slice and
DCN across slices — there is no hand-written NCCL/MPI-style layer, by
design. What remains host-side is bootstrapping the JAX distributed
runtime so all processes agree on topology, which this module owns, plus
small helpers for process-level facts the data pipeline needs.

Failure/recovery model (SURVEY.md §5): crash-restart with deterministic
resume — a failed pod job restarts, ``jax.distributed.initialize`` re-forms
the cluster, and the Experiment restores the latest orbax checkpoint; the
(seed, epoch)-keyed data pipeline makes the replay exact.
"""

import os
from typing import Optional

from zookeeper_tpu.core import Field, component


#: The one persistent compile-cache directory this program sets itself:
#: ``<checkout>/.jax_cache`` (git-ignored). The path is part of the
#: cache key, so it is never derived from a pid, a time or a tempdir.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache before the first
    compile and return its directory. Where ``JAX_COMPILATION_CACHE_DIR``
    is set the cache is placed from outside: jax reads the variable
    itself and nothing is touched here. Otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`. Every entry point that compiles (the task
    CLI, ``chip_smoke.py``, ``bench.py``, the fleet workers) calls this
    once, so separate processes of one checkout share compiled
    programs."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def _enable_cpu_collectives() -> None:
    """Select the gloo collectives implementation for the CPU backend
    before it is instantiated: without it, jax rejects every
    cross-process computation on CPU clusters ("Multiprocess
    computations aren't implemented on the CPU backend") — the local
    N-process dryrun/chaos legs and any gloo-backed CPU cluster need
    it. Only applies when the CPU platform was explicitly requested
    (``JAX_PLATFORMS=cpu`` / config)."""
    import jax

    platforms = (
        str(getattr(jax.config, "jax_platforms", None) or "")
        or os.environ.get("JAX_PLATFORMS", "")
    )
    if "cpu" in platforms.lower():
        jax.config.update("jax_cpu_collectives_implementation", "gloo")


def _single_host_tpu_env() -> bool:
    """Whether the TPU runtime's own environment says this process is
    the whole job: ``TPU_WORKER_HOSTNAMES`` names one host and no
    multislice coordinator is set. jax's auto-detection takes the same
    variables for a cluster and then asks the metadata server for its
    coordinator; on a sealed single-host machine (measured on the v5e
    host of PR 21) that costs 3 s and raises
    ``requests.exceptions.ConnectionError`` — so a job that is one host
    by its environment never calls it."""
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES")
    return (
        bool(hosts)
        and "," not in hosts
        and not os.environ.get("MEGASCALE_COORDINATOR_ADDRESS")
    )


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Initialize the JAX distributed runtime (idempotent).

    With no arguments, relies on the TPU environment's auto-detection
    (GCE metadata / megascale env), which is the normal path on Cloud TPU
    pods. No-op when already initialized, when the TPU environment names
    a single host, or when no cluster is detected.

    ``num_processes``/``process_id`` describe a MANUALLY-specified
    cluster and are meaningless without the coordinator every process
    rendezvouses at — passing them alone would silently fall into
    auto-detection with the explicit topology ignored, so that is a
    loud config error instead.
    """
    import jax

    if (
        num_processes is not None or process_id is not None
    ) and coordinator_address is None:
        raise ValueError(
            "num_processes/process_id were given without a "
            "coordinator_address: an explicit cluster topology needs "
            "the coordinator every process rendezvouses at (e.g. "
            "runtime.coordinator_address=10.0.0.1:8476). On TPU pods, "
            "pass NONE of the three and let auto-detection run."
        )
    if jax.distributed.is_initialized():
        return
    if coordinator_address is None and _single_host_tpu_env():
        return
    if coordinator_address is not None:
        # Only when actually forming a cluster: gloo with NO
        # distributed client breaks single-process CPU backend init.
        _enable_cpu_collectives()
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    try:
        jax.distributed.initialize(**kwargs)
    except (ValueError, RuntimeError) as e:
        if coordinator_address is not None:
            raise
        # Auto-detection unavailable (single host, no cluster env): fine.
        import logging

        logging.getLogger(__name__).debug(
            "jax.distributed.initialize skipped: %s", e
        )


@component
class DistributedRuntime:
    """Component wrapper so pod bootstrap is configurable from the CLI::

        python train.py Exp runtime.coordinator_address=10.0.0.2:1234 \\
            runtime.num_processes=8 runtime.process_id=0
    """

    coordinator_address: Optional[str] = Field(None)
    num_processes: int = Field(-1)
    process_id: int = Field(-1)
    enabled: bool = Field(True)

    def initialize(self) -> None:
        if not self.enabled:
            return
        initialize_distributed(
            coordinator_address=self.coordinator_address,
            num_processes=None if self.num_processes < 0 else self.num_processes,
            process_id=None if self.process_id < 0 else self.process_id,
        )
