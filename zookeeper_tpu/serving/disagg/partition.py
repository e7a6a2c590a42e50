"""Role-aware partitioning: one host, two mesh slices (docs/DESIGN.md §22).

Disaggregated serving runs the two decode-subsystem programs on
DIFFERENT device slices: prefill (compute-bound, batched wide) on one,
the decode step (memory-bound, latency-critical) on the other. The
existing partitioners cannot express that — ``num_devices`` always
takes the FIRST N devices, so two of them would overlap. This module
adds the topology object:

- :class:`DisaggPartitioner` — owns two :class:`~zookeeper_tpu.parallel
  .partitioner.MeshPartitioner` roles pinned to disjoint device slices
  via ``with_devices`` (the programmatic seam added for exactly this).
  Device counts resolve at ``setup()``: explicit ``prefill_devices`` /
  ``decode_devices`` or an even split of the host. When the host
  cannot provide disjoint slices (the 1-device CPU tier-1 case) the
  roles OVERLAP from device 0 — functionally identical, flagged in
  ``describe()`` so an operator never mistakes the portable fallback
  for real disaggregation.

The class is itself a :class:`~zookeeper_tpu.parallel.partitioner.
Partitioner` delegating to the DECODE role (the latency-critical slice
is the service's "default" placement), so anything written against the
ABC — observability, resilience probes — keeps working unchanged.
"""

from typing import Any, Optional, Tuple

from zookeeper_tpu.core import ComponentField, Field, component
from zookeeper_tpu.parallel.partitioner import MeshPartitioner, Partitioner

__all__ = ["DisaggPartitioner"]


@component
class DisaggPartitioner(Partitioner):
    """Two-role device topology: a prefill mesh slice and a decode mesh
    slice over one host's devices (see module docstring)."""

    #: Devices for the prefill role (-1 = half the host, rounded down,
    #: at least 1).
    prefill_devices: int = Field(-1)
    #: Devices for the decode role (-1 = the rest of the host, at
    #: least 1).
    decode_devices: int = Field(-1)
    #: Per-role mesh partitioners (CLI-configurable mesh axes, e.g.
    #: ``partitioner.prefill_mesh.mesh_shape=(-1,2)``); their device
    #: lists are pinned HERE at setup — ``num_devices`` on the roles is
    #: ignored by construction.
    prefill_mesh: MeshPartitioner = ComponentField(MeshPartitioner)
    decode_mesh: MeshPartitioner = ComponentField(MeshPartitioner)

    # -- topology resolution ---------------------------------------------

    def setup(self) -> None:
        """Resolve the device split and build both role meshes.
        Idempotent."""
        if getattr(self, "_roles_ready", False):
            return
        import jax

        devices = list(jax.devices())
        n = len(devices)
        pn = int(self.prefill_devices)
        dn = int(self.decode_devices)
        if pn == 0 or dn == 0 or pn < -1 or dn < -1:
            raise ValueError(
                f"prefill_devices={pn} / decode_devices={dn} must be "
                ">= 1 per role (-1 = auto split)."
            )
        if pn < 0:
            pn = max(1, n // 2)
        if dn < 0:
            dn = max(1, n - pn)
        if pn > n or dn > n:
            raise ValueError(
                f"role sizes prefill={pn} / decode={dn} exceed the "
                f"host's {n} devices."
            )
        disjoint = pn + dn <= n
        if disjoint:
            prefill_devs = devices[:pn]
            decode_devs = devices[pn:pn + dn]
        else:
            # Overlapping fallback (e.g. the 1-device CPU host): both
            # roles from device 0. The page transfer degenerates to a
            # same-device move — every protocol step still runs, which
            # is exactly what the tier-1 certification needs.
            prefill_devs = devices[:pn]
            decode_devs = devices[:dn]
        self.prefill_mesh.with_devices(prefill_devs)
        self.decode_mesh.with_devices(decode_devs)
        self.prefill_mesh.setup()
        self.decode_mesh.setup()
        object.__setattr__(self, "_disjoint", disjoint)
        object.__setattr__(self, "_roles_ready", True)

    @property
    def prefill(self) -> MeshPartitioner:
        """The prefill role's partitioner (mesh built)."""
        self.setup()
        return self.prefill_mesh

    @property
    def decode(self) -> MeshPartitioner:
        """The decode role's partitioner (mesh built)."""
        self.setup()
        return self.decode_mesh

    @property
    def disjoint(self) -> bool:
        """Whether the two roles landed on disjoint device slices
        (False = the overlapping single-host fallback)."""
        self.setup()
        return bool(self._disjoint)

    def describe(self) -> dict:
        """The ``/statusz`` topology section: per-role device lists and
        whether the slices are genuinely disjoint."""
        self.setup()
        return {
            "disjoint": bool(self._disjoint),
            "prefill_devices": [
                str(d) for d in self.prefill_mesh.mesh.devices.flat
            ],
            "decode_devices": [
                str(d) for d in self.decode_mesh.mesh.devices.flat
            ],
        }

    # -- Partitioner ABC: delegate to the DECODE role --------------------
    #
    # The decode slice is the service's default placement (the
    # latency-critical role); code written against the ABC — probes,
    # ledger keys, resilience checks — sees that mesh. The prefill role
    # is reached explicitly via ``.prefill``.

    @property
    def mesh(self):
        return self.decode.mesh

    def prepare_model(self, model: Any) -> None:
        self.decode.prepare_model(model)

    def batch_sharding(self):
        return self.decode.batch_sharding()

    def slab_sharding(self):
        return self.decode.slab_sharding()

    def shard_state(self, state: Any) -> Any:
        return self.decode.shard_state(state)

    def state_sharding(self, state: Any) -> Any:
        return self.decode.state_sharding(state)

    def compile_step(self, step_fn, state, *, donate_state: bool = True):
        return self.decode.compile_step(
            step_fn, state, donate_state=donate_state
        )

    def compile_multi_step(
        self,
        multi_step_fn,
        state,
        *,
        donate_state: bool = True,
        donate_slab: bool = False,
    ):
        return self.decode.compile_multi_step(
            multi_step_fn,
            state,
            donate_state=donate_state,
            donate_slab=donate_slab,
        )

    def compile_eval(self, eval_fn, state):
        return self.decode.compile_eval(eval_fn, state)

    def variables_sharding(self, variables: Any) -> Any:
        return self.decode.variables_sharding(variables)

    def compile_forward(self, forward_fn, variables, *, batch_rows=None):
        return self.decode.compile_forward(
            forward_fn, variables, batch_rows=batch_rows
        )

    def decode_cache_axes(self) -> Tuple[Tuple[str, ...], Optional[str]]:
        return self.decode.decode_cache_axes()

    def page_pool_sharding(self, pool: Any) -> Any:
        return self.decode.page_pool_sharding(pool)
