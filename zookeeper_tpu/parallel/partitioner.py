"""Partitioner components: who owns the mesh and the shardings.

SNIPPETS.md [3]-style ``Partitioner`` abstraction (public pattern): the
training loop asks the partitioner to (a) place the initial state, (b)
provide the batch sharding for host->device prefetch, and (c) compile the
step function. Everything else — collectives, replication, donation — is
derived by XLA from the shardings.

- ``SingleDevicePartitioner``: plain ``jax.jit`` on the default device
  (BASELINE config #1, CPU/1-chip path).
- ``DataParallelPartitioner``: 1-D mesh over all devices, batch sharded on
  the ``data`` axis, state replicated; XLA inserts the gradient all-reduce
  over ICI (the MirroredStrategy+NCCL equivalent, SURVEY.md §2.5).
- ``MeshPartitioner``: general N-D mesh (``data``/``fsdp``/``model`` axes)
  with regex partition rules for tensor-parallel / FSDP layouts and batch
  sharded over all data-like axes.
"""

from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from zookeeper_tpu.core import Field, component
from zookeeper_tpu.observability.ledger import LedgeredExecutable
from zookeeper_tpu.parallel.rules import PartitionRule, match_partition_rules


@component
class Partitioner:
    """Abstract distribution strategy."""

    def setup(self) -> None:
        """Create the mesh (if any). Idempotent."""

    def _ledgered(self, kind: str, jitted: Any) -> LedgeredExecutable:
        """Wrap a compiled-seam callable so its (lazy) lower + compile
        is timed and recorded in the process program ledger
        (docs/DESIGN.md §14): identity key, XLA cost-analysis FLOPs,
        compile wall time, compiled memory analysis. The wrapper's
        steady-state dispatch is the AOT-compiled executable — the
        same program the jit would have cached, one attribute read
        away."""
        mesh = self.mesh
        mesh_desc = (
            "x".join(f"{k}:{v}" for k, v in mesh.shape.items())
            if mesh is not None
            else "1"
        )
        return LedgeredExecutable(
            jitted,
            kind=kind,
            key=f"{type(self).__name__}/mesh={mesh_desc}",
            attrs={"partitioner": type(self).__name__},
        )

    @property
    def mesh(self) -> Optional[Mesh]:
        return None

    def process_span(self) -> int:
        """How many DISTINCT JAX processes this partitioner's mesh
        spans (1 = meshless or single-host). The resilience stack keys
        on it: per-host sharded checkpointing and group recovery only
        engage when state/collectives actually cross a process
        boundary, and the multi-process dryrun asserts its mesh spans
        the whole group."""
        mesh = self.mesh
        if mesh is None:
            return 1
        return len({d.process_index for d in mesh.devices.flat})

    def prepare_model(self, model: Any) -> None:
        """Hook called before ``model.build()`` (the experiment does it
        in ``build_state``): a partitioner that owns part of the MODEL
        program — e.g. ``SequenceParallelPartitioner`` injecting its
        mesh-bound attention callable — wires it here, so recipes stay
        config-first instead of hand-wiring callables into models.
        Default: no-op."""

    def batch_sharding(self) -> Optional[NamedSharding]:
        """Sharding for host->device prefetch of batches (None = default
        device placement)."""
        return None

    def slab_sharding(self) -> Optional[NamedSharding]:
        """Sharding for ``[unroll, batch, ...]`` SLABS (the fused
        multi-step loop's input unit): the leading unroll axis is the
        scan dimension and stays unsharded; the batch axis (now axis 1)
        carries the data-parallel sharding. None = default placement."""
        return None

    def shard_state(self, state: Any) -> Any:
        """Place the freshly-initialized state onto devices."""
        return state

    def state_sharding(self, state: Any) -> Any:
        """Sharding pytree (or prefix) describing the placed state."""
        return None

    def compile_step(
        self, step_fn: Callable, state: Any, *, donate_state: bool = True
    ) -> Callable:
        """Compile ``(state, batch) -> (state, metrics)``."""
        raise NotImplementedError

    def compile_multi_step(
        self,
        multi_step_fn: Callable,
        state: Any,
        *,
        donate_state: bool = True,
        donate_slab: bool = False,
    ) -> Callable:
        """Compile a fused ``(state, slab) -> (state, stacked_metrics)``
        multi-step (``training.step.build_multi_step`` output).
        ``donate_slab`` stays off by default: donation is input->OUTPUT
        aliasing, and no output shares the slab's ``[unroll, batch,
        ...]`` shape, so donating it buys nothing and XLA warns on
        every compile. The slab's HBM frees normally when the loop
        drops its reference after the dispatch."""
        raise NotImplementedError

    def compile_eval(self, eval_fn: Callable, state: Any) -> Callable:
        """Compile ``(state, batch) -> metrics``."""
        raise NotImplementedError

    def variables_sharding(self, variables: Any) -> Any:
        """Sharding pytree for an inference variables dict
        (``{"params": ..., **model_state}`` — no optimizer state). Paths
        match the same partition rules as training state (``params/...``
        prefixes are identical), so a model serves under the layout it
        trained with. None = default placement."""
        return None

    def compile_forward(
        self, forward_fn: Callable, variables: Any, *,
        batch_rows: Optional[int] = None,
    ) -> Callable:
        """Compile an inference forward ``(variables, batch) -> outputs``
        for the serving engine. DONATION-SAFE by contract: unlike the
        train step's consumed state, the variables serve every subsequent
        request and must never be donated; the batch is not donated
        either (no output aliases its shape — donating would buy nothing
        and warn on every compile, the ``donate_slab`` lesson).

        ``batch_rows`` is the concrete bucket size being compiled (the
        serving engine compiles per shape bucket, so it always knows):
        mesh partitioners use it to fall back to a REPLICATED batch when
        the bucket cannot split over the data axes (a 1-row request on
        an 8-way mesh) — correct everywhere, wasteful only on the small
        buckets; size the bucket ladder in multiples of the data-axis
        product to serve fully sharded."""
        raise NotImplementedError

    def decode_cache_axes(self) -> Tuple[Tuple[str, ...], Optional[str]]:
        """``(data_axes, model_axis)`` the decode engine's per-slot
        operands and its page pool's heads shard over — the ONE
        derivation both :meth:`page_pool_sharding` and the decode
        engine's sharded attention wrapper
        (``ops.sharded_pool_paged_decode_attention``) consume: if the
        two disagreed, GSPMD would reshard/gather the pool around the
        kernel on every decode step — token-correct output, silently
        wrong bytes. Default (no mesh): nothing to shard over."""
        return (), None

    def page_pool_sharding(self, pool: Any) -> Any:
        """Sharding pytree for a decode engine's SHARED page-pool state
        (docs/DESIGN.md §20): per-layer ``k``/``v`` pools ``[num_pages,
        head_shards, page_size, row_width]`` (+ int8 scale arrays).
        Pages replicate over the data axes (any slot references any
        page), the head shards shard over the model axis via
        :func:`zookeeper_tpu.parallel.rules.page_pool_rules`. The
        ENGINE checks divisibility and falls back to replicated pool
        state when the shapes cannot split — the same degrade-don't-die
        posture ``compile_forward``'s small buckets take. None =
        default placement (single device)."""
        return None


@component
class SingleDevicePartitioner(Partitioner):
    """Plain jit on the default device."""

    def compile_step(self, step_fn, state, *, donate_state: bool = True):
        return self._ledgered(
            "train_step",
            jax.jit(step_fn, donate_argnums=(0,) if donate_state else ()),
        )

    def compile_multi_step(
        self,
        multi_step_fn,
        state,
        *,
        donate_state: bool = True,
        donate_slab: bool = False,
    ):
        donate = tuple(
            i
            for i, d in enumerate((donate_state, donate_slab))
            if d
        )
        return self._ledgered(
            "multi_step", jax.jit(multi_step_fn, donate_argnums=donate)
        )

    def compile_eval(self, eval_fn, state):
        return self._ledgered("eval_step", jax.jit(eval_fn))

    def compile_forward(self, forward_fn, variables, *, batch_rows=None):
        return jax.jit(forward_fn)


def _device_mesh(
    axis_sizes: Sequence[int],
    axis_names: Sequence[str],
    num_devices: int = -1,
    devices: Optional[Sequence[Any]] = None,
) -> Mesh:
    """Build a mesh over the first ``num_devices`` devices (-1 = all).
    ``-1`` in ``axis_sizes`` infers that axis from the device count (like
    reshape). An explicit ``devices`` list overrides both — the
    role-aware seam (docs/DESIGN.md §22): a disaggregated topology
    carves the host's devices into disjoint prefill/decode slices, so
    "first N" cannot express the second role's slice."""
    all_devices = (
        list(devices) if devices is not None else jax.devices()
    )
    if devices is None and num_devices > 0:
        if num_devices > len(all_devices):
            raise ValueError(
                f"Requested {num_devices} devices, have {len(all_devices)}."
            )
        all_devices = all_devices[:num_devices]
    devices = np.asarray(all_devices)
    n = devices.size
    sizes = list(axis_sizes)
    if sizes.count(-1) > 1:
        raise ValueError("At most one mesh axis may be -1.")
    known = int(np.prod([s for s in sizes if s != -1])) if sizes else 1
    if -1 in sizes:
        if n % known != 0:
            raise ValueError(
                f"Device count {n} not divisible by fixed axes {known}."
            )
        sizes[sizes.index(-1)] = n // known
    if int(np.prod(sizes)) != n:
        raise ValueError(
            f"Mesh {dict(zip(axis_names, sizes))} needs "
            f"{int(np.prod(sizes))} devices, have {n}."
        )
    try:
        from jax.experimental import mesh_utils

        # Pass the (possibly subset) device list explicitly: without it,
        # create_device_mesh sizes itself against the full host and always
        # fails for subsets, losing ICI-topology-aware placement.
        dev_array = mesh_utils.create_device_mesh(sizes, devices=list(devices))
    except (ValueError, NotImplementedError) as e:
        # Only the no-known-good-assignment case falls back; anything else
        # should surface. The naive order loses ICI-topology awareness, so
        # say so.
        import warnings

        warnings.warn(
            f"mesh_utils.create_device_mesh failed ({e}); falling back to "
            "enumeration-order device layout, which may place mesh "
            "neighbors across slow ICI links.",
            stacklevel=2,
        )
        dev_array = devices.reshape(sizes)
    return Mesh(dev_array, tuple(axis_names))


@component
class MeshPartitioner(Partitioner):
    """General N-D mesh partitioner.

    ``mesh_shape``/``mesh_axes`` define the mesh (e.g. ``(-1, 8)`` with
    ``('data', 'model')``); ``data_axes`` names the axes the batch dimension
    is sharded over (DP and FSDP axes both carry batch); ``rules`` maps
    param paths to PartitionSpecs (empty = fully replicated params).
    """

    mesh_shape: Sequence[int] = Field((-1,))
    mesh_axes: Sequence[str] = Field(("data",))
    data_axes: Sequence[str] = Field(("data",))
    #: Use only the first N devices (-1 = all); lets dry runs build an
    #: n-device mesh on hosts exposing more.
    num_devices: int = Field(-1)

    _mesh: Optional[Mesh] = None
    _rules: List[PartitionRule] = []

    def with_rules(self, rules: Sequence[PartitionRule]) -> "MeshPartitioner":
        """Set param partition rules (programmatic, since PartitionSpecs are
        not CLI-expressible). Returns self for chaining."""
        object.__setattr__(self, "_rules_override", list(rules))
        return self

    def with_devices(self, devices: Sequence[Any]) -> "MeshPartitioner":
        """Pin the mesh to an EXPLICIT device list (programmatic, like
        ``with_rules`` — device objects are not CLI-expressible):
        the role-aware seam a :class:`~zookeeper_tpu.serving.disagg.\
partition.DisaggPartitioner` uses to put its prefill and decode roles
        on disjoint device slices. Must be called before the mesh is
        built. Returns self for chaining."""
        if self._mesh is not None:
            raise RuntimeError(
                "with_devices after the mesh was built; pin devices "
                "before the first setup()/mesh access."
            )
        object.__setattr__(self, "_devices_override", list(devices))
        return self

    @property
    def rules(self) -> List[PartitionRule]:
        return getattr(self, "_rules_override", self._rules)

    def setup(self) -> None:
        if self._mesh is None:
            object.__setattr__(
                self,
                "_mesh",
                _device_mesh(
                    tuple(self.mesh_shape),
                    tuple(self.mesh_axes),
                    self.num_devices,
                    devices=getattr(self, "_devices_override", None),
                ),
            )

    @property
    def mesh(self) -> Optional[Mesh]:
        self.setup()
        return self._mesh

    def batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec(tuple(self.data_axes)))

    def slab_sharding(self) -> NamedSharding:
        # Leading unroll (scan) axis replicated, batch axis sharded over
        # the data axes — each device holds its batch slice of EVERY
        # step in the slab, so the scanned per-step batch carries
        # exactly the batch_sharding() layout.
        return NamedSharding(
            self.mesh, PartitionSpec(None, tuple(self.data_axes))
        )

    def state_sharding(self, state: Any) -> Any:
        """Per-leaf shardings for the whole TrainState.

        The partition rules are matched against full state paths
        (``params/Dense_0/kernel``, ``opt_state/0/mu/Dense_0/kernel``), so
        a rule like ``("kernel", P(None, "model"))`` shards the parameter
        AND its Adam moments identically — which is exactly the invariant
        sharded optimizers need. Unmatched leaves (step, batch_stats,
        counters) replicate.
        """
        return self._sharding_from_rules(state, self.rules)

    def _sharding_from_rules(
        self, state: Any, rules: Sequence[PartitionRule]
    ) -> Any:
        mesh = self.mesh
        specs = match_partition_rules(rules, state)
        return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)

    def shard_state(self, state: Any) -> Any:
        sharding = self.state_sharding(state)
        if self.process_span() > 1:
            # Cross-process mesh: device_put of a host-local value onto
            # a non-addressable sharding asserts value equality via a
            # collective broadcast — unsupported on CPU clusters and
            # wasted work on pods. Every process initialized the SAME
            # state (same seed — the determinism contract), so each
            # assembles the global array from its own local copy
            # instead, shard by addressable shard.
            def place(x, s):
                arr = np.asarray(x)
                return jax.make_array_from_callback(
                    arr.shape, s, lambda idx: arr[idx]
                )

            return jax.tree.map(place, state, sharding)
        return jax.tree.map(
            lambda x, s: jax.device_put(x, s),
            state,
            sharding,
        )

    def _with_activation_scope(self, fn: Callable) -> Callable:
        """Wrap ``fn`` so it traces inside this mesh's activation-sharding
        scope: layer code (Quant* layers) pins batch-dim activation
        shardings to the data axes via
        :func:`zookeeper_tpu.parallel.sharding.constrain_batch_sharded`,
        which keeps GSPMD from spreading the batch over non-data axes in
        the backward (the dp×tp involuntary-rematerialization trigger —
        see that module's docstring)."""
        import functools

        from zookeeper_tpu.parallel.sharding import activation_sharding_scope

        mesh, data_axes = self.mesh, tuple(self.data_axes)
        # Non-data mesh axes carry tensor-parallel channel shardings.
        model_axes = tuple(
            a for a in self.mesh_axes if a not in set(data_axes)
        )

        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with activation_sharding_scope(mesh, data_axes, model_axes):
                return fn(*args, **kwargs)

        return scoped

    def compile_step(self, step_fn, state, *, donate_state: bool = True):
        state_sh = self.state_sharding(state)
        batch_sh = self.batch_sharding()
        metrics_sh = NamedSharding(self.mesh, PartitionSpec())
        return self._ledgered(
            "train_step",
            jax.jit(
                self._with_activation_scope(step_fn),
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, metrics_sh),
                donate_argnums=(0,) if donate_state else (),
            ),
        )

    def compile_multi_step(
        self,
        multi_step_fn,
        state,
        *,
        donate_state: bool = True,
        donate_slab: bool = False,
    ):
        state_sh = self.state_sharding(state)
        slab_sh = self.slab_sharding()
        # Stacked [unroll] per-step metrics replicate like the single
        # step's scalars (PartitionSpec() is rank-agnostic).
        metrics_sh = NamedSharding(self.mesh, PartitionSpec())
        donate = tuple(
            i for i, d in enumerate((donate_state, donate_slab)) if d
        )
        return self._ledgered(
            "multi_step",
            jax.jit(
                self._with_activation_scope(multi_step_fn),
                in_shardings=(state_sh, slab_sh),
                out_shardings=(state_sh, metrics_sh),
                donate_argnums=donate,
            ),
        )

    def compile_eval(self, eval_fn, state):
        state_sh = self.state_sharding(state)
        batch_sh = self.batch_sharding()
        return self._ledgered(
            "eval_step",
            jax.jit(
                self._with_activation_scope(eval_fn),
                in_shardings=(state_sh, batch_sh),
                out_shardings=NamedSharding(self.mesh, PartitionSpec()),
            ),
        )

    def variables_sharding(self, variables: Any) -> Any:
        # Same rule table as training state: rules are matched against
        # full paths, and an inference dict's ``params/...`` /
        # ``batch_stats/...`` paths are exactly the training prefixes.
        return self._sharding_from_rules(variables, self.rules)

    def decode_cache_axes(self):
        data_axes = tuple(self.data_axes)
        model_axes = tuple(
            a for a in self.mesh_axes if a not in set(data_axes)
        )
        return data_axes, (model_axes[0] if model_axes else None)

    def page_pool_sharding(self, pool: Any) -> Any:
        from zookeeper_tpu.parallel.rules import page_pool_rules

        data_axes, model_axis = self.decode_cache_axes()
        rules = page_pool_rules(data_axes, model_axis)
        return self._sharding_from_rules(pool, rules)

    def compile_forward(self, forward_fn, variables, *, batch_rows=None):
        vars_sh = self.variables_sharding(variables)
        batch_sh = self.batch_sharding()
        scoped = self._with_activation_scope(forward_fn)
        if batch_rows is not None:
            total = int(
                np.prod([self.mesh.shape[a] for a in self.data_axes])
            )
            if batch_rows % total != 0:
                # A bucket that cannot split over the data axes (e.g.
                # the 1-row bucket on an 8-way mesh) runs REPLICATED —
                # every device computes the whole small batch. Correct
                # always; only the sub-mesh buckets pay the redundancy.
                # The activation scope would re-pin batch dims to the
                # data axes inside the trace and fight the replicated
                # in_sharding, so it is dropped for these buckets.
                repl = NamedSharding(self.mesh, PartitionSpec())
                return jax.jit(
                    forward_fn,
                    in_shardings=(vars_sh, repl),
                    out_shardings=repl,
                )
        # Outputs keep the batch-sharded layout (PartitionSpec is
        # rank-agnostic on trailing dims): the serving readback slices
        # per-request rows on host, so replicating (an all-gather) would
        # be pure waste. No donation — see the base-class contract.
        return jax.jit(
            scoped,
            in_shardings=(vars_sh, batch_sh),
            out_shardings=batch_sh,
        )


@component
class DataParallelPartitioner(MeshPartitioner):
    """Pure DP: 1-D mesh, batch on 'data', everything replicated (the
    MeshPartitioner defaults, under the name users reach for)."""


@component
class FsdpPartitioner(MeshPartitioner):
    """Turnkey FSDP: 1-D mesh, batch AND large weights sharded over the
    same ``fsdp`` axis (ZeRO-3-style — see
    :func:`zookeeper_tpu.parallel.rules.auto_fsdp_rules`). Per-device
    param + optimizer memory drops ~N-fold for the sharded weights; XLA
    inserts the per-layer weight all-gathers and gradient
    reduce-scatters over ICI. Explicit ``with_rules`` overrides the
    auto-generated layout.
    """

    mesh_shape: Sequence[int] = Field((-1,))
    mesh_axes: Sequence[str] = Field(("fsdp",))
    data_axes: Sequence[str] = Field(("fsdp",))
    #: Parameters below this many ELEMENTS replicate (biases, BN):
    #: sharding tiny tensors costs more collective latency than it saves.
    min_weight_size: int = Field(2**15)
    #: Regexes over params-relative paths forced to replicate regardless
    #: of size — the escape hatch for large grouped/depthwise conv
    #: kernels, whose FSDP-sharded weight gradients hit a GSPMD
    #: full-rematerialization reshard (see rules.auto_fsdp_rules).
    replicate_patterns: Sequence[str] = Field(())

    def _auto_rules(self, params: Any) -> List[PartitionRule]:
        from zookeeper_tpu.parallel.rules import auto_fsdp_rules

        axis = tuple(self.mesh_axes)[0]
        return auto_fsdp_rules(
            params,
            axis_size=self.mesh.shape[axis],
            fsdp_axis=axis,
            min_weight_size=self.min_weight_size,
            replicate_patterns=tuple(self.replicate_patterns),
        )

    def state_sharding(self, state: Any) -> Any:
        # An explicit with_rules (even an empty list = replicate all)
        # always wins; otherwise rules derive from THIS state's params on
        # every call — no caching, so reusing one partitioner across
        # differently-shaped states cannot silently apply stale rules.
        if getattr(self, "_rules_override", None) is not None:
            return super().state_sharding(state)
        return self._sharding_from_rules(state, self._auto_rules(state.params))

    def variables_sharding(self, variables: Any) -> Any:
        # Serving under FSDP: derive the same auto layout from the
        # inference dict's params (suffix-anchored rules, so the
        # ``params/`` prefix matches like training state paths).
        if getattr(self, "_rules_override", None) is not None:
            return super().variables_sharding(variables)
        return self._sharding_from_rules(
            variables, self._auto_rules(variables["params"])
        )
