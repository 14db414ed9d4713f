"""``GLISPSystem`` — the single front door to the GLISP stack.

    from repro.api import GLISPConfig, GLISPSystem

    system = GLISPSystem.build(g, GLISPConfig(num_parts=4, fanouts=(15, 10, 5)))
    ticket = system.submit(seeds)                   # async request plan
    sub = ticket.result()                           # Gather-Apply K-hop
    sub = system.sample(seeds)                      # blocking convenience
    for seeds, batch in system.loader(train_ids):   # prefetching pipeline
        ...
    trainer = system.train(model, train_ids, epochs=2)
    result = system.infer_layerwise(layer_fns, workdir)

``build`` runs partitioner -> partition materialization -> sampling service,
each resolved by name from the registries in ``repro.api.backends``; no
caller ever wires ``SamplingServer`` / ``VertexRouter`` by hand again.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from repro.api.backends import (
    CACHE_POLICIES,
    REORDERS,
    SAMPLERS,
    GatherApplyBackend,
    PartitionPipeline,
    PartitionPlan,
    SamplerBackend,
)
from repro.api.config import GLISPConfig
from repro.api.pipeline import BatchPipeline
from repro.graph.graph import GraphPartition, HeteroGraph
from repro.graph.metrics import partition_metrics

__all__ = ["GLISPSystem"]


@dataclass
class GLISPSystem:
    graph: HeteroGraph
    config: GLISPConfig
    plan: PartitionPlan
    partitions: list[GraphPartition]
    backend: SamplerBackend
    partition_seconds: float = 0.0
    # True when the partition/reorder artifacts were loaded from the
    # content-addressed pipeline cache instead of computed
    partition_cache_hit: bool = False
    # reorder permutation from the pipeline (perm[new_id] = old vertex id),
    # grouped by the plan's per-vertex partition per config.reorder
    reorder_perm: np.ndarray | None = field(default=None, repr=False)
    pipeline_seconds: dict = field(default_factory=dict, repr=False)
    _metrics: dict | None = field(default=None, repr=False)
    # (signature, engine, pinned refs) for infer_layerwise reuse: repeat
    # calls with the same resolved parameters hit the same engine, so its
    # jitted (layer, bucket) slices never recompile across calls
    _infer_cache: tuple | None = field(default=None, repr=False)

    # ------------------------------------------------------------------
    @classmethod
    def build(
        cls,
        graph: HeteroGraph,
        config: GLISPConfig | None = None,
        *,
        cache_dir: str | None = None,
        **overrides,
    ):
        """Compose the full system from a config (plus keyword overrides).

        Partitioning runs through the cached ``PartitionPipeline``:
        ``cache_dir`` (or ``config.partition_cache_dir``) names an on-disk
        artifact cache keyed by graph content + partition config, so a
        second build over the same inputs skips repartitioning entirely
        (``partition_cache_hit`` reports which path was taken)."""
        config = (config or GLISPConfig()).replace(**overrides).validate()
        pipeline = PartitionPipeline(
            config.partitioner,
            config.num_parts,
            reorder=config.reorder,
            seed=config.seed,
            direction=config.direction,
            cache_dir=(
                cache_dir if cache_dir is not None else config.partition_cache_dir
            ),
        )
        res = pipeline.run(graph)
        plan = res.plan
        if config.balance_partitions and plan.vertex_owner is None:
            raise ValueError(
                "balance_partitions needs per-vertex owners, which only "
                "vertex partitioners produce (e.g. partitioner='ldg'); "
                f"{config.partitioner!r} yields a vertex-cut edge assignment"
            )
        backend = SAMPLERS.get(config.sampler)(graph, plan, res.partitions, config)
        return cls(
            graph=graph,
            config=config,
            plan=plan,
            partitions=res.partitions,
            backend=backend,
            partition_seconds=res.partition_seconds,
            partition_cache_hit=res.cache_hit,
            reorder_perm=res.perm,
            pipeline_seconds=res.seconds,
        )

    # -- sampling ------------------------------------------------------
    @property
    def service(self):
        """The shared ``SamplingService`` (servers, scheduler, counters)."""
        return self.backend.service

    @property
    def client(self):
        """Legacy alias for :attr:`service` (workload counters live here)."""
        return self.backend.service

    def submit(
        self,
        seeds: np.ndarray,
        spec=None,
        *,
        key=None,
        fanouts=None,
        weighted: bool | None = None,
        direction: str | None = None,
        replace: bool | None = None,
    ):
        """Submit an asynchronous sample request; returns a ``SampleTicket``.

        The plan is ``spec`` (a ``SamplingSpec``) or the config's spec with
        per-call overrides.  Multiple tickets may ride in flight at once —
        the service overlaps their hops and coalesces shared frontier
        seeds; ``ticket.result()`` is bit-identical either way."""
        if spec is None:
            spec = self.config.sampling_spec(
                fanouts=fanouts,
                weighted=weighted,
                direction=direction,
                replace=replace,
            )
        elif any(
            x is not None for x in (fanouts, weighted, direction, replace)
        ):
            raise ValueError(
                "pass either a SamplingSpec or individual "
                "fanouts/weighted/direction/replace overrides, not both"
            )
        return self.backend.submit(seeds, spec, key=key)

    def sample(
        self,
        seeds: np.ndarray,
        fanouts=None,
        *,
        spec=None,
        weighted: bool | None = None,
        direction: str | None = None,
        replace: bool | None = None,
        key=None,
    ):
        """Blocking convenience: ``submit(...).result()``.

        Pass ``key=`` to pin the request's RNG key; without it the service
        assigns a sequence key (fine for a lone blocking caller, not for
        code sharing the service with other submitters)."""
        # timeout=None defers to the service's configured ticket_timeout
        return self.submit(
            seeds,
            spec,
            fanouts=fanouts,
            weighted=weighted,
            direction=direction,
            replace=replace,
            key=key,
        ).result(timeout=None)

    def partition_metrics(self) -> dict:
        if self._metrics is None:
            self._metrics = partition_metrics(
                self.partitions, self.graph.num_vertices
            )
        return self._metrics

    def server_workloads(self) -> np.ndarray:
        return self.backend.server_workloads()

    def server_health(self) -> dict:
        """Health of every sampling server replica (circuit-breaker view):
        ``{"server.<part>.<replica>": "up" | "quarantined"}``."""
        return self.service.server_health()

    def reset_stats(self) -> None:
        self.backend.reset_stats()

    # -- lifecycle -----------------------------------------------------
    def close(self, timeout: float = 2.0) -> None:
        """Release owned OS resources — today that is the remote sampling
        worker pool when ``dist_transport != "inproc"``.  Idempotent; the
        in-process system is a no-op, so unconditional cleanup is cheap."""
        close = getattr(self.backend, "close", None)
        if close is not None:
            close(timeout=timeout)

    def __enter__(self) -> "GLISPSystem":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    # -- batch pipeline ------------------------------------------------
    def loader(
        self,
        seeds: np.ndarray,
        num_layers: int | None = None,
        *,
        batch_size: int | None = None,
        prefetch: int | None = None,
        seed: int | None = None,
        fanouts=None,
        spec=None,
        inflight: int | None = None,
        feature_source=None,
    ) -> BatchPipeline:
        """A prefetching seed->batch pipeline over this system's service.

        ``feature_source`` (a ``repro.core.storage.FeatureSource``) swaps
        the in-memory feature matrix for e.g. a disk-backed tiered store —
        batches are bit-identical either way."""
        cfg = self.config
        partition_of = (
            self.plan.vertex_owner if cfg.balance_partitions else None
        )
        if spec is None:
            spec = cfg.sampling_spec(fanouts=fanouts)
        elif fanouts is not None:
            raise ValueError("pass either a SamplingSpec or fanouts, not both")
        return BatchPipeline(
            self.backend,
            self.graph,
            seeds,
            list(spec.fanouts),
            num_layers if num_layers is not None else len(spec.fanouts),
            batch_size=batch_size if batch_size is not None else cfg.batch_size,
            spec=spec,
            prefetch=prefetch if prefetch is not None else cfg.prefetch,
            inflight=inflight if inflight is not None else cfg.inflight,
            seed=cfg.seed if seed is None else seed,
            partition_of=partition_of,
            balance_partitions=cfg.balance_partitions,
            vertex_quantum=cfg.vertex_quantum,
            edge_quantum=cfg.edge_quantum,
            feature_source=feature_source,
            ticket_timeout=cfg.ticket_timeout,
            worker_respawns=cfg.worker_respawns,
        )

    # -- training ------------------------------------------------------
    def trainer(
        self,
        model,
        train_ids: np.ndarray,
        *,
        opt=None,
        batch_size: int | None = None,
        prefetch: int | None = None,
        spec=None,
        inflight: int | None = None,
        feature_source=None,
    ):
        """A ``GNNTrainer`` wired to this system's backend and config."""
        from repro.train.loop import GNNTrainer  # lazy: avoids import cycle

        cfg = self.config
        spec = spec if spec is not None else cfg.sampling_spec()
        return GNNTrainer(
            model,
            self.backend,
            self.graph,
            list(spec.fanouts),
            train_ids,
            batch_size=batch_size if batch_size is not None else cfg.batch_size,
            opt=opt,
            spec=spec,
            seed=cfg.seed,
            prefetch=prefetch if prefetch is not None else cfg.prefetch,
            inflight=inflight if inflight is not None else cfg.inflight,
            partition_of=(
                self.plan.vertex_owner if cfg.balance_partitions else None
            ),
            balance_partitions=cfg.balance_partitions,
            feature_source=feature_source,
            checkpoint_dir=cfg.checkpoint_dir,
            checkpoint_every=cfg.checkpoint_every,
            ticket_timeout=cfg.ticket_timeout,
            worker_respawns=cfg.worker_respawns,
        )

    def train(
        self,
        model,
        train_ids: np.ndarray,
        *,
        epochs: int = 1,
        opt=None,
        log_every: int = 10,
        batch_size: int | None = None,
        prefetch: int | None = None,
    ):
        """Build a trainer, run ``epochs``, return the (trained) trainer."""
        tr = self.trainer(
            model,
            train_ids,
            opt=opt,
            batch_size=batch_size,
            prefetch=prefetch,
        )
        tr.train(epochs=epochs, log_every=log_every)
        return tr

    def dp_trainer(
        self,
        model,
        train_ids: np.ndarray,
        *,
        mesh=None,
        opt=None,
        batch_size: int | None = None,
        prefetch: int | None = None,
        reference: bool = False,
    ):
        """A ``DataParallelGNNTrainer``: the train step sharded over the
        mesh's data axis (``launch.make_local_mesh`` by default), params
        replicated, one sampling client per shard.  ``reference=True``
        additionally runs an unsharded single-device step on the same
        batches and logs its losses for equivalence checks."""
        from repro.train.data_parallel import (  # lazy: avoids import cycle
            DataParallelGNNTrainer,
        )

        cfg = self.config
        return DataParallelGNNTrainer(
            model,
            self.backend,
            self.graph,
            train_ids,
            mesh=mesh,
            spec=cfg.sampling_spec(),
            batch_size=batch_size if batch_size is not None else cfg.batch_size,
            opt=opt,
            seed=cfg.seed,
            prefetch=prefetch if prefetch is not None else cfg.prefetch,
            inflight=cfg.inflight,
            vertex_quantum=cfg.vertex_quantum,
            edge_quantum=cfg.edge_quantum,
            ticket_timeout=cfg.ticket_timeout,
            reference=reference,
        )

    # -- layerwise inference -------------------------------------------
    def infer_layerwise(
        self,
        layer_fns: list,
        workdir: str,
        *,
        feats: np.ndarray | None = None,
        fanouts=None,
        out_dims: list[int] | None = None,
        reorder: str | None = None,
        cache_policy: str | None = None,
        storage_tiers: tuple | None = None,
        tier_capacities: tuple | None = None,
        chunk_rows: int | None = None,
        dynamic_frac: float | None = None,
        batch_size: int | None = None,
        mode: str | None = None,
        jit: bool | None = None,
        use_kernel: bool | None = None,
        kernel_autotune: bool | None = None,
        kernel_cache_dir: str | None = None,
        edge_buckets: tuple | None = None,
    ):
        """Run the redundancy-free layerwise engine over the whole graph.

        ``mode``/``jit``/``use_kernel``/``edge_buckets`` control the
        device-resident bucketed execution path (see ``GLISPConfig``'s
        ``infer_*`` fields for the defaults); ``kernel_autotune``/
        ``kernel_cache_dir`` sweep Pallas block sizes per shape bucket
        before its first compile (``repro.kernels.autotune``).

        Repeat calls with the same resolved parameters (and the *same*
        ``layer_fns``/``feats`` objects) reuse one engine, so jitted
        (layer, bucket) slices carry over and nothing recompiles — the
        property ``repro.analysis.recompile_guard`` asserts."""
        from repro.core.inference.engine import LayerwiseInferenceEngine

        if not isinstance(self.backend, GatherApplyBackend):
            raise ValueError(
                "layerwise inference needs the 'gather_apply' sampler backend "
                f"(vertex-cut hosting sets drive owner assignment); this "
                f"system uses {self.config.sampler!r}"
            )
        cfg = self.config
        if fanouts is None and len(cfg.fanouts) >= len(layer_fns):
            # follow the config like every other facade method; a config
            # with fewer fanouts than layers falls back to the engine default
            fanouts = cfg.fanouts[: len(layer_fns)]
        feats_arr = self.graph.vertex_feats if feats is None else feats
        resolved = dict(
            workdir=workdir,
            fanouts=tuple(fanouts) if fanouts is not None else None,
            reorder=reorder or cfg.reorder,
            chunk_rows=chunk_rows if chunk_rows is not None else cfg.chunk_rows,
            cache_policy=cache_policy or cfg.cache_policy,
            storage_tiers=(
                tuple(storage_tiers)
                if storage_tiers is not None
                else cfg.storage_tiers
            ),
            tier_capacities=(
                tuple(tier_capacities)
                if tier_capacities is not None
                else cfg.tier_capacities
            ),
            dynamic_frac=(
                dynamic_frac if dynamic_frac is not None else cfg.dynamic_frac
            ),
            batch_size=(
                batch_size if batch_size is not None else cfg.infer_batch_size
            ),
            direction=cfg.direction,
            out_dims=tuple(out_dims) if out_dims is not None else None,
            seed=cfg.seed,
            mode=mode if mode is not None else cfg.infer_mode,
            jit=jit if jit is not None else cfg.infer_jit,
            use_kernel=(
                use_kernel if use_kernel is not None else cfg.infer_use_kernel
            ),
            kernel_autotune=(
                kernel_autotune
                if kernel_autotune is not None
                else cfg.kernel_autotune
            ),
            kernel_cache_dir=(
                kernel_cache_dir
                if kernel_cache_dir is not None
                else cfg.kernel_cache_dir
            ),
            edge_buckets=(
                tuple(edge_buckets)
                if edge_buckets is not None
                else cfg.infer_edge_buckets
            ),
        )
        # identity (not value) for the unhashables: reusing the compiled
        # slices is only sound for the very same layer callables/features
        sig = (
            tuple(resolved.items()),
            tuple(id(fn) for fn in layer_fns),
            id(feats_arr),
        )
        if self._infer_cache is not None and self._infer_cache[0] == sig:
            return self._infer_cache[1].run()
        engine = LayerwiseInferenceEngine(
            self.graph,
            self.client,
            layer_fns,
            feats_arr,
            workdir,
            fanouts=list(fanouts) if fanouts is not None else None,
            reorder_alg=REORDERS.get(resolved["reorder"]),
            chunk_rows=resolved["chunk_rows"],
            policy=CACHE_POLICIES.get(resolved["cache_policy"]),
            storage_tiers=resolved["storage_tiers"],
            tier_capacities=resolved["tier_capacities"],
            dynamic_frac=resolved["dynamic_frac"],
            batch_size=resolved["batch_size"],
            direction=resolved["direction"],
            out_dims=out_dims,
            seed=resolved["seed"],
            mode=resolved["mode"],
            use_jit=resolved["jit"],
            use_kernel=resolved["use_kernel"],
            kernel_autotune=resolved["kernel_autotune"],
            kernel_cache_dir=resolved["kernel_cache_dir"],
            edge_buckets=resolved["edge_buckets"],
            ticket_timeout=cfg.ticket_timeout,
            retry_policy=cfg.retry_policy,
            faults=cfg.fault_plan,
        )
        # pin layer_fns/feats so the id()s in the signature stay valid
        self._infer_cache = (sig, engine, (list(layer_fns), feats_arr))
        return engine.run()

    @property
    def infer_engine(self):
        """The engine behind the last ``infer_layerwise`` call (None before
        the first); exposes ``jit_trace_count()``/``shape_count()`` for
        ``repro.analysis.recompile_guard``."""
        return self._infer_cache[1] if self._infer_cache is not None else None

    # -- online serving ------------------------------------------------
    def server(
        self,
        *,
        queue_depth: int | None = None,
        max_batch_delay_ms: float | None = None,
        deadline_ms: float | None | str = "config",
    ):
        """An online :class:`repro.serve.GNNServer` over the last
        ``infer_layerwise`` run (call that first — serving recomputes only
        the final layer, reading the layer-(K-1) store through a demand
        cache).  Knobs default to the config's ``serve_*`` fields;
        ``deadline_ms=None`` explicitly disables the request deadline."""
        from repro.serve.server import GNNServer  # lazy: avoids import cycle

        cfg = self.config
        return GNNServer(
            self,
            queue_depth=(
                queue_depth if queue_depth is not None else cfg.serve_queue_depth
            ),
            max_batch_delay_ms=(
                max_batch_delay_ms
                if max_batch_delay_ms is not None
                else cfg.serve_max_batch_delay_ms
            ),
            deadline_ms=(
                cfg.serve_deadline_ms if deadline_ms == "config" else deadline_ms
            ),
        )
