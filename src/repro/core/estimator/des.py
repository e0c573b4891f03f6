"""DES backend: causal discrete-event simulation (highest fidelity).

Runs the compiled task graph on the multi-server, bandwidth-shared
resource model (``repro.core.sim.engine``): DMA engines are concurrent
servers, collectives sharing an ICI channel split its bandwidth, and every
dependency blocks causally.  The report keeps the full ``SimResult`` so
Gantt/trace exports still work.
"""
from __future__ import annotations

import os
import tempfile
import time
from typing import List

import numpy as np

from repro.core.estimator import (EstimateReport, EstimatorBackend,
                                  layer_reports, register_backend)
from repro.core.taskgraph.compiler import CompiledGraph
from repro.core.sim.engine import simulate_static


def _simulate_variant(common, item) -> EstimateReport:
    """Worker-pool job for :meth:`DesBackend.estimate_many`: one what-if
    variant = (row of the shared duration matrix, its system/resources).

    The structural graph is broadcast once per map; the duration matrix
    arrives as a shared-memory memmap token (or inline ndarray fallback)
    attached once per worker and cached in ``WORKER_STATE`` for the rest
    of the map.  The variant's ``CompiledGraph`` is reassembled around the
    shared task list, so the worker's dependency-CSR cache (rebuilt on
    the first row) is reused for every subsequent row it simulates.
    """
    from repro.core.estimator import get_backend
    from repro.core.parallel import WORKER_STATE, WORKER_STORE

    key, mat = common
    graph = WORKER_STORE[key]
    i, system, resources = item
    if isinstance(mat, tuple):                  # ("mmap", path, shape)
        _, path, shape = mat
        arr = WORKER_STATE.get(path)            # keyed by path: a serial
        if arr is None:                         # fallback in the parent
            arr = np.memmap(path, dtype=np.float64, mode="r", shape=shape)
            WORKER_STATE[path] = arr            # can't see a stale matrix
        mat = arr
    work, ridx, fidx, _ = graph.anno_arrays()
    variant = CompiledGraph(
        tasks=graph.tasks, ops=graph.ops, system=system, plan=graph.plan,
        resources=resources,
        _anno_arrays=(work, ridx, fidx, np.asarray(mat[i])),
        _shared=graph._shared)
    rep = get_backend("des").estimate(variant)
    rep.sim_result = None
    return rep


@register_backend
class DesBackend(EstimatorBackend):
    name = "des"
    fidelity = 2

    def estimate_many(self, graphs: List[CompiledGraph],
                      workers: int = 1) -> List[EstimateReport]:
        """Parallel what-if fan-out over the persistent worker pool.

        Re-annotated variants of one structure share their task list, so
        only one structural graph is broadcast; the per-variant duration
        vectors are stacked into one matrix placed in shared memory (a
        ``/dev/shm`` memmap when available) instead of being pickled into
        every worker.  Falls back to the generic path for unrelated
        graphs and to inline shipping if the memmap cannot be created.
        """
        graphs = list(graphs)
        if workers <= 1 or len(graphs) <= 1:
            return [self.estimate(g) for g in graphs]
        first = graphs[0]
        if any(g.tasks is not first.tasks for g in graphs):
            return super().estimate_many(graphs, workers)
        from repro.core.parallel import ensure_shared, parallel_map

        key = first.pool_key()
        if not ensure_shared(workers, key, first):
            return super().estimate_many(graphs, workers)
        mat = np.ascontiguousarray(
            [np.asarray(g.durations, dtype=np.float64) for g in graphs])
        items = [(i, g.system, g.resources) for i, g in enumerate(graphs)]
        payload = mat
        path = None
        try:
            try:
                shm = "/dev/shm" if os.path.isdir("/dev/shm") else None
                fd, path = tempfile.mkstemp(prefix="repro_durs_", dir=shm)
                with os.fdopen(fd, "wb") as f:
                    f.write(mat.tobytes())
                payload = ("mmap", path, mat.shape)
            except OSError:
                path = None                   # ship the matrix inline
            return parallel_map(_simulate_variant, items, workers,
                                common=(key, payload))
        finally:
            if path is not None:
                try:
                    os.unlink(path)           # workers keep their mapping
                except OSError:
                    pass
                # a serial fallback in *this* process may have attached
                # the memmap; drop it so the unlinked file's pages are
                # released (workers clear theirs on the next broadcast)
                from repro.core.parallel import WORKER_STATE
                WORKER_STATE.pop(path, None)

    def estimate(self, graph: CompiledGraph,
                 build_seconds: float = 0.0) -> EstimateReport:
        t0 = time.perf_counter()
        # Array-backed fast path: compiled graphs are static (no callbacks,
        # no injection), so the dependency CSR is precomputed once per
        # structure (shared across re-annotated what-if variants) and the
        # event loop runs over flat duration arrays with records
        # materialized lazily (parity with the seed engine:
        # tests/test_engine_parity).
        result = simulate_static(graph.tasks, graph.resources,
                                 graph.durations, cache=graph.sim_cache())

        def util(prefix: str) -> float:
            if result.makespan <= 0:
                return 0.0
            busy = 0.0
            capacity = 0
            for name, b in result.resource_busy.items():
                if not name.startswith(prefix):
                    continue
                busy += b
                spec = graph.resources.get(name)
                capacity += spec.servers if spec is not None else 1
            return busy / (max(1, capacity) * result.makespan)

        t_c = sum(b for k, b in result.resource_busy.items()
                  if k in ("nce", "vpu"))
        t_m = result.resource_busy.get("dma", 0.0)
        t_i = sum(b for k, b in result.resource_busy.items()
                  if k.startswith("ici"))
        return EstimateReport(
            system=graph.system.name, backend=self.name,
            step_time=result.makespan,
            t_compute=t_c, t_memory=t_m, t_collective=t_i,
            nce_util=util("nce"), dma_util=util("dma"), ici_util=util("ici"),
            layers=layer_reports(graph, result.layer_durations()),
            build_seconds=build_seconds,
            estimate_seconds=time.perf_counter() - t0,
            n_tasks=len(graph.tasks),
            sim_result=result)
