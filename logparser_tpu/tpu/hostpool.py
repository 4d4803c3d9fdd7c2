"""Shared host-side worker pool for Arrow column assembly.

One parallelism knob for the whole delivery path: ``TpuBatchParser``
owns an :class:`AssemblyPool` whose worker count both (a) fans the
per-column Arrow assembly (`arrow_bridge.batch_to_arrow`) across Python
threads and (b) feeds the native memcpy fan-outs (`gather_spans`,
`build_views`, `views_interleave`) their thread budget, so the two
layers never oversubscribe each other: pooled per-column tasks run their
native calls single-threaded, unpooled batched calls get the full
budget.

Threads, not processes: every heavy step (native memcpy fan-out via
ctypes, numpy reductions, pyarrow buffer construction) releases the GIL,
and the assembled Arrow buffers must reference the batch's host memory
zero-copy — a process pool would force a serialize/copy per column.
"""
from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, List, Optional, Sequence


# Below this many rows the per-column fan-out costs more in task
# dispatch + GIL churn than it overlaps (measured on a 2-core host,
# copy mode: 0.5x at 8k rows, 1.39x at 32k, 2.27x at 64k): smaller
# batches take the serial/batched path.
MIN_POOLED_ROWS = 32768

# View-mode column assembly is mostly small numpy/pyarrow work that
# HOLDS the GIL (the byte-heavy stages are already threaded inside the
# native calls), so fanning it out needs enough workers to hide the
# Python overhead: 2-worker pooling measured 0.86x at 64k rows.  Copy
# mode has no such floor — its per-column work is one big GIL-released
# native gather.
VIEW_POOL_MIN_WORKERS = 4


def default_workers() -> int:
    """The delivery path's default parallelism (the native module's
    memcpy fan-out default: min(8, cpu_count))."""
    from ..native import _default_threads

    return _default_threads()


class AssemblyPool:
    """Lazily-started shared thread pool with a fixed worker count.

    ``workers == 1`` never starts threads — every ``run_all`` executes
    serially in the caller, so a 1-worker pool is bit-for-bit the
    pre-pool code path (the thread-count parity suite depends on it).
    """

    def __init__(self, workers: Optional[int] = None):
        self.workers = max(1, int(workers if workers else default_workers()))
        self._executor: Optional[ThreadPoolExecutor] = None
        self._closed = False
        self._lock = threading.Lock()

    def _get_executor(self) -> Optional[ThreadPoolExecutor]:
        if self._executor is None:
            with self._lock:
                if self._closed:
                    return None  # terminal: never respawn after close()
                if self._executor is None:
                    self._executor = ThreadPoolExecutor(
                        max_workers=self.workers,
                        thread_name_prefix="lp-assembly",
                    )
        return self._executor

    def run_all(self, tasks: Sequence[Callable[[], Any]]) -> List[Any]:
        """Run independent thunks, returning results in order.  Serial
        when the pool is 1-wide, closed, or there is nothing to
        overlap; the first raised exception propagates either way.

        The pooled path feeds the metrics registry (batch granularity —
        once per run plus one cheap gauge/counter update per TASK, and
        tasks are per-column, not per-line): queue depth and in-flight
        gauges, busy/wall second counters (utilization =
        busy / (wall * workers)), and a per-task (per-column assembly)
        time histogram.  The 1-wide serial path stays untouched — it is
        the bit-for-bit pre-pool baseline the parity suite pins."""
        if self.workers == 1 or len(tasks) <= 1:
            return [t() for t in tasks]
        ex = self._get_executor()
        if ex is None:
            return [t() for t in tasks]

        import time

        from ..observability import metrics

        reg = metrics()
        reg.gauge_set("hostpool_workers", self.workers)
        reg.increment("hostpool_runs_total")
        reg.increment("hostpool_tasks_total", len(tasks))
        reg.gauge_add("hostpool_queue_depth", len(tasks))

        def timed(t: Callable[[], Any]) -> Any:
            # Submitted -> running: the task leaves the queue.
            reg.gauge_add("hostpool_queue_depth", -1)
            reg.gauge_add("hostpool_active_workers", 1)
            t0 = time.perf_counter()
            try:
                return t()
            finally:
                dt = time.perf_counter() - t0
                reg.gauge_add("hostpool_active_workers", -1)
                reg.increment("hostpool_busy_seconds_total", dt)
                reg.observe("hostpool_task_seconds", dt)

        t_run = time.perf_counter()
        try:
            return list(ex.map(timed, tasks))
        finally:
            reg.increment(
                "hostpool_wall_seconds_total", time.perf_counter() - t_run
            )

    def submit(self, fn: Callable[[], Any]):
        """Submit ONE thunk for background execution; returns a Future,
        or None when the pool is serial (1-wide) or closed — callers
        then run the thunk inline.  Used by the batch runtime's rescue
        path to overlap the host oracle parse with the CSR/column
        materialization; run_all's per-task metrics stay per-column, so
        this path only counts the run."""
        if self.workers == 1:
            return None
        ex = self._get_executor()
        if ex is None:
            return None

        from ..observability import metrics

        metrics().increment("hostpool_runs_total")
        return ex.submit(fn)

    def close(self) -> None:
        """Terminal: later run_all calls execute serially instead of
        respawning threads (a retained BatchResult may outlive its
        parser and still deliver to_arrow correctly)."""
        with self._lock:
            self._closed = True
            if self._executor is not None:
                self._executor.shutdown(wait=False)
                self._executor = None

    # Pools never pickle (parser artifacts rebuild them on load).
    def __getstate__(self):  # pragma: no cover - defensive
        return {"workers": self.workers}

    def __setstate__(self, state):  # pragma: no cover - defensive
        self.__init__(state.get("workers"))
