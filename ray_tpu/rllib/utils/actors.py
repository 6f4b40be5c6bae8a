"""In-flight task tracking over actor fleets.

Parity: `rllib/utils/actors.py:8` `TaskPool` — tracks pending
`sample.remote()` calls so async optimizers can pull completed batches as
they arrive and keep every worker busy.
"""

from __future__ import annotations

from typing import Dict, Iterator, Tuple

import ray_tpu


class TaskPool:
    def __init__(self):
        self._tasks: Dict = {}   # ObjectRef -> actor handle

    def add(self, worker, obj_ref) -> None:
        self._tasks[obj_ref] = worker

    def completed(self, blocking_wait: bool = False
                  ) -> Iterator[Tuple[object, object]]:
        """Yield (worker, ref) for finished tasks; removes them."""
        pending = list(self._tasks)
        if not pending:
            return
        ready, _ = ray_tpu.wait(
            pending, num_returns=len(pending), timeout=0)
        if not ready and blocking_wait:
            ready, _ = ray_tpu.wait(pending, num_returns=1, timeout=10.0)
        for ref in ready:
            # The consumer may retire a worker between two yields (fleet
            # eviction / preemption -> remove_worker), which takes that
            # worker's other ready refs out from under this loop.
            worker = self._tasks.pop(ref, None)
            if worker is not None:
                yield worker, ref

    def remove_worker(self, worker) -> list:
        """Drop every in-flight task of one worker (fleet removal /
        eviction: the refs die with the actor, so blocking on them
        would stall the pull loop). Returns the dropped refs."""
        refs = [ref for ref, w in self._tasks.items() if w is worker]
        for ref in refs:
            del self._tasks[ref]
        return refs

    @property
    def count(self) -> int:
        return len(self._tasks)
