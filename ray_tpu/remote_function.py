"""`@ray_tpu.remote` functions.

Parity: `python/ray/remote_function.py` — a wrapper exporting the pickled
function to the GCS function table once, with `.remote()` and `.options()`.
"""

from __future__ import annotations

import hashlib
from typing import Optional

import cloudpickle

from ._private import worker_state


def _resource_spec(num_cpus, resources) -> dict:
    spec = {}
    spec["CPU"] = float(num_cpus) if num_cpus is not None else 1.0
    if resources:
        spec.update({k: float(v) for k, v in resources.items()})
    return spec


class RemoteFunction:
    def __init__(self, fn, num_returns=1, num_cpus=None,
                 resources=None, max_retries=3, name=None):
        self._function = fn
        self._num_returns = num_returns
        self._resources = _resource_spec(num_cpus, resources)
        self._max_retries = max_retries
        self._name = name or getattr(fn, "__name__", "fn")
        self._key: Optional[str] = None
        self._pickled: Optional[bytes] = None
        self.__doc__ = getattr(fn, "__doc__", None)

    def _ensure_exported(self, rt):
        if self._key is None:
            self._pickled = cloudpickle.dumps(self._function, protocol=5)
            h = hashlib.sha1(self._pickled).hexdigest()[:20]
            self._key = f"fn:{self._name}:{h}"
        rt.export_function(self._key, self._pickled)

    def remote(self, *args, **kwargs):
        rt = worker_state.get_runtime()
        self._ensure_exported(rt)
        refs = rt.submit_task(
            self._key, args, kwargs, num_returns=self._num_returns,
            resources=self._resources, max_retries=self._max_retries,
            name=self._name)
        if self._num_returns == 0:
            return None
        return refs[0] if self._num_returns == 1 else refs

    def options(self, num_returns=None, num_cpus=None,
                resources=None, max_retries=None, name=None):
        """Return a copy with overridden submit options (reference:
        `remote_function.py` `.options`)."""
        clone = RemoteFunction(
            self._function,
            num_returns=self._num_returns if num_returns is None else num_returns,
            max_retries=self._max_retries if max_retries is None else max_retries,
            name=name or self._name)
        clone._resources = dict(self._resources)
        if num_cpus is not None:
            clone._resources["CPU"] = float(num_cpus)
        if resources:
            clone._resources.update({k: float(v) for k, v in resources.items()})
        # Share the exported key/bytes with the original.
        clone._key = self._key
        clone._pickled = self._pickled
        return clone

    def __call__(self, *args, **kwargs):
        raise TypeError(
            f"Remote function '{self._name}' cannot be called directly; use "
            f"'{self._name}.remote()'.")
