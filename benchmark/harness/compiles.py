"""Counts what JAX lowers and compiles, so that a run can show that
nothing did inside its measured window."""

from __future__ import annotations

import threading

_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """One listener for the life of the process (JAX has no way to
    remove one); ``mark()`` returns the counts since the last mark."""

    _instance = None
    _guard = threading.Lock()

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._lowerings = 0
        self._backend = 0
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_event)

    @classmethod
    def get(cls) -> "CompileCounter":
        with cls._guard:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        del duration, kwargs
        with self._lock:
            if event == _LOWER:
                self._lowerings += 1
            elif event == _BACKEND:
                self._backend += 1

    def mark(self):
        """(programs lowered, programs compiled by the backend) since
        the previous mark. A lowering whose executable comes from the
        persistent cache is still work inside the window, so both count."""
        with self._lock:
            out = (self._lowerings, self._backend)
            self._lowerings = self._backend = 0
        return out
