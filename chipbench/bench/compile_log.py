"""XLA compiles as JAX reports them, so the harness can show that none
happens inside the measured window."""
from __future__ import annotations


class CompileLog:
    """(program, seconds) per backend compile since the last ``take()``."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seen = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, fun_name="?", **_):
        if event == self.EVENT:
            self.seen.append((fun_name, secs))

    def take(self) -> list:
        seen, self.seen = self.seen, []
        return seen
