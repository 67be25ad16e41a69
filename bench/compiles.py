"""Counts the programs JAX lowers, so a window can show it compiled none.

Every jit cache miss and every new eager shape is lowered to MLIR before it
is compiled or fetched from the persistent cache, and JAX reports each
lowering as a ``/jax/core/compile/jaxpr_to_mlir_module_duration`` event.
"""

from __future__ import annotations

import jax.monitoring

EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class LoweringCounter:
    """Counts lowerings from construction until ``close``."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, name, *_args, **_kw):
        if name == EVENT:
            self.count += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._listen)
