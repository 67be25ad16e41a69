"""The system under test, composed from the program's own serving functions.

``build(cfg, ...)`` returns an ``Engine``: ``step(batch)`` takes one
scheduler batch of raw float32 queries (batch, dim) on the host and returns
(dists, ids) as host arrays, the shape ``runtime.scheduler.BatchScheduler``
drives.  The rotation into the PCA basis runs inside ``step``, as a
deployment pays it.

A configuration's ``route`` names a file ``bench/routes/<route>.py`` whose
``build(cfg, blocks, devices, seed, *, interpret)`` returns the Engine, so a
new route comes with a file of its own.  Routes use only names in the
program's ``__all__`` lists (and ``configs.dade_ivf.ServiceConfig``);
PERF.md lists them.
"""

from __future__ import annotations

import importlib.util
import os
from dataclasses import dataclass, field
from typing import Callable

ROUTES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "routes")


@dataclass
class Engine:
    step: Callable
    batch: int
    notes: dict = field(default_factory=dict)
    free: Callable | None = None  # drop the program's device state


def build(cfg: dict, blocks, devices, seed: int, *, interpret: bool) -> Engine:
    route = cfg["route"]
    spec = importlib.util.spec_from_file_location(
        "bench_route_" + route, os.path.join(ROUTES, route + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.build(cfg, blocks, devices, seed, interpret=interpret)
