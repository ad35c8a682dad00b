"""What one run of one cell carries between set-up, window, check and
metrics."""

from __future__ import annotations

import dataclasses
import functools
import importlib
import time

import numpy as np

from bench.lib import registry
from bench.lib.spans import Spans

# the traced run traces this much of its window, at most
TRACE_SECONDS = 4.0


@dataclasses.dataclass
class Ctx:
    cell: dict
    cfg: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    t_start: float                      # perf_counter at process start
    smoke: bool = False                 # CPU tests: the config's smoke sizes
    control: bool = False               # also read the control (readings)
    spans: Spans = None
    counts: dict = dataclasses.field(default_factory=dict)
    e2e: dict = dataclasses.field(default_factory=dict)
    readings: dict = dataclasses.field(default_factory=dict)
    extra: dict = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    trace_data: object = None           # bench.lib.trace.Reduced
    hooks: dict = dataclasses.field(default_factory=dict)  # tests only

    def __post_init__(self):
        self.spans = Spans(annotate=self.trace)

    @property
    def sizes(self) -> dict:
        return self.cfg["smoke"] if self.smoke else self.cfg["sizes"]

    @property
    def window_seconds(self) -> float:
        return min(self.seconds, TRACE_SECONDS) if self.trace \
            else self.seconds

    @property
    def platform(self) -> str:
        return self.devices[0].platform

    @functools.cached_property
    def mesh(self):
        """The cell's chips as a 1-D ``"model"`` mesh, over which the
        serving store and the tables are row-sharded; None on one
        chip, where every call is the one-device call."""
        if len(self.devices) == 1:
            return None
        from jax.sharding import Mesh
        return Mesh(np.array(self.devices), ("model",))

    def reference(self):
        return registry.reference(self.cfg)

    def since_start(self) -> float:
        return time.perf_counter() - self.t_start


def resolve(dotted: str):
    mod, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(mod), attr)


def build_model(cfg: dict, sizes: dict):
    """The program's model for these sizes, through its own builder."""
    prog = cfg["program"]
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in sizes.items()}
    return resolve(prog["builder"])(resolve(prog["config"])(**kw))


def global_ids(cards, idx: np.ndarray) -> np.ndarray:
    off = np.concatenate([[0], np.cumsum(np.asarray(cards, np.int64))[:-1]])
    return (idx.astype(np.int64) + off[None, :]).astype(np.int32)

