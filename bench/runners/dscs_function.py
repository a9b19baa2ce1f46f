"""The system under test: one Table I vision function served by
``DSCSExecutor`` (f1 on the vector-engine kernel, f2 through im2col and
the systolic kernel, f3 the top-1 on the host).

A request is a batch-1 uint8 frame; its answer is the top-1 result on the
host, and its output the f2 logits the answer came from.
"""
from __future__ import annotations

import jax
import numpy as np


class Server:
    def __init__(self, cfg: dict, seed: int):
        from repro.core.executor import DSCSExecutor
        self.executor = DSCSExecutor(
            cfg["pipeline"], platform=cfg["platform"],
            image_size=cfg["image_size"], width=cfg["width"], seed=seed)
        jax.block_until_ready([v for v in jax.tree.leaves(self.executor.params)
                               if isinstance(v, jax.Array)])

    def invoke(self, frame):
        """Dispatch one invocation; returns before the device is done."""
        return self.executor(frame)

    @staticmethod
    def wait(handle) -> None:
        handle.result.block_until_ready()

    @staticmethod
    def fetch(handle) -> np.ndarray:
        """The answer (top-1), copied to the host."""
        return np.asarray(handle.result)

    @staticmethod
    def output(handle):
        """The device array the answer was taken from (f2's logits)."""
        return handle.output

    def close(self) -> None:
        self.executor = None
