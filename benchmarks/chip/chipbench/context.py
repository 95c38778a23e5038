"""What a per-layer reader is handed: the run's records, spans and trace,
and the reference's count of the work each batch required."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np


@dataclasses.dataclass
class Context:
    config: dict
    schedule: Any                 # traffic.Schedule
    record: Any                   # drive.Record
    spans: list                   # the program tracer's finished roots
    trace: Any                    # trace.Trace, or None untraced
    peaks: dict                   # the device's row of peaks.json
    flops_peak: float             # the peak the kernel's arithmetic runs at
    batch_work: Callable          # request indices -> (bytes, flops)
    rng: np.random.Generator      # draws the batches a reader samples
    chips: int                    # devices the cell's kernel state is on
    n_sampled_batches: int = 64

    def sample_batches(self, n: int) -> np.ndarray:
        k = min(n, self.n_sampled_batches)
        return np.sort(self.rng.choice(n, size=k, replace=False))
