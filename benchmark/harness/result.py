"""What a run hands to the metric readers, and small statistics."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np


@dataclass
class Run:
    """One finished run, as the readers in ``metrics/`` see it."""
    cell: object                 # spec.Cell
    peaks: dict
    window: object               # serve.WindowResult
    setup_s: float
    trace: Optional[dict] = None  # xtrace.reduce(...) + "window_s"

    @property
    def config(self):
        return self.cell.config

    # -- serving helpers the readers share ---------------------------------
    def measured(self):
        return [t for t in self.window.tracks if t.measured]

    def steps_in(self, t0=None, t1=None):
        w = self.window
        t0 = w.w0 if t0 is None else t0
        t1 = w.w1 if t1 is None else t1
        return [s for s in w.steps if t0 <= s.t1 < t1]

    def traced_steps(self):
        tr = self.window.trace
        return [s for s in self.window.steps
                if tr["t0"] <= s.t0 and s.t1 <= tr["t1"]]

    @property
    def window_s(self):
        return self.window.w1 - self.window.w0


def percentile(values, q):
    """None where there is nothing to take it of."""
    values = list(values)
    if not values:
        return None
    return float(np.percentile(np.asarray(values, float), q))
