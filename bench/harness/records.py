"""What one run leaves for the metric readers (``bench/metrics/*.py``)."""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from .driver import Round, Sent
from .tracefile import TraceSummary


def percentile(values, q: float) -> Optional[float]:
    """numpy's linear-interpolation percentile; None for no values."""
    values = list(values)
    return float(np.percentile(values, q)) if values else None


@dataclasses.dataclass
class Run:
    m: Dict                      # the configuration's sizes (model.dims)
    peak: Dict[str, float]       # the chip's peaks (flops.peaks)
    sent: List[Sent]
    rounds: List[Round]
    t_open: float
    t_close: float
    t_end: float
    setup_s: float
    built_in_window: int
    trace: Optional[TraceSummary] = None
    traced: Optional[tuple] = None       # host times of the traced part

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def in_window(self, t: float) -> bool:
        return self.t_open <= t <= self.t_close

    @property
    def host_close(self) -> float:
        """End of the part of the window that host-clock layer metrics read:
        the trace's start where there is one (tracing slows the host)."""
        return self.traced[0] if self.traced is not None else self.t_close

    def window_requests(self, until: Optional[float] = None) -> List[Sent]:
        """Requests due inside the window (or before ``until``)."""
        end = self.t_close if until is None else until
        return [s for s in self.sent if self.t_open <= s.due < end]

    def ttft_s(self, s: Sent) -> float:
        """Due time to first token; a request with none yet counts at the
        time the loop stopped."""
        first = s.token_times[0] if s.token_times else self.t_end
        return first - s.due

    def window_rounds(self, until: Optional[float] = None) -> List[Round]:
        end = self.t_close if until is None else until
        return [r for r in self.rounds if self.t_open <= r.t0 < end]

    def traced_rounds(self) -> List[Round]:
        if self.traced is None:
            return []
        a, b = self.traced
        return [r for r in self.rounds if r.t0 >= a and r.t1 <= b]

    def program_seconds(self, prefix: str) -> tuple:
        """(device seconds, runs) of the traced programs named ``prefix*``."""
        if self.trace is None:
            return 0.0, 0
        sec = sum(v[0] for k, v in self.trace.programs.items()
                  if k.startswith(prefix))
        runs = sum(v[1] for k, v in self.trace.programs.items()
                   if k.startswith(prefix))
        return sec, runs
