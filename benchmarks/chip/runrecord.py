"""What one run leaves for the metric readers: every request of the window
with its due, submit and completion times, the program's spans of the
window, and (with ``--trace 1``) the trace digest placed on the same clock.

All times here are ``time.monotonic()`` seconds, the clock the program's
spans and response stamps use.  A reader returns ``None`` when the run left
it nothing to read; the harness then leaves that metric out.
"""

from __future__ import annotations

import statistics
from dataclasses import dataclass, field

import tracefile


@dataclass
class Request:
    """One request of the window."""

    spec: dict
    due: float                 # when it was due (open loop) or submitted
    submitted: float = 0.0
    done: float | None = None  # response stamp; None if it never came
    response: object = None    # SynthesisResponse | SheddedResponse | None
    points: int = 0            # lattice points the request covers
    recal: tuple | None = None     # (tech field, factor) it was asked under
    ticket: object = None      # the frontend's handle on it

    @property
    def served(self) -> bool:
        return self.response is not None and getattr(
            self.response, "served_from", None) is not None

    @property
    def failed(self) -> bool:
        return not self.served


@dataclass
class Run:
    t0: float                  # window open
    t_end: float               # window close
    setup_s: float
    gave_up: float             # end of the wait for late answers
    requests: list[Request] = field(default_factory=list)
    spans: list = field(default_factory=list)   # repro.obs Span objects
    trace: dict | None = None  # tracefile digest
    trace_offset_ns: float = 0.0   # trace ns = monotonic s * 1e9 + offset

    # -- the window's requests ----------------------------------------------

    def due_in_window(self) -> list[Request]:
        return [r for r in self.requests if self.t0 <= r.due < self.t_end]

    def latencies(self) -> list[float]:
        """Seconds from due time to response, for every request due in the
        window; a request that failed or never came counts with the whole
        wait it was given (it missed every latency limit)."""
        return [(r.done if r.served else self.gave_up) - r.due
                for r in self.due_in_window()]

    def quantile(self, q: float) -> float | None:
        lat = sorted(self.latencies())
        if not lat:
            return None
        if len(lat) == 1:
            return lat[0]
        cuts = statistics.quantiles(lat, n=100, method="inclusive")
        return cuts[int(round(q * 100)) - 1]

    def completed_in_window(self) -> list[Request]:
        return [r for r in self.requests
                if r.served and r.done is not None and r.done <= self.t_end]

    # -- the program's spans --------------------------------------------------

    def window_spans(self, prefix: str) -> list:
        return [s for s in self.spans if s.name.startswith(prefix)
                and self.t0 <= s.start_s < self.t_end
                and s.end_s is not None]

    def span_total(self, prefix: str) -> float:
        return sum(s.duration_s for s in self.window_spans(prefix))

    def lanes(self) -> int:
        """Spec lanes through the engine in the window (``engine.execute``
        spans carry their pass's lane count)."""
        return sum(int(s.tags.get("n_specs", 0))
                   for s in self.window_spans("engine.execute"))

    def per_lane(self, prefix: str) -> float | None:
        lanes = self.lanes()
        return self.span_total(prefix) / lanes if lanes else None

    def per_request(self, prefix: str) -> float | None:
        n = sum(s.name == "request" for s in self.window_spans("request"))
        return self.span_total(prefix) / n if n > 0 else None

    # -- the device trace -----------------------------------------------------

    def trace_window(self) -> tuple[float, float]:
        return (self.t0 * 1e9 + self.trace_offset_ns,
                self.t_end * 1e9 + self.trace_offset_ns)

    def host_spans_on_trace(self) -> list[tuple[str, float, float]]:
        off = self.trace_offset_ns
        return [(s.name, s.start_s * 1e9 + off, s.end_s * 1e9 + off)
                for s in self.spans if s.end_s is not None]

    def idle_share(self) -> float | None:
        if self.trace is None:
            return None
        return tracefile.idle_share(self.trace, *self.trace_window())

    def module_s(self, match: str) -> float | None:
        if self.trace is None:
            return None
        ns = tracefile.module_ns(self.trace, match, *self.trace_window())
        return None if ns is None else ns / 1e9
