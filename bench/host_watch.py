"""What the harness's own process did in the window: the collector's runs,
and the calls that stood still.

With one call in flight, a stall of the host holds the chip idle and lands
on one call's latency and on the window's rate. The readings here tell two
causes apart, on this process alone:

* the process's own work (the collector, Python code) shows in
  ``time.process_time``;
* the process descheduled or blocked shows as wall time with flat CPU time,
  with involuntary (preempted) or voluntary (blocked, sleeping) context
  switches, and major page faults, from ``getrusage(RUSAGE_SELF)``.

Each call's clocks cost two ``getrusage`` and two ``process_time`` calls,
some 4 us on a host core.
"""

from __future__ import annotations

import gc
import resource
import statistics
import time

#: A call is named when it takes longer than this many window medians.
SLOW_FACTOR = 10.0


def clocks() -> tuple:
    """(CPU seconds, involuntary switches, voluntary switches, major faults)
    of this process so far."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return time.process_time(), ru.ru_nivcsw, ru.ru_nvcsw, ru.ru_majflt


class CallClocks:
    """Each call's latency and the deltas of ``clocks()`` across it, kept in
    flat lists of numbers, which the collector does not track."""

    def __init__(self):
        self.lat, self.cpu, self.nivcsw, self.nvcsw, self.majflt = [], [], [], [], []

    def record(self, lat: float, before: tuple) -> None:
        after = clocks()
        self.lat.append(lat)
        self.cpu.append(after[0] - before[0])
        self.nivcsw.append(after[1] - before[1])
        self.nvcsw.append(after[2] - before[2])
        self.majflt.append(after[3] - before[3])

    def slow(self, first_index: int = 1) -> list:
        """One line for each call over ``SLOW_FACTOR`` medians, calls
        numbered from ``first_index``."""
        if not self.lat:
            return []
        med = statistics.median(self.lat)
        slow = [j for j, x in enumerate(self.lat) if x > SLOW_FACTOR * med]
        lines = [f"calls over {SLOW_FACTOR:g} times the median call "
                 f"({med * 1e3:.6f} ms): {len(slow)} of {len(self.lat)}"]
        for j in slow:
            lines.append(
                f"slow call {j + first_index}: {self.lat[j] * 1e3:.6f} ms; process_time "
                f"+{self.cpu[j] * 1e3:.6f} ms, involuntary switches +{self.nivcsw[j]}, "
                f"voluntary switches +{self.nvcsw[j]}, major faults +{self.majflt[j]}")
        return lines


class Collector:
    """The collector's runs while entered: count per generation, their total
    time and the longest, through ``gc.callbacks``."""

    def __init__(self):
        self.counts = [0] * len(gc.get_count())
        self.total = 0.0
        self.longest = 0.0
        self._t0 = None

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            dt = time.perf_counter() - self._t0
            self._t0 = None
            self.counts[info["generation"]] += 1
            self.total += dt
            self.longest = max(self.longest, dt)

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)

    def report(self) -> str:
        gens = ", ".join(f"generation {g} {n}" for g, n in enumerate(self.counts))
        return (f"collector in the window: {sum(self.counts)} collections ({gens}), "
                f"{self.total * 1e3:.6f} ms in all, longest {self.longest * 1e3:.6f} ms")
