"""What a generator hands the harness, and what a metric reader receives."""
from __future__ import annotations

import dataclasses
from typing import Any


@dataclasses.dataclass
class Window:
    """One measured window."""

    seconds: float                 #: length of the window, host clock
    attempted: int                 #: calls or requests due in the window
    failed: int                    #: of those, failed or never answered
    end_to_end: dict[str, float]   #: end-to-end metrics of the window
    work: dict[str, Any]           #: shapes and counts of the work done
    notes: list[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class MetricContext:
    """What ``bench/metrics/<name>.py``'s ``read(ctx)`` may read."""

    reduced: Any                   #: ``bench.trace.Reduced`` of the window
    peak: dict                     #: the device's entry of ``peaks.json``
    window: Window


class Generator:
    """Interface of ``bench/generators/<name>.py``'s ``Generator``.

    ``span(name)`` is a context manager for a host span (a no-op unless
    the window is traced); ``control=True`` puts the reference one
    precision step down in the program's place.
    """

    span_names: tuple[str, ...] = ()

    def __init__(self, config: dict, traffic: dict, seed: int, *, span,
                 control: bool = False, log=None):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.span, self.control, self.log = span, control, log

    def setup(self) -> None:
        """Make the inputs and warm up every shape the window uses."""
        raise NotImplementedError

    def run_window(self, seconds: float) -> Window:
        raise NotImplementedError

    def release(self) -> None:
        """Free the program's state before the reference runs."""

    def check(self) -> dict[str, float]:
        """Readings of what the window produced against the reference."""
        raise NotImplementedError
