"""What a reader may read: the run, as the benchmark recorded it."""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class Context:
    seconds: float
    setup_s: float
    window: list = field(default_factory=list)  # Records the cell judges
    completed: list = field(default_factory=list)  # replied in the window
    scrapes: list = field(default_factory=list)  # (seconds, /metrics text)
    trace: dict | None = None  # lib.trace.extract's summary
    peaks: dict | None = None  # lib.peaks row of the device
    notes: list = field(default_factory=list)  # what a reader wants said
