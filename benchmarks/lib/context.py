"""What a reader and a check may read: the run, as the benchmark
recorded it."""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class Context:
    seconds: float
    setup_s: float
    config: dict = field(default_factory=dict)  # configs/<config>.json
    out: str = ""  # the run's output directory
    window: list = field(default_factory=list)  # Records the cell judges
    scrapes: list = field(default_factory=list)  # (seconds, /metrics text)
    trace: dict | None = None  # lib.trace.extract's summary
    peaks: dict | None = None  # lib.peaks row of the device
    notes: list = field(default_factory=list)  # what a reader wants said


@dataclass
class CheckRun:
    """What a file under checks/ is given, at both of its moments:
    ``before_window`` (the warm-up is done, the child serves at ``url``)
    and ``after_exit`` (the child has exited and the chips are free,
    ``url`` is None, ``window`` holds the judged requests' Records and
    ``requests`` the open loop's schedule). A number a check compares
    goes into ``compared`` as name -> [value, limit]."""
    cell: object  # lib.cell.Cell
    config: dict
    seed: int
    out: str
    url: str | None = None
    window: list = field(default_factory=list)
    requests: list = field(default_factory=list)
    compared: dict = field(default_factory=dict)
    notes: list = field(default_factory=list)  # what a check wants said
