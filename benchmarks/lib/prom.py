"""Reading a Prometheus text page (copied from chip_smoke.py's
``_samples``/``_metric``, PR 22, which proved them on the chip)."""

from __future__ import annotations


def samples(text: str, name: str):
    """(label text, value) of every sample of one series."""
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        head, _, value = line.rpartition(" ")
        series, _, labels = head.partition("{")
        if series == name:
            yield labels, float(value)


def value(text: str, name: str, labels: dict | None = None) -> float | None:
    """Sum of the samples whose labels include ``labels``; None when the
    series is absent, so a reader can tell 0 from nothing."""
    total, found = 0.0, False
    for have, v in samples(text, name):
        if all(f'{k}="{w}"' in have for k, w in (labels or {}).items()):
            total += v
            found = True
    return total if found else None


def by_label(text: str, name: str, label: str) -> dict[str, float]:
    return {have.partition(f'{label}="')[2].partition('"')[0]: v
            for have, v in samples(text, name)}
