"""Bytes the semantics NEED, counted from a cell's own sizes — never from
the compiled program — so that a roofline share reads the same whatever
kernel implements the step, and still bounds a claim after a later PR
swaps a kernel."""
from __future__ import annotations


def import_bytes(elements: int, bytes_in_per_element: float,
                 bytes_out_per_element: float) -> float:
    """A bulk import has to read every element's columns once and write
    every element's answer once.  ``elements`` are the documents' own
    (unpadded) sequence elements, summed over the documents merged."""
    return float(elements) * (bytes_in_per_element + bytes_out_per_element)


def roofline_pct(needed_bytes: float, bytes_per_s: float, busy_s: float) -> float:
    """The least time the chip could take for ``needed_bytes`` over the
    time it was busy, in percent."""
    return 100.0 * (needed_bytes / bytes_per_s) / busy_s


def load_peak(kind: str, key: str, peaks: dict) -> float:
    """The published peak of device ``kind``; unknown is an error."""
    if kind not in peaks.get("devices", {}):
        raise KeyError(f"device kind {kind!r} is not in benchmarks/peaks.json")
    return float(peaks["devices"][kind][key])
