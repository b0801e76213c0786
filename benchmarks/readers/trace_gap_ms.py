"""Mean device-idle gap between consecutive launches of the window's main
program (the one with most device time), in milliseconds."""
import trace_reduce


def read(params: dict, run) -> float | None:
    t = run.trace_numbers
    if not t:
        return None
    gaps = trace_reduce.launch_gaps(t["launches"])
    if not gaps:
        return None
    return 1e3 * sum(gaps) / len(gaps)
