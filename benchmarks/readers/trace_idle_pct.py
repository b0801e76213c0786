"""Device idle share of the traced window: 1 - busy / window, in percent."""


def read(params: dict, run) -> float | None:
    t = run.trace_numbers
    if not t or t["busy_s"] is None or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
