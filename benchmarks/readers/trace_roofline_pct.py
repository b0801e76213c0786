"""Needed bytes / peak HBM bytes per second / device busy time, in percent.

``params["bytes"]`` names the function of ``bytes_model`` and
``params["facts"]`` maps its arguments to facts of the window (driver's
counts) or to constants of the cell's configuration or traffic file."""
import bytes_model


def read(params: dict, run) -> float | None:
    t = run.trace_numbers
    if not t or not t["busy_s"]:
        return None
    facts = {**run.config, **run.traffic, **run.facts}
    args = {k: facts[v] for k, v in params["facts"].items()}
    needed = getattr(bytes_model, params["bytes"])(**args)
    if needed <= 0:
        return None
    peak = bytes_model.load_peak(run.device["kind"], "hbm_bytes_per_s", run.peaks)
    return bytes_model.roofline_pct(needed, peak, t["busy_s"])
