"""Summed SELF time of the program's own spans, per root span, in
milliseconds: a span's duration minus the part of it that its child spans
cover (``loro_tpu.utils.tracing.events()``: the spans of the traced
window, on the host's clock, read inside the program).

``params["spans"]`` names the spans summed, ``params["per"]`` the span
whose count divides the sum (one per call, per launch round or per
document).  Nothing to read — a program without the record, no ``per``
span, none of the named spans (a parent commit from before a stage had
its span: 0.0 would say the stage costs nothing), or a span that fell off
the record's ring — leaves the metric out."""


def spans_of_window() -> list | None:
    """The program's record of the traced window, or None when it has
    none or lost a part of it."""
    from loro_tpu.obs import metrics as obs
    from loro_tpu.utils import tracing

    if obs.counter("trace.spans_dropped_total").total():
        return None
    return [e for e in tracing.events() if "span_id" in e] or None


def self_ns(spans: list, names: set) -> int:
    """Summed self time of the spans called ``names``."""
    covered = {}  # span id -> its children's intervals
    for e in spans:
        covered.setdefault(e["parent_id"], []).append((e["start_ns"], e["end_ns"]))
    total = 0
    for e in spans:
        if e["name"] not in names:
            continue
        total += e["end_ns"] - e["start_ns"]
        at = e["start_ns"]  # children of one thread: clip, never count twice
        for s, t in sorted(covered.get(e["span_id"], ())):
            s, t = max(s, at), min(t, e["end_ns"])
            if t > s:
                total -= t - s
                at = t
    return total


def read(params: dict, run) -> float | None:
    spans = spans_of_window()
    if not spans:
        return None
    names = set(params["spans"])
    per = sum(1 for e in spans if e["name"] == params["per"])
    if not per or not any(e["name"] in names for e in spans):
        return None
    return self_ns(spans, names) / per / 1e6
