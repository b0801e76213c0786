"""CPU time over wall time of the program's own spans, in percent: the
thread's ``thread_time_ns`` over the span against its duration, summed
over ``params["spans"]`` (``loro_tpu.utils.tracing.events()``).  Under 100
the threads wait: for the interpreter's lock, for a core, for the host's
other tenants.  Nothing to read leaves the metric out."""
from readers.span_self_ms import spans_of_window


def read(params: dict, run) -> float | None:
    spans = [e for e in spans_of_window() or ()
             if e["name"] in params["spans"] and e["cpu_ns"] is not None]
    wall = sum(e["end_ns"] - e["start_ns"] for e in spans)
    if not wall:
        return None
    return 100.0 * sum(e["cpu_ns"] for e in spans) / wall
