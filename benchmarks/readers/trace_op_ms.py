"""Device time of the traced operations whose name holds
``params["match"]`` (a kernel's own name, as the trace gives it), per
launch of the window's main program (the one with most device time), in
milliseconds: one kernel's time a call.  The operations are those of the
reduction's ``device_ops`` (the ten with most device time); nothing to
read — no trace, no launch, no such operation among them — leaves the
metric out."""
import trace_reduce


def read(params: dict, run) -> float | None:
    t = run.trace_numbers
    if not t or not t["launches"]:
        return None
    mine = [s for name, s in t["device_ops"] if params["match"] in name]
    if not mine:
        return None
    # one gap fewer than launches of the main program
    launches = len(trace_reduce.launch_gaps(t["launches"])) + 1
    return 1e3 * sum(mine) / launches
