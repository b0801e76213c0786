#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration
(``benchmarks/configs/``), its traffic mix (``benchmarks/traffic/``, which
names its driver under ``benchmarks/drivers/``) and its per-layer metrics
(``benchmarks/layer_metrics/``, each naming a reader under
``benchmarks/readers/``) are files found by those names, so a later PR adds
a cell by adding files and manifest entries.

One process owns the chip: this one.  Host-only work (typing the seeded
script into replicas, the plain reference) runs in worker processes
started BEFORE this process initialises JAX, pinned to the CPU.  The run
makes its inputs from ``--seed``, warms every shape its window uses
(set-up), measures for ``--seconds``, reads the peak memory, compares every
answer with the plain reference's, and prints ONE JSON object as the last
line of stdout.  It exits non-zero, printing no result, when there is no
TPU or fewer chips than the cell asks for (``--rehearsal`` allows the CPU
for the tests, and labels every number ``cpu_rehearsal.*``).
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time
from multiprocessing import get_context

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import gen  # noqa: E402  (host-only; loads no JAX)


class BenchError(Exception):
    """The run cannot give a result (no chip, unknown cell, bad file)."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def log(obj: dict) -> None:
    """An earlier line of the output: decides nothing."""
    print(json.dumps(obj), flush=True)


class Run:
    """What the driver and the readers see of one run."""

    def __init__(self, args, manifest: dict):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if args.workload not in cells:
            raise BenchError(f"no cell {args.workload!r} in BENCHMARK.json")
        self.manifest = manifest
        self.cell = cells[args.workload]
        cfg = next(c for c in manifest["configs"] if c["name"] == self.cell["config"])
        self.config = load_json(ROOT, cfg["file"])
        self.traffic = load_json(HERE, "traffic", self.cell["traffic"] + ".json")
        self.peaks = load_json(HERE, "peaks.json")
        self.seed, self.seconds = args.seed, args.seconds
        self.trace, self.rehearsal = bool(args.trace), args.rehearsal
        if self.rehearsal:  # the tests' tiny sizes, kept with the data
            self.config.update(self.config.get("rehearsal", {}))
            self.traffic.update(self.traffic.get("rehearsal", {}))
        self.work_dir = os.path.join(gen.CACHE_DIR, "work", self.cell["name"])
        self.trace_dir = os.path.join(self.work_dir, "trace")
        self.tracing = False
        self.pool = self.mesh = self.device = self.events = None
        self.facts, self.trace_numbers = {}, None
        self.control = False  # the drivers' compare() reads it

    # -- the profiler ------------------------------------------------------
    def window_span(self):
        """The driver's ``with`` around exactly what it times: in a traced
        run the annotation that bounds the trace's window."""
        import jax.profiler as P

        import trace_reduce

        return P.TraceAnnotation(trace_reduce.WINDOW_SPAN)

    def start_trace(self) -> None:
        """In a ``--trace 1`` run, start the profiler: a driver calls this
        where its traced window starts.  Host events are the benchmark's
        own annotations only (tracer level 1); what the device records
        cannot be thinned, and its buffer holds about 300 MB (my chip
        runs, PR 24)."""
        import jax.profiler as P

        if not self.trace or self.tracing:
            return
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = P.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        P.start_trace(self.trace_dir, profiler_options=opts)
        self.tracing = True

    def stop_trace(self) -> None:
        """Stop the profiler and reduce its trace.  ``main`` calls this
        after the window; a driver whose traced window is shorter than its
        ``window()`` calls it earlier itself."""
        import jax.profiler as P

        import trace_reduce

        if not self.tracing:
            return
        self.tracing = False
        t0 = time.perf_counter()
        P.stop_trace()
        t1 = time.perf_counter()
        self.trace_numbers = t = trace_reduce.reduce_trace(self.trace_dir)
        log({"trace_stop_s": t1 - t0, "trace_reduce_s": time.perf_counter() - t1,
             "trace_bytes": os.path.getsize(trace_reduce.find_xplane(self.trace_dir)),
             **{k: t[k] for k in ("n_device_ops", "first_device_op_s",
                                  "last_device_op_s", "window_s")}})
        shutil.rmtree(self.trace_dir, ignore_errors=True)


def layer_metrics(run: Run) -> dict:
    """Every per-layer metric of the manifest that lists this cell, read
    by its own reader; a reader that finds nothing leaves its metric out."""
    out = {}
    for m in run.manifest["per_layer"]:
        if "workloads" in m and run.cell["name"] not in m["workloads"]:
            continue
        spec = load_json(HERE, "layer_metrics", m["name"] + ".json")
        reader = importlib.import_module(f"readers.{spec['reader']}")
        value = reader.read(spec.get("params", {}), run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def start_jax(run: Run) -> None:
    """Initialise the backend, the compile cache and the device record;
    fail when the platform is not the chip the cell asks for."""
    import jax

    from checks import CompileEvents
    from loro_tpu import native
    from loro_tpu.parallel.mesh import make_mesh

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(gen.CACHE_DIR, "jax"))
    # cache every program, also those that compile in under a second:
    # a warm run must find all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    run.events = CompileEvents()
    devs = jax.devices()
    run.device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
    chips = run.cell["chips"]
    if run.rehearsal:
        if devs[0].platform != "cpu":
            raise BenchError("--rehearsal is for the CPU backend only")
    else:
        if devs[0].platform != "tpu":
            raise BenchError(f"no TPU: JAX runs on {devs[0].platform!r}")
        if devs[0].device_kind not in run.peaks["devices"]:
            raise BenchError(f"device kind {devs[0].device_kind!r} is not in "
                             "benchmarks/peaks.json")
        if len(devs) < chips:
            raise BenchError(f"the cell needs {chips} chips, JAX has {len(devs)}")
    native.require()
    run.devices = devs[:chips]
    run.mesh = make_mesh(run.devices)


def memory_peak(run: Run) -> int:
    peak = 0
    for d in run.devices:
        stats = d.memory_stats()
        if stats:
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the cell's control (the reference with one "
                    "guarantee broken) in the program's place: correct must "
                    "come out false")
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU backend, for the tests; never a device number")
    args = ap.parse_args(argv)
    run = Run(args, load_json(ROOT, "BENCHMARK.json"))
    driver = importlib.import_module(f"drivers.{run.traffic['driver']}")
    shutil.rmtree(run.work_dir, ignore_errors=True)
    os.makedirs(run.work_dir, exist_ok=True)
    run.pool = get_context("spawn").Pool(
        int(run.traffic.get("worker_processes", 3)),
        initializer=gen.pin_worker_to_cpu)
    try:
        driver.prepare(run)  # host-only work, BEFORE this process touches JAX
        start_jax(run)
        driver.setup(run)  # build, load, warm every shape of the window
        setup_s = time.perf_counter() - T_START
        mark = run.events.mark()
        try:
            out = driver.window(run)  # starts the trace where its window starts
        finally:
            run.stop_trace()
        run.facts.update(out.get("facts", {}))
        # a driver whose window() goes on past its window says itself
        # what compiled inside it
        compiled = out.get("compiled", run.events.names_since(mark))
        run.facts["compiles_in_window"] = len(compiled)
        peak = memory_peak(run)
        compared = driver.compare(run)  # after the peak was read
        if args.control:  # the same run, with the control in the program's place
            log({"sound_run_compared": compared})
            run.control = True
            compared = driver.compare(run)
        moved = driver.counters_moved(run)
        compared["counters_moved"] = [sum(1 for _ in moved), 0]
        if run.traffic.get("fail_on_compile_in_window"):
            compared["compiles_in_window"] = [run.facts["compiles_in_window"], 0]
        log({"setup_s": setup_s, "counters_moved": moved,
             "compiled_in_window": compiled,
             "compile_cache": run.events.since(), **out.get("log", {})})
    finally:
        try:
            driver.close(run)
        finally:
            run.pool.terminate()  # host-only workers: they never load the chip
            run.pool.join()
            shutil.rmtree(run.work_dir, ignore_errors=True)
    correct = all(v <= lim for v, lim in compared.values())
    device = dict(run.device, memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    if run.trace:
        t = run.trace_numbers
        if t["busy_s"] is not None:
            device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        result["metrics"] = layer_metrics(run)
        result["breakdown"] = {"device_ops": t["device_ops"],
                               "idle_gaps": t["idle_gaps"]}
    else:
        values = dict(out["metrics"], setup_s=setup_s)
        # the manifest says which end-to-end metrics this cell reports; a
        # driver that lacks one of them fails here, loudly
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in run.manifest["end_to_end"]
            if run.cell["name"] in m.get("workloads", [run.cell["name"]])}
    if run.rehearsal:  # never a device metric's name on a CPU number
        result["metrics"] = {f"cpu_rehearsal.{k}": v
                             for k, v in result["metrics"].items()}
        result["rehearsal"] = True
    result["device"] = device
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    for k, (v, lim) in compared.items():
        print(f"compared {k}: {v} (limit {lim})", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0 if correct else 1


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        sys.exit(2)
