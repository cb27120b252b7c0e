"""One run of one cell: find the cell's files by the names in
BENCHMARK.json, hand them to its runner, read the per-layer metrics of a
traced run, print the result line.

Everything that belongs to one configuration, one traffic mix, one
per-layer metric or one kind of job is a file of its own (README.md);
nothing here names any of them.
"""

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SCRATCH = os.path.join(ROOT, ".chipbench")      # traces; in .gitignore


def seconds_since_process_start():
    """From the kernel's record of when this process started, so that
    the interpreter's start and every import count as set-up."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - ticks / os.sysconf("SC_CLK_TCK")


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name, base=HERE):
    """``<base>/<kind>/<name>.py`` as a module, by file: names follow
    BENCHMARK.json's rules (``-`` and ``.`` allowed), not Python's."""
    path = os.path.join(base, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench.%s.%s" % (kind, name.replace("-", "_").replace(".", "_")),
        path)
    loaded = sys.modules.get(spec.name)
    if loaded is not None and getattr(loaded, "__file__", None) == path:
        return loaded
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def find_chips(chips):
    """The devices of this run.  No accelerator, or fewer chips than the
    cell asks for, ends the process before anything is run or printed."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit("chipbench: JAX found no TPU (platform %r); nothing was "
                 "run and nothing is reported" % devices[0].platform)
    if len(devices) < chips:
        sys.exit("chipbench: the cell needs %d chips, this machine has %d"
                 % (chips, len(devices)))
    return devices[:chips]


def enable_compile_cache():
    """JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says,
    else at the program's fixed path inside the checkout; every program
    is kept, however quickly it compiled, so a second run compiles
    nothing."""
    import jax
    from mxtpu.runtime import enable_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts what compiles: XLA back-end compilations seen by
    ``jax.monitoring`` (a cache fetch is not one) and the program's own
    compile ledger's misses."""

    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.backend = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **_):
        if event == self.BACKEND:
            self.backend += 1

    def snapshot(self):
        from mxtpu.analysis.compile_ledger import get_ledger

        return self.backend, sum(get_ledger().miss_counts().values())

    @staticmethod
    def compiled_between(before, after):
        return max(after[0] - before[0], after[1] - before[1])


class Cell:
    """What a runner is handed: the cell's files, the run's arguments,
    the devices, and the window."""

    def __init__(self, name, entry, config, traffic, base, seed, seconds,
                 trace, devices):
        self.name, self.entry = name, entry
        self.config, self.traffic, self.base = config, traffic, base
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices = devices
        self.spans = []             # (name, start, end) on the host clock
        self.setup_s = None
        self.window_s = None
        self.memory_peak_bytes = None
        self.compiled_in_window = None
        self.trace_dir = os.path.join(SCRATCH, "trace-" + name)
        self._compiles = CompileCounter()

    def module(self, kind, name):
        """``<kind>/<name>.py`` beside the cell's files, else the
        benchmark's own (a test's cells bring only what they add)."""
        own = os.path.exists(os.path.join(self.base, kind, name + ".py"))
        return load_module(kind, name, self.base if own else HERE)

    @contextlib.contextmanager
    def span(self, name):
        """A host span: kept in memory, and in a traced run written into
        the profiler's trace beside the device's operations."""
        import jax

        start = time.perf_counter()
        if self.trace:
            with jax.profiler.TraceAnnotation("chipbench." + name):
                yield
        else:
            yield
        self.spans.append((name, start, time.perf_counter()))

    @contextlib.contextmanager
    def window(self):
        """The measured window.  Set-up ends where it opens; when it
        closes the trace is stopped, the compilations inside it are
        counted and the device's peak memory is read — before any
        reference runs on the chip."""
        import jax

        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=options)
        before = self._compiles.snapshot()
        self.setup_s = seconds_since_process_start()
        start = time.perf_counter()
        try:
            with self.span("window"):
                yield
        finally:
            self.window_s = time.perf_counter() - start
            if self.trace:
                jax.profiler.stop_trace()
            self.compiled_in_window = self._compiles.compiled_between(
                before, self._compiles.snapshot())
            peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in self.devices]
            self.memory_peak_bytes = max(peaks)


def per_layer_metrics(cell, bench, observed, trace):
    """Every ``metrics/*.json`` whose ``workloads`` lists this cell, read
    by the reader it names.  A reader that finds nothing returns None and
    its metric is left out of the line."""
    declared = {m["name"]: m for m in bench["per_layer"]}
    values = {}
    folder = os.path.join(cell.base, "metrics")
    for fname in sorted(os.listdir(folder)):
        if not fname.endswith(".json"):
            continue
        spec = load_json(folder, fname)
        if cell.name not in spec["workloads"] or spec["name"] not in declared:
            continue
        reader = cell.module("readers", spec["reader"])
        value = reader.read(cell=cell, spec=spec, observed=observed,
                            trace=trace)
        if value is not None:
            values[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return values


def run_cell(bench, workload, seed, seconds, trace, base=HERE,
             need_chip=True, out=sys.stdout, err=sys.stderr):
    """Run one cell once and print the result line.  ``need_chip=False``
    is for the CPU rehearsal tests only: the command line never sets it.
    Returns the result."""
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        sys.exit("chipbench: BENCHMARK.json has no workload %r" % workload)
    config = load_json(base, "configs", entry["config"] + ".json")
    traffic = load_json(base, "traffic", entry["traffic"] + ".json")

    import jax

    devices = (find_chips(entry["chips"]) if need_chip
               else jax.devices()[:entry["chips"]])
    cache_dir = enable_compile_cache() if need_chip else None
    cell = Cell(workload, entry, config, traffic, base, seed, seconds,
                bool(trace), devices)
    runner = cell.module("runners", config["runner"])
    ran = runner.run(cell)          # set-up, window, then the comparison

    checks = ran["checks"]          # [{"name", "value", "limit"}]
    checks.append({"name": "compiled_in_window",
                   "value": cell.compiled_in_window, "limit": 0})
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks)

    end_to_end = dict(ran["end_to_end"], setup_s=cell.setup_s)
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": cell.memory_peak_bytes}
    result = {"correct": correct, "attempted": ran["attempted"],
              "failed": ran["failed"]}
    if trace:
        from chipbench import xplane

        reduced = xplane.load(xplane.find(cell.trace_dir))
        shutil.rmtree(cell.trace_dir, ignore_errors=True)
        summary = reduced["summary"] = xplane.summary(reduced)
        result["metrics"] = per_layer_metrics(cell, bench, ran["observed"],
                                              reduced)
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    else:
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in end_to_end.items()}
    result["device"] = device
    result["run"] = {"workload": workload, "seed": seed, "seconds": seconds,
                     "trace": int(bool(trace)), "window_s": cell.window_s,
                     "setup_s": cell.setup_s, "cache_dir": cache_dir,
                     "reference_s": ran.get("reference_s")}
    result["compared"] = {c["name"]: [c["value"], c["limit"]]
                          for c in checks}      # last, as the record keeps
    for c in checks:
        print("chipbench: compared %-28s %s (limit %s)%s" % (
            c["name"], _fmt(c["value"]), _fmt(c["limit"]),
            "" if c["value"] is not None and c["value"] <= c["limit"]
            else "  <-- NOT CORRECT"), file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
    return result


def _fmt(v):
    return "%.6g" % v if isinstance(v, float) else str(v)
