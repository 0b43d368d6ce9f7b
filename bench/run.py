"""Run one cell of ``BENCHMARK.json`` once and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``).  The configuration's
``kind`` names its runner (``bench/runners/<kind>.py``), which builds the
system from the seed, warms every shape the traffic uses, serves the
measured window and compares what the window produced with the plain
reference.  Each metric is a reader of its own (``bench/metrics/<name>.py``):
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` traces
the window and reports its per-layer metrics.

A runner is the class ``Runner(ctx)`` of ``bench/runners/<kind>.py``
(``ctx``: a ``Context``), with four methods that the harness calls in
this order:

  - ``setup()``: build the system from ``ctx.seed`` and warm every shape
    the traffic uses; this is set-up, timed as ``setup_s``;
  - ``serve(seconds)``: the measured window.  It sets ``ctx.records``:
    ``window_s`` (the window's seconds on the host clock), ``attempted``
    and ``failed`` (rounds or requests, and those that failed), and
    whatever the cell's end-to-end readers read (``fl_samples_per_s``:
    ``samples``).  It wraps each round in ``ctx.span("bench.round")``,
    which the per-round readers count;
  - ``release()``: free the program's state before the check;
  - ``check()``: compare what the window produced with the plain
    reference; returns ``[(name, value, limit), ...]``, every number the
    comparison read, with ``None`` as the limit of a number read but not
    compared.  The run is correct where every compared value is finite
    and at most its limit, nothing failed and nothing compiled in the
    window.

By the end of ``serve`` the runner has set the ``ctx.counters`` that the
shared per-layer readers of its cells take: ``users`` and
``params_per_user`` (``mix_roofline.fl``), ``samples_per_round`` and
``train_flops_per_sample`` (``fl_step_mfu``, from the kind's own count
functions).

A configuration of a new kind joins as new files: its configuration, its
runner, its plain reference (``bench/reference/``), its traffic mixes,
readers of its own metrics, its count functions (``bench/flops_<kind>.py``)
and its faults (``bench/faults_<kind>.py``, see ``faults.py``); and its
cells' names are added to the ``workloads`` lists of the shared metrics
they report.  Nothing else changes.

A run needs the accelerator: without a TPU, or with fewer chips than the
cell asks for, it exits 2 and prints no result.  JAX's persistent
compilation cache is ``<checkout>/.jax_cache``.  The last lines on standard
error are the compared numbers with their limits; the last line on
standard output is the result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, spec: dict | None = None) -> dict:
    """The workload entry with its configuration, traffic and metrics."""
    if spec is None:
        spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; choose from {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cell = make_cell(w, ROOT / configs[w["config"]]["file"], w["traffic"])
    e2e = [m for m in spec["end_to_end"] if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if ("workloads" in m and workload in m["workloads"])
             or ("workloads" not in m and m["moves"] in moved)]
    cell["end_to_end"], cell["per_layer"] = e2e, layer
    return cell


def load_runner(kind: str):
    """The runner module of a configuration's ``kind``."""
    return load_module(BENCH / "runners" / f"{kind}.py", "bench_runner_" + kind)


def make_cell(entry: dict, config_file, traffic: str) -> dict:
    """A cell from a workload entry, its configuration's file and the name
    of its traffic mix (``bench/traffic/<traffic>.json``), with no metrics:
    enough to drive and check it."""
    cell = dict(entry, end_to_end=[], per_layer=[])
    cell["config_data"] = json.loads(pathlib.Path(config_file).read_text())
    cell["traffic_data"] = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    return cell


def require_chips(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: jax sees {len(devices)} {devices[0].platform} device(s)")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, found {len(devices)}")
    return devices[:chips]


def add_paths() -> None:
    """Make the program (``src``) and the benchmark's modules importable."""
    for path in (str(BENCH), str(ROOT / "src")):
        if path not in sys.path:
            sys.path.insert(0, path)


def configure_jax() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Context:
    """What a runner and a metric reader see of the run."""

    def __init__(self, cell, seed, trace, devices, clock, variant="program"):
        self.cell = cell
        self.config = cell["config_data"]
        self.traffic = cell["traffic_data"]
        self.seed = seed
        self.trace = trace
        self.devices = devices
        self.clock = clock
        self.variant = variant
        self.counters: dict = {}
        self.records: dict = {}
        self.setup_s = None
        self.setup_parts: dict = {}
        self.tr = None          # trace_reduce.Trace of a traced window
        self.peaks = None
        self.flops = load_module(BENCH / "flops.py", "bench_flops")

    def span(self, name: str):
        """A host span in the profiler's trace (only while tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def part(self, name: str):
        """Time one part of set-up, with its compiling split out."""
        from clock import now

        t0 = now()
        try:
            yield
        finally:
            self.setup_parts[name] = (now() - t0, self.clock.seconds_since(t0))


def peaks_for(device) -> dict:
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if device.device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device.device_kind!r} in bench/peaks.json")
    return peaks[device.device_kind]


def read_metrics(ctx, entries) -> dict:
    out = {}
    for m in entries:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is None:
            log(f"metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(cell, seed, seconds, trace, devices, variant="program",
             readings: dict | None = None) -> dict:
    """Set up, serve the window, read the metrics, check; return the result.

    ``devices`` are the chips to run on (``require_chips``); the tests
    pass the CPU device instead.  ``readings``, where given, receives every
    number the check reads, those its configuration does not compare too.
    """
    add_paths()
    import jax

    from clock import CompileClock, now

    clock = CompileClock()
    ctx = Context(cell, seed, trace, devices, clock, variant)
    runner = load_runner(ctx.config["kind"]).Runner(ctx)
    runner.setup()
    ctx.setup_s = time.perf_counter() - T_START
    comp_total = clock.seconds_since(0.0)
    log(f"setup_s={ctx.setup_s:.3f} compile_s={comp_total:.3f} "
        f"backend_compiles={clock.backend_compiles} cache_hits={clock.cache_hits}")
    for name, (wall, comp) in ctx.setup_parts.items():
        log(f"  setup part {name}: wall_s={wall:.3f} compile_s={comp:.3f}")

    window = seconds
    if trace:
        window = min(seconds, float(ctx.traffic.get("trace_seconds", seconds)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    n_spans, n_backend, t_win = clock.traces(), clock.backend_compiles, now()
    try:
        with ctx.span("bench.window"):
            runner.serve(window)
    finally:
        if trace:
            jax.profiler.stop_trace()
    window_traces = clock.traces() - n_spans
    window_compiles = clock.backend_compiles - n_backend
    log(f"window: seconds={window} compile_spans={window_traces} "
        f"backend_compiles={window_compiles} compile_s={clock.seconds_since(t_win):.3f}")

    device = devices[0]
    ctx.peaks = peaks_for(device) if device.platform == "tpu" else None
    mem = memory_peak(devices)
    dev_out = {"platform": device.platform, "kind": device.device_kind,
               "count": len(devices), "memory_peak_bytes": mem}
    if trace:
        import trace_reduce as trace_mod

        ctx.tr = trace_mod.load(str(TRACE_DIR))
        for line in trace_mod.describe(ctx.tr):
            log(line)
        dev_out["busy_s"] = ctx.tr.busy_s()
        dev_out["window_s"] = ctx.tr.window_s
        metrics = read_metrics(ctx, cell["per_layer"])
        breakdown = {"device_ops": ctx.tr.top_ops(10), "idle_gaps": ctx.tr.idle_gaps(10)}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        metrics = read_metrics(ctx, cell["end_to_end"])
        breakdown = None

    runner.release()
    gc.collect()
    t_check = time.perf_counter()
    numbers = runner.check()
    log(f"check_s={time.perf_counter() - t_check:.3f}")
    if readings is not None:
        readings.update({name: v for name, v, _ in numbers})
    for name, v, lim in numbers:
        if lim is None:
            log(f"reading {name}: {v!r} (not compared)")
    log("counters: " + json.dumps(ctx.counters, default=str))
    checks = [(name, v, lim) for name, v, lim in numbers if lim is not None]
    rec = ctx.records
    failed = int(rec.get("failed", 0))
    correct = bool(checks) and failed == 0 and window_compiles == 0 and all(
        math.isfinite(v) and v <= lim for _, v, lim in checks)
    out = {"correct": correct, "attempted": int(rec.get("attempted", 0)),
           "failed": failed, "metrics": metrics, "device": dev_out}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {name: {"value": v, "limit": lim} for name, v, lim in checks}
    out["checks"]["window_compiles"] = {"value": window_compiles, "limit": 0}
    return out


def result_line(out: dict) -> str:
    """Log the compared numbers with their limits, the last lines on
    standard error, and return the result's JSON line."""
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    return json.dumps(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    add_paths()
    try:
        devices = require_chips(int(cell["chips"]))
    except NoChip as exc:
        log(str(exc))
        return 2
    configure_jax()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    print(result_line(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
