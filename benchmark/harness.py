"""What every kind of cell shares: reading the manifest, the device
check, the compile meter, host spans, the profiler's window, the
per-layer readers, and the result line."""

import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

OUT_DIR = ".bench_out"  # inside the checkout; .gitignore lists it


class CompileMeter:
    """Counts what jax compiled, or fetched from the persistent cache, in
    this process (jax's own monitoring events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


class Run:
    """One run's facts, handed to the driver and to the readers."""

    def __init__(self, args, root, t_start):
        self.args, self.root, self.t_start = args, root, t_start
        self.here = os.path.join(root, "benchmark")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.manifest = json.load(f)
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if args.workload not in cells:
            raise SystemExit(
                f"no cell {args.workload!r} in BENCHMARK.json: {sorted(cells)}")
        self.cell = cells[args.workload]
        with open(os.path.join(
                self.here, "workloads", self.cell["name"] + ".json")) as f:
            self.cell_file = json.load(f)
        cfg_entry = next(
            c for c in self.manifest["configs"]
            if c["name"] == self.cell["config"])
        with open(os.path.join(root, cfg_entry["file"])) as f:
            self.config = json.load(f)
        self.rehearse = bool(args.rehearse)
        self.control = bool(args.control)
        self.trace = bool(args.trace)
        if self.rehearse:
            tiny = self.cell_file.get("rehearse", {})
            self.config = {**self.config, **tiny.get("config", {})}
            for key in ("traffic", "engine", "check"):
                if key in tiny:
                    self.cell_file[key] = {
                        **self.cell_file.get(key, {}), **tiny[key]}
        self.traffic = self.cell_file["traffic"]
        self.family = importlib.import_module(
            "benchmark.families." + self.config["family"])
        self.reference = importlib.import_module(
            "benchmark.reference." + self.config["family"])
        self.spans = []  # (name, start, end) on time.perf_counter
        self.facts = {}  # what the driver learned, for the readers
        self.checks = []  # (what, value, limit, ok)
        self.trace_data = None
        self.tracing = False
        self.trace_dir = os.path.join(root, OUT_DIR, "trace", self.cell["name"])

    # -- host spans --------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name):
        """A host span on the benchmark's clock and, while the profiler
        runs, in its trace as ``bench/<name>``."""
        import jax

        ann = (
            jax.profiler.TraceAnnotation("bench/" + name)
            if self.tracing else contextlib.nullcontext()
        )
        t0 = time.perf_counter()
        with ann:
            yield
        self.spans.append((name, t0, time.perf_counter()))

    # -- the profiler's window ---------------------------------------------

    def start_trace(self):
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the benchmark's spans are enough
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.tracing = True

    def stop_trace(self):
        import jax

        from benchmark import trace_reduce

        jax.profiler.stop_trace()
        self.tracing = False
        path = trace_reduce.newest_xplane(self.trace_dir)
        if path is not None:
            self.trace_data = trace_reduce.load(path)
            self.facts["trace_file"] = path

    # -- checks ------------------------------------------------------------

    def check(self, what, value, limit, ok=None):
        """One number compared beside its limit; printed in every run."""
        if ok is None:
            ok = value <= limit
        ok = bool(ok)
        self.checks.append((what, value, limit, ok))
        print(f"check {what}: {value!r} limit {limit!r} -> "
              f"{'ok' if ok else 'NOT ok'}", flush=True)
        return ok

    def limit(self, name):
        return self.cell_file["check"][name]["limit"]


def device_facts():
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_peak_bytes():
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.local_devices()
    ]
    return int(max(peaks)) if peaks else 0


def load_peaks(here, kind):
    with open(os.path.join(here, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table or kind.startswith("_"):
        raise SystemExit(
            f"device kind {kind!r} is not in benchmark/peaks.json: add its "
            "published peaks with their source, a default would be a guess")
    return table[kind]


def metrics_of(manifest, cell_name, group):
    """The manifest's metrics of ``group`` that this cell reports."""
    def reports(m):
        return "workloads" not in m or cell_name in m["workloads"]

    if group == "end_to_end":
        return [m for m in manifest["end_to_end"] if reports(m)]
    mine = {m["name"] for m in manifest["end_to_end"] if reports(m)}
    return [
        m for m in manifest["per_layer"]
        if (cell_name in m["workloads"] if "workloads" in m
            else m["moves"] in mine)
    ]


def read_layer_metric(here, name, run):
    path = os.path.join(here, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark.layer_metrics." + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def run(args, root, t_start):
    run_ = Run(args, root, t_start)

    # the program's own placement of the compile cache: where
    # JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache
    from fms_fsdp_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    # small programs (norms of a leaf, the sampler) are cached too, so
    # that a second run compiles nothing and set-up stays the same
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    try:
        device = device_facts()
    except Exception as e:  # no backend at all
        print(f"no device answered: {e}", file=sys.stderr)
        return 3
    chips = int(run_.cell["chips"])
    if not run_.rehearse:
        if device["platform"] != "tpu":
            print(f"jax found {device['platform']!r}, not a tpu: no result",
                  file=sys.stderr)
            return 3
        if device["count"] != chips:
            print(f"cell {run_.cell['name']} asks for {chips} chip(s), jax "
                  f"reports {device['count']}: no result", file=sys.stderr)
            return 3
        run_.peaks = load_peaks(run_.here, device["kind"])
    else:
        run_.peaks = None
        if device["count"] < chips:
            print(f"rehearsal of a {chips}-chip cell needs {chips} devices "
                  f"(XLA_FLAGS=--xla_force_host_platform_device_count={chips})",
                  file=sys.stderr)
            return 3
    run_.device = device
    run_.chips = chips
    run_.meter = CompileMeter()
    print(f"cell {run_.cell['name']} seed {args.seed} seconds {args.seconds} "
          f"trace {args.trace} cache {cache_dir} device {device}", flush=True)

    driver = importlib.import_module("benchmark.drivers." + run_.cell_file["kind"])
    out = driver.run(run_)

    run_.check("compiles_in_window", out["compiles_in_window"], 0)
    correct = all(ok for _, _, _, ok in run_.checks)
    if run_.control:
        print(f"control run: correct has to be false, it is {correct}")

    metrics = {}
    if run_.rehearse and run_.trace:
        # the readers are walked, their CPU readings are not reported
        read = [
            m["name"]
            for m in metrics_of(run_.manifest, run_.cell["name"], "per_layer")
            if read_layer_metric(run_.here, m["name"], run_) is not None]
        print(f"rehearsal read per-layer metrics: {read}")
    if not run_.rehearse:
        if run_.trace:
            for m in metrics_of(run_.manifest, run_.cell["name"], "per_layer"):
                v = read_layer_metric(run_.here, m["name"], run_)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        else:
            for m in metrics_of(run_.manifest, run_.cell["name"], "end_to_end"):
                v = out["end_to_end"].get(m["name"])
                if v is not None:
                    metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    device_out = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    line = {
        "correct": bool(correct) and not run_.rehearse,
        "attempted": int(out["attempted"]),
        "failed": int(out["failed"]),
        "metrics": metrics,
        "device": device_out,
    }
    if run_.rehearse:
        line["rehearsal"] = True
        line["rehearsal_checks_passed"] = bool(correct)
    if run_.trace and run_.trace_data is not None and not run_.rehearse:
        from benchmark import trace_reduce

        busy, window = trace_reduce.busy_and_window_s(run_.trace_data)
        device_out["busy_s"], device_out["window_s"] = busy, window
        line["breakdown"] = {
            "device_ops": trace_reduce.top_device_ops(run_.trace_data),
            "idle_gaps": trace_reduce.idle_gaps_by_span(run_.trace_data),
        }
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    # a result from the chip is a result, whatever it says; a rehearsal
    # is none
    return 1 if run_.rehearse else 0
