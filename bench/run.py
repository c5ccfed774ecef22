#!/usr/bin/env python3
"""Run one cell of the benchmark on the accelerator JAX finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``). The traffic file names the generator
(``bench/generators/<generator>.py``) that drives it. The run makes its
inputs from ``--seed``, warms up every shape the window will use, measures
for ``--seconds``, frees the program's state, compares what the window
produced with the plain reference (``bench/reference/``) against the
cell's limits (``bench/limits/<cell>.json``), and prints one JSON line as
the last line of standard output:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "checks": {...}}

``--trace 0`` reports the cell's end-to-end metrics, taken with the
profiler off. ``--trace 1`` traces the window and reports the cell's
per-layer metrics, each read by ``bench/metrics/<metric>.py`` from the
reduced trace (``bench/trace.py``) and the generator's record of the
window.

Without a TPU, with fewer chips than the cell asks for, or on a device
that ``bench/peaks.json`` does not list, the run exits non-zero before it
measures anything and prints no result.
"""
import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class Refused(Exception):
    """The run cannot measure here (no chip, unknown device, no program)."""


def _paths() -> None:
    """Import from the checkout root: the script's own directory must not
    come first on the path, or ``bench/trace.py`` would shadow the
    standard library's ``trace``."""
    here = [p for p in sys.path if os.path.abspath(p or ".") == BENCH]
    for p in here:
        sys.path.remove(p)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_entry(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise Refused(f"no workload {name!r} in BENCHMARK.json; cells: "
                  f"{[w['name'] for w in spec['workloads']]}")


def cell_parts(cell: str, overrides: dict | None = None,
               traffic_overrides: dict | None = None):
    """The cell's entry, configuration and traffic, with overrides."""
    entry = cell_entry(load_json(ROOT, "BENCHMARK.json"), cell)
    config = dict(load_json(BENCH, "configs", f"{entry['config']}.json"))
    config.update(overrides or {})
    traffic = dict(load_json(BENCH, "traffic", f"{entry['traffic']}.json"))
    traffic.update(traffic_overrides or {})
    return entry, config, traffic


def cell_metrics(spec: dict, cell: str):
    """The end-to-end and per-layer entries that ``cell`` reports."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if cell in m.get("workloads", ()) or
             ("workloads" not in m and m["moves"] in names)]
    return e2e, layer


def load_metric(name: str):
    """``bench/metrics/<name>.py``: a reader ``read(ctx) -> float | None``."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_devices(chips: int, peaks: dict):
    """The devices to run on, or :class:`Refused`."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX found "
                      f"{len(devs)}")
    if devs[0].device_kind not in peaks:
        raise Refused(f"no peaks for device kind {devs[0].device_kind!r} in "
                      f"bench/peaks.json")
    return devs


class CompileWatch:
    """Counts JAX traces, backend compiles and persistent-cache hits and
    misses, so that the window can be shown to compile nothing."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}
    CACHE = {"/jax/compilation_cache/cache_hits": "cache_hits",
             "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        import jax

        self.counts = dict.fromkeys(
            list(self.EVENTS.values()) + list(self.CACHE.values()), 0)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **_):
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def _event(self, event, **_):
        if event in self.CACHE:
            self.counts[self.CACHE[event]] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


def enable_cache() -> str:
    """JAX's persistent compilation cache in the checkout (``.jax_cache/``,
    or ``JAX_COMPILATION_CACHE_DIR``), keeping every program however fast
    it compiled, so a second run of a cell finds all of them."""
    import jax
    from repro.core.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def memory_peak(devs) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    return max(peaks)


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """Each reading against its limit; every limited number must be read."""
    missing = sorted(set(limits) - set(readings))
    extra = sorted(set(readings) - set(limits))
    if missing or extra:
        raise ValueError(f"checks read {sorted(readings)}, limits name "
                         f"{sorted(limits)}")
    # a comparison that found no number (a missing or malformed answer)
    # reads as the largest float, which no limit admits
    checks = {name: {"value": readings[name] if math.isfinite(readings[name])
                     else sys.float_info.max,
                     "limit": limits[name]["limit"]} for name in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             need_chip: bool = True, overrides: dict | None = None,
             traffic_overrides: dict | None = None, control: bool = False,
             watch: "CompileWatch | None" = None, log=sys.stderr) -> dict:
    """One run of ``cell``; returns the result line as a dict.

    ``need_chip=False`` skips the look for a TPU (tests on the CPU);
    ``overrides`` and ``traffic_overrides`` replace keys of the
    configuration and the traffic (smaller sizes, for tests);
    ``control=True`` puts the reference, one precision step down, in the
    program's place. Runs in one process share one ``watch``.
    """
    import jax

    from bench import trace as trace_mod
    from bench.generators.base import MetricContext

    spec = load_json(ROOT, "BENCHMARK.json")
    entry, config, traffic = cell_parts(cell, overrides, traffic_overrides)
    limits = load_json(BENCH, "limits", f"{cell}.json")["limits"]
    peaks = load_json(BENCH, "peaks.json")
    e2e, layer = cell_metrics(spec, cell)
    if need_chip:
        devs = check_devices(entry["chips"], peaks)
        peak = peaks[devs[0].device_kind]
    else:
        devs = jax.devices()
        peak = peaks.get(devs[0].device_kind) or next(iter(peaks.values()))
    watch = watch or CompileWatch()
    gen_mod = importlib.import_module(
        f"bench.generators.{traffic['generator']}")

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None

    def span(name):
        if not trace:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    gen = gen_mod.Generator(config, traffic, seed, span=span,
                            control=control, log=log)
    try:
        gen.setup()
        # objects made in set-up stay: out of the collector's full passes,
        # which otherwise pause the window for about 0.1 s each
        gc.freeze()
        setup_s = time.perf_counter() - T_START
        before = watch.snapshot()
        print(f"set-up: {setup_s:.3f} s; " + ", ".join(
            f"{k} {v}" for k, v in before.items()), file=log, flush=True)
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with span(trace_mod.WINDOW_SPAN):
                window = gen.run_window(seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        after = watch.snapshot()
        delta = {k: after[k] - before[k] for k in after}
        print(f"window: {window.seconds:.3f} s, {window.attempted} attempted, "
              f"{window.failed} failed; inside it: "
              + ", ".join(f"{k} {v}" for k, v in delta.items()),
              file=log, flush=True)
        for line in window.notes:
            print(line, file=log, flush=True)
        mem = memory_peak(devs) if need_chip else 0
        result = {"correct": False, "attempted": window.attempted,
                  "failed": window.failed, "metrics": {}, "device": {
                      "platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs),
                      "memory_peak_bytes": mem}}
        if trace:
            reduced = trace_mod.reduce(trace_mod.find_xplane(tdir),
                                       span_names=gen.span_names)
            result["device"]["busy_s"] = reduced.busy_s
            result["device"]["window_s"] = reduced.window_s
            result["breakdown"] = {
                "device_ops": [[n, s] for n, s in reduced.top_ops],
                "idle_gaps": [[n, s] for n, s in reduced.idle_gaps]}
            print("spans: " + ", ".join(
                f"{n} {c}x {t:.3f} s" for n, (c, t) in reduced.spans.items()),
                file=log, flush=True)
            ctx = MetricContext(reduced=reduced, peak=peak, window=window)
            for m in layer:
                value = load_metric(m["name"]).read(ctx)
                if value is not None:
                    result["metrics"][m["name"]] = {"value": value,
                                                    "unit": m["unit"]}
        else:
            measured = dict(window.end_to_end, setup_s=setup_s)
            for m in e2e:
                result["metrics"][m["name"]] = {"value": measured[m["name"]],
                                                "unit": m["unit"]}
        gen.release()
        readings = gen.check()
        ok, checks = judge(readings, limits)
        result["correct"] = ok and window.failed == 0
        result["checks"] = checks
        for name, c in checks.items():
            print(f"check {name}: {c['value']!r} (limit {c['limit']!r}) "
                  f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
                  file=log, flush=True)
        return result
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    _paths()
    try:
        import repro.core  # noqa: F401  the system under test
    except ImportError as e:
        print(f"bench: the program (src/repro) is missing: {e}",
              file=sys.stderr)
        return 3
    try:
        enable_cache()
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
