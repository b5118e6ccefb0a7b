#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the checkout root.  ``<name>`` is a workload of ``BENCHMARK.json``.
Set-up (imports, TPU start-up, the cell's checks and one whole warm-up
job, which compiles or loads every scan program the window uses) is timed
as ``setup_s``; then jobs run back to back until ``--seconds`` have passed.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` traces
the window with the JAX profiler and the program's span stream and reports
its per-layer metrics.  After the window the plain reference checks what
the jobs produced (``correct``).

Without a TPU, or with fewer chips than the cell asks for, the run exits
with a non-zero code and prints no result.  The last line of standard
output is the result, one JSON object.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    # run as a script: resolve ``bench`` and the program from the checkout,
    # not from this file's directory
    sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from bench import devtrace, jobs, manifest, spans  # noqa: E402

OUT = ROOT / ".bench_out"


def fail(message: str) -> None:
    """End the run with exit code 1 and no result line."""
    raise SystemExit(f"bench: {message}")


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        fail("--seed must be a whole number >= 0")
    return args


def check_device(chips: int) -> dict:
    """The chips JAX finds; fails unless they are TPUs, enough of them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU found (jax platform {devices[0].platform!r})")
    if len(devices) < chips:
        fail(f"the cell needs {chips} chips, jax finds {len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache, inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` points); every program is kept."""
    import jax

    from repro import compile_cache

    where = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def device_memory_peak(chips: int) -> int:
    import jax

    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def scan_programs() -> float:
    from repro import obs

    return obs.counters().get("scan.jax.programs", 0)


def run_window(work, seconds: float) -> tuple[list[dict], float]:
    """Jobs back to back until ``seconds`` have passed; every job that
    starts is finished.  Returns the answers and the elapsed seconds."""
    from repro import obs

    answers = []
    t0 = time.perf_counter()
    while True:
        with obs.span("bench.job"), jobs.annotate("bench.job"):
            answers.append(work.job())
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            return answers, elapsed


def traced_window(work, seconds: float, out: Path):
    """The window under the JAX profiler and the program's span stream.
    Returns answers, elapsed seconds, spans and the device reduction."""
    import jax

    from repro import obs

    if out.exists():
        shutil.rmtree(out)
    out.mkdir(parents=True)
    obs_path = out / "obs.jsonl"
    obs.enable(obs_path)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # no event per Python call
    jax.profiler.start_trace(str(out / "xplane"), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.anchor"):
        anchor_wall_ns = time.time_ns()
    answers, elapsed = run_window(work, seconds)
    jax.profiler.stop_trace()
    obs.disable()
    events = devtrace.read_xplane(
        next((out / "xplane").rglob("*.xplane.pb")))
    anchor = next(e for e in events if e.name == "bench.anchor")
    offset_ns = anchor_wall_ns - anchor.start_ns   # wall = profiler + offset
    jobs_ev = [e for e in events if e.name == "bench.job"]
    lo = min(e.start_ns for e in jobs_ev)
    hi = max(e.end_ns for e in jobs_ev)
    span_list = spans.read_spans(obs_path)
    return answers, elapsed, span_list, events, (lo, hi), offset_ns


def breakdown(stats: devtrace.DeviceStats, span_list, offset_ns: float):
    """The top device ops, and idle time by what the host was doing."""
    main = [s for s in span_list if s.name == "bench.job"]
    tids = {(s.pid, s.tid) for s in main}
    segs = spans.timeline([s for s in span_list if (s.pid, s.tid) in tids])
    mids = [((a + b) / 2 + offset_ns) / 1e3 for a, b in stats.gaps]
    idle: dict[str, float] = {}
    for (a, b), name in zip(stats.gaps, spans.attribute(mids, segs)):
        key = name or "outside any span"
        idle[key] = idle.get(key, 0.0) + (b - a)
    return {"device_ops": devtrace.top(stats.ops),
            "idle_gaps": devtrace.top(idle)}


class Context:
    """What a per-layer metric reader may read."""

    def __init__(self, refs: int, spans_, stats) -> None:
        self.refs = refs          # trace references characterized
        self.spans = spans_       # the program's host spans (bench.spans)
        self.device = stats       # devtrace.DeviceStats of the window


def check(work, answers: list[dict]):
    """Drop the program's state, run the plain reference, compare."""
    last = work.last_outputs()
    gc.collect()
    return work.compare(answers, last, work.reference(last))


def make_result(numbers, attempted: int, metrics: dict, device: dict,
                breakdown_: dict | None = None) -> dict:
    """The run's result line: ``correct`` is every compared number within
    its limit; the numbers with their limits come last."""
    correct = all(n.ok for n in numbers)
    result = {"correct": correct, "attempted": attempted,
              "failed": 0 if correct else attempted,
              "metrics": metrics, "device": device}
    if breakdown_ is not None:
        result["breakdown"] = breakdown_
    result["check"] = {n.name: {"value": n.value, "limit": n.limit}
                       for n in numbers}
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = manifest.cell(args.workload)
    device = check_device(cell.chips)
    cache = enable_compile_cache()
    print(f"# device {device} compile_cache={cache}", flush=True)

    work = jobs.make(cell.config, cell.traffic, args.seed)
    work.setup()
    with jobs.annotate("bench.warmup"):
        work.job()
    refs_per_job = work.count_refs()
    programs_before = scan_programs()
    setup_s = time.perf_counter() - T_START
    print(f"# setup_s={setup_s!r} refs_per_job={refs_per_job} "
          f"scan_programs_before_window={programs_before}", flush=True)

    if args.trace:
        answers, elapsed, span_list, events, (lo, hi), offset_ns = \
            traced_window(work, args.seconds, OUT / cell.name)
    else:
        answers, elapsed = run_window(work, args.seconds)
    compiled = scan_programs() - programs_before
    print(f"# jobs={len(answers)} window_s={elapsed!r} "
          f"scan_programs_compiled_in_window={compiled}", flush=True)
    if compiled:
        fail(f"{compiled} scan programs compiled inside the window")
    device["memory_peak_bytes"] = device_memory_peak(cell.chips)
    refs = refs_per_job * len(answers)

    trace_breakdown = None
    if args.trace:
        stats = devtrace.reduce_events(events, lo, hi, cell.chips)
        del events
        device["busy_s"] = stats.busy_s
        device["window_s"] = stats.window_ns / 1e9
        ctx = Context(refs, span_list, stats)
        metrics = {}
        for m in cell.per_layer:
            value = manifest.load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        trace_breakdown = breakdown(stats, span_list, offset_ns)
    else:
        values = {"sim_refs_per_s": refs / elapsed, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    t_check = time.perf_counter()
    numbers = check(work, answers)
    print(f"# check_s={time.perf_counter() - t_check!r}", flush=True)
    for n in numbers:
        print(f"check {n.name}: {n.value!r} (limit {n.limit!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(make_result(numbers, len(answers), metrics, device,
                                 trace_breakdown)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
