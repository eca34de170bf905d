#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``).  The run makes the weights and the
requests from ``--seed``, sets up and warms the serving engine, measures
``--seconds`` of serving, then checks what was served against the plain
reference (``bench/reference/``).  With ``--trace 1`` the window is traced
by the profiler and the cell's per-layer metrics (``bench/metrics/<name>.py``)
are read from the run's record and the trace; with ``--trace 0`` the
end-to-end metrics are reported.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced); its last key, ``checks``, holds each number compared with its
limit, which also close standard error.  With no TPU, or fewer chips than
the cell asks for, it prints no result and exits 1.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def load_json(*parts) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def cell_spec(bench: dict, name: str) -> tuple[dict, dict, dict]:
    """(cell, configuration file, mix file) of the cell called ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; know {sorted(cells)}")
    cell = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = load_json(confs[cell["config"]]["file"])
    mix = load_json("bench", "traffic", cell["traffic"] + ".json")
    return cell, config, mix


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics (``trace`` off) or per-layer ones."""
    if not trace:
        return [m for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])]
    reported = {m["name"] for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in reported
                             else [])]


def read_metrics(bench: dict, cell: str, trace: bool, rec, e2e: dict) -> dict:
    """The cell's metrics as the result line carries them: end-to-end ones
    from the window's numbers, per-layer ones each from its own reader,
    ``bench/metrics/<name>.py``; a reader with nothing to read gives None
    and its metric is left out."""
    out = {}
    for m in metrics_for(bench, cell, trace):
        if trace:
            value = importlib.import_module(
                f"bench.metrics.{m['name']}").read(rec)
        else:
            value = e2e.get(m["name"])
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def execute(bench: dict, cell: dict, config: dict, mix: dict, *, seed: int,
            seconds: float, trace: bool, device: dict, peaks: dict,
            t_start: float, stderr=sys.stderr) -> dict:
    """One run of a cell on the devices JAX has; returns the result."""
    import jax

    from bench.lib import check, serve
    from bench.lib import trace as trace_lib

    run = serve.Run(config, mix, seed, seconds)
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        t0, _ = run.run(tdir)
        setup_s = t0 - t_start
        stats = jax.devices()[0].memory_stats() or {}
        device = dict(device,
                      memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)))
        summary = None
        if tdir is not None:
            summary = trace_lib.summarize(trace_lib.load(tdir))
    finally:
        if tdir is not None:
            shutil.rmtree(tdir, ignore_errors=True)
    e2e = serve.end_to_end(run)
    e2e["setup_s"] = setup_s
    attempted, failed = serve.window_requests(run)
    # per-layer readers see the traced part of the window when traced
    w0, w1 = run.traced or run.window
    rec = types.SimpleNamespace(
        run=run, window=(w0, w1), trace=summary, peaks=peaks, config=config,
        mix=mix, steps=[s for s in run.steps if w0 <= s.t0 and s.t1 <= w1])
    out_metrics = read_metrics(bench, cell["name"], trace, rec, e2e)
    result = {"correct": False, "attempted": attempted, "failed": failed,
              "metrics": out_metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": summary.top_ops,
            "idle_gaps": sorted(([k, v] for k, v in
                                 summary.idle_by_span.items()),
                                key=lambda kv: -kv[1])[:10]}
    print(f"window: {e2e['tokens']} tokens, {e2e['gaps']} gaps, "
          f"{attempted} requests, {e2e['window_s']:.3f} s; "
          f"compiles in set-up {run.compiles['setup']}, in the window "
          f"{run.compiles['window']}, traces or lowerings in the window "
          f"{run.traces['window']}", file=stderr)
    steps = run.steps
    slow = sorted(steps, key=lambda s: s.t0 - s.t1)[:3]
    waits = sorted(zip(steps, steps[1:]), key=lambda p: p[0].t1 - p[1].t0)[:3]
    print("longest steps: " + ", ".join(
        f"{1e3 * (s.t1 - s.t0):.1f} ms at {s.t0 - t0:.2f} s" for s in slow)
        + "; longest waits between steps: " + ", ".join(
        f"{1e3 * (b.t0 - a.t1):.1f} ms at {a.t1 - t0:.2f} s"
        for a, b in waits), file=stderr)
    # the reference runs once the window is closed, the peak read and the
    # program's state freed
    chosen = check.sample(run.tracks.values(), seed,
                          int(mix.get("check_requests", 4)))
    run.free()
    del rec
    gc.collect()
    t_ref = time.perf_counter()
    checks = check.compare(config, seed, chosen)
    print(f"reference: {len(chosen)} requests, "
          f"{sum(len(t.tokens) for t in chosen)} served tokens, "
          f"{time.perf_counter() - t_ref:.1f} s", file=stderr)
    result["correct"] = check.passed(checks) and failed == 0
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be >= 0")

    bench = load_json("BENCHMARK.json")
    cell, config, mix = cell_spec(bench, args.workload)
    use_compile_cache()
    import jax

    from bench.lib import peaks as peaks_lib

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if dev.platform != "tpu" or len(devices) < int(cell["chips"]):
        print(f"bench: needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform} device(s): nothing was run",
              file=sys.stderr)
        return 1
    result = execute(bench, cell, config, mix, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace),
                     device=device, peaks=peaks_lib.for_kind(dev.device_kind),
                     t_start=T_START)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
