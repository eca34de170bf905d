"""Helpers for driving the harness on the CPU at a reduced size."""
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def data(name: str) -> dict:
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


def bench_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def execute(config: dict, mix: dict, *, seed: int, seconds: float = 2.0,
            cell: str = "phi3.decode_backlog"):
    """A run of the harness with the chip check skipped: everything after
    it (set-up, window, reference comparison) as ``bench/run.py`` does."""
    from bench import run
    from bench.lib import peaks
    return run.execute(bench_json(), {"name": cell, "chips": 1}, config,
                       mix, seed=seed, seconds=seconds, trace=False,
                       device={"platform": "cpu"},
                       peaks=peaks.PEAKS["TPU v5 lite"],
                       t_start=time.perf_counter())
