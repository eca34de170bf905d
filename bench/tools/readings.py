"""Readings a cell's ``correct`` limits are set from, in one process.

    python bench/tools/readings.py <cell> <seconds> <seed> [<seed> ...]

For each seed: one run of the cell's set-up and window as ``bench/run.py``
makes it, then, with the engine freed, the numbers ``correct`` compares
for the program against the float32 reference (the lower readings) and
for the control in the program's place: the same reference with weights
and keys/values rounded through float8 e4m3, read at the same prompts and
served tokens (the upper readings).  Each side goes through the harness's
own comparison against the configuration's limits (``passed``).  One JSON
line per seed, prefixed ``READING``, with the window's end-to-end numbers.
Needs the chip, like a run.
"""
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main(argv) -> int:
    from bench import run
    from bench.lib import check, serve
    cell_name, seconds, seeds = argv[0], float(argv[1]), argv[2:]
    bench = run.load_json("BENCHMARK.json")
    cell, config, mix = run.cell_spec(bench, cell_name)
    run.use_compile_cache()
    for seed in (int(s) for s in seeds):
        t = time.perf_counter()
        r = serve.Run(config, mix, seed, seconds)
        r.run()
        e2e = serve.end_to_end(r)
        chosen = check.sample(r.tracks.values(), seed,
                              int(mix.get("check_requests", 4)))
        r.free()
        gc.collect()
        t1 = time.perf_counter()
        prog = check.compare(config, seed, chosen)
        t2 = time.perf_counter()
        ctl = check.control(config, seed, chosen)
        print("READING", json.dumps({
            "seed": seed,
            "program": {k: c["value"] for k, c in prog.items()},
            "program_passed": check.passed(prog),
            "control": {k: c["value"] for k, c in ctl.items()},
            "control_passed": check.passed(ctl),
            "limits": {k: c["limit"] for k, c in prog.items()},
            "requests": len(chosen),
            "finished": sum(c.finished for c in chosen),
            "tokens": sum(len(c.tokens) for c in chosen),
            "longest": max((len(c.req.prompt) + len(c.tokens)
                            for c in chosen), default=0),
            "output_tok_s": e2e["output_tok_s"],
            "itl_p95_ms": e2e.get("itl_p95_ms"),
            "window_compiles": r.compiles["window"],
            "run_s": t1 - t, "reference_s": t2 - t1,
            "control_s": time.perf_counter() - t2}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
