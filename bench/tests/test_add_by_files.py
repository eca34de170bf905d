"""A cell, a traffic mix, a configuration and a per-layer metric added as
new files (and entries in ``BENCHMARK.json``, which is data) are found by
the harness without an edit to any file that was there."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap

from bench.tests import harness

ROOT = harness.ROOT


def digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            if "__pycache__" in d or f.endswith(".pyc"):
                continue
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_cell_mix_config_and_metric_are_found(tmp_path):
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = digest(root)

    # new files only
    shutil.copy(os.path.join(ROOT, "bench", "tests", "data", "tiny.json"),
                os.path.join(root, "bench", "configs", "tiny2.json"))
    mix = harness.data("tiny_backlog.json")
    with open(os.path.join(root, "bench", "traffic", "tiny_mix.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "bench", "metrics", "window_tokens.py"),
              "w") as f:
        f.write(textwrap.dedent('''
            """window_tokens (tokens): decode tokens emitted in the window."""


            def read(rec):
                return sum(len(s.decode_ctx) for s in rec.steps) or None
        '''))
    # ... and entries in BENCHMARK.json
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny2", "source": "test",
                             "file": "bench/configs/tiny2.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny2.tiny_mix", "config": "tiny2",
                               "traffic": "tiny_mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "window_tokens", "unit": "tokens",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler",
                               "moves": "output_tok_s",
                               "workloads": ["tiny2.tiny_mix"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    script = textwrap.dedent('''
        import json, sys, time, types
        sys.path[:0] = ["src", "."]
        from bench import run
        from bench.lib import peaks, serve
        bench = run.load_json("BENCHMARK.json")
        cell, config, mix = run.cell_spec(bench, "tiny2.tiny_mix")
        res = run.execute(bench, cell, config, mix, seed=7, seconds=1.0,
                          trace=False, device={"platform": "cpu"},
                          peaks=peaks.PEAKS["TPU v5 lite"],
                          t_start=time.perf_counter())
        r = serve.Run(config, mix, 7, 1.0)
        r.run()
        rec = types.SimpleNamespace(run=r, window=r.window, steps=r.steps,
                                    trace=None, config=config, mix=mix,
                                    peaks=None)
        layer = run.read_metrics(bench, "tiny2.tiny_mix", True, rec, {})
        print(json.dumps({"e2e": sorted(res["metrics"]),
                          "correct": res["correct"],
                          "layer": sorted(layer)}))
    ''')
    out = subprocess.run([sys.executable, "-c", script], cwd=root,
                         capture_output=True, text=True, timeout=600,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["e2e"] == ["itl_p95_ms", "output_tok_s", "setup_s"]
    assert got["correct"] is True
    # the new metric is read; metrics needing a trace read nothing here
    assert "window_tokens" in got["layer"]
    after = digest(root)
    assert {k: after[k] for k in before} == before
