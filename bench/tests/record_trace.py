"""Record the small TPU trace that ``test_trace.py`` reduces.

    python bench/tests/record_trace.py [--out DIR]

Runs, on one TPU, a few calls of a jitted matmul step and of the program's
Pallas paged-decode kernel at a small size, inside the harness's own span
names, with host sleeps between them so that the trace has idle gaps.
Copies the profiler's ``.xplane.pb`` to ``bench/tests/data/small.xplane.pb``
and prints a summary of its planes, lines and event names (the summary is
what the reduction in ``bench/lib/trace.py`` was written against).
"""
from __future__ import annotations

import argparse
import collections
import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))


def summarize(path: str) -> None:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            durs = collections.Counter()
            for ev in events:
                durs[ev.name] += ev.duration_ns
            print(f"  LINE {line.name!r}: {len(events)} events")
            for name, ns in durs.most_common(8):
                print(f"    {ns / 1e3:12.1f} us  {name[:100]}")
            if events:
                ev = events[0]
                stats = [(k, str(v)[:60]) for k, v in ev.stats][:8]
                print(f"    first event start_ns={ev.start_ns} "
                      f"stats={stats}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="profile directory (default: a new temporary one)")
    args = ap.parse_args()
    args.out = args.out or tempfile.mkdtemp(prefix="trace-probe-")
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.models  # noqa: F401  (models before kernels)
    from repro.kernels.decode_attention.ops import paged_gqa_decode_attention

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    slots, page, pages, kvh, d, h = 4, 16, 33, 2, 128, 8
    key = jax.random.PRNGKey(0)
    kp = jax.random.normal(key, (2, pages, page, kvh * d), jnp.bfloat16)
    vp = jax.random.normal(jax.random.fold_in(key, 1), kp.shape, jnp.bfloat16)
    table = jnp.asarray(np.arange(1, pages).reshape(slots, -1), jnp.int32)
    pos = jnp.asarray([5, 40, 100, 127], jnp.int32)
    q = jax.random.normal(key, (slots, h, d), jnp.bfloat16)
    attn = jax.jit(lambda q, k, v, t, p: paged_gqa_decode_attention(
        q, k, v, t, p, layer=jnp.int32(1), impl="fused"))
    w = jax.random.normal(key, (1024, 1024), jnp.bfloat16)

    @jax.jit
    def step(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x

    x = jnp.ones((256, 1024), jnp.bfloat16)
    jax.block_until_ready((attn(q, kp, vp, table, pos), step(x, w)))
    shutil.rmtree(args.out, ignore_errors=True)
    jax.profiler.start_trace(args.out)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                jax.block_until_ready(step(x, w))
                jax.block_until_ready(attn(q, kp, vp, table, pos))
            with jax.profiler.TraceAnnotation("bench.generator"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(args.out, "**", "*.xplane.pb"),
                      recursive=True)
    print(f"trace files: {found}")
    dst = os.path.join(HERE, "data", "small.xplane.pb")
    shutil.copy(found[0], dst)
    print(f"copied {found[0]} ({os.path.getsize(dst)} bytes) -> {dst}")
    summarize(dst)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
