"""Time the paged decode kernel alone at the benchmark cells' shapes.

For each serving cell of ``BENCHMARK.json`` (its configuration's widths,
slots, page size and window; its traffic's set-up depths) and each of three
position profiles,

* ``pos15``: every slot at position 15, one live block a slot;
* ``depth``: the slots at the depths the cell's set-up fills them to
  (``bench.lib.traffic.generate``, so the window's first decode step);
* ``last``:  every slot at ``max_len - 1``,

it builds one layer's page pools and a page table laid out as the engine
leaves it (live blocks on distinct pages, every other entry on the scratch
page 0), runs the kernel back to back on the host clock, and prints
microseconds per layer call and per grid step, the walk's form and pages
per step, and the largest gap to the float32 oracle.  ``--shards N`` gives
the kernel the heads one of N tensor-parallel shards holds; ``--cache
fp8|int8`` quantized pools with their per-token scales.

A TPU is required:

    PYTHONPATH=src python -m benchmarks.paged_walk [--cells phi3,danube]
        [--shards N] [--cache fp8|int8] [--out paged_walk.json]

``bench/run.py`` does not run it.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
MIN_SECONDS = 0.5          # timed calls per profile: at least this long


def cells() -> dict[str, tuple[dict, dict]]:
    """Configuration name -> (configuration, traffic) of its first cell."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    out = {}
    for w in bench["workloads"]:
        if w["config"] in out:
            continue
        cfg = json.loads((ROOT / "bench" / "configs"
                          / f"{w['config']}.json").read_text())
        mix = json.loads((ROOT / "bench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
        out[w["config"]] = (cfg, mix)
    return out


def profiles(cfg: dict, mix: dict) -> dict[str, np.ndarray]:
    from bench.lib import traffic
    s = cfg["serving"]
    slots = s["num_slots"]
    reqs = traffic.generate(mix, 0, vocab=2, num_slots=slots)[:slots]
    return {"pos15": np.full(slots, 15, np.int64),
            "depth": np.array([len(r.prompt) for r in reqs], np.int64),
            "last": np.full(slots, s["max_len"] - 1, np.int64)}


def layout(pos, page, n_blocks, window, rng):
    """A page table with each slot's live blocks on distinct pages."""
    from repro.kernels.decode_attention.paged_kernel import live_walk
    lo, live, _ = live_walk(pos, page, window, 1)
    n_pages = 1 + int(live.sum())
    ids = rng.permutation(np.arange(1, n_pages))
    table = np.zeros((len(pos), n_blocks), np.int32)
    at = 0
    for b in range(len(pos)):
        table[b, lo[b]:lo[b] + live[b]] = ids[at:at + live[b]]
        at += live[b]
    return table, n_pages


def time_calls(fn, args) -> float:
    """Seconds per call of ``fn(*args)``, back to back."""
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    fn(*args).block_until_ready()
    one = time.perf_counter() - t0
    n = max(3, int(MIN_SECONDS / max(one, 1e-6)))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / n


def measure(name: str, cfg: dict, mix: dict, seed: int, shards: int = 1,
            cache: str | None = None) -> list[dict]:
    import jax
    import jax.numpy as jnp
    import repro.models  # noqa: F401  (import order: models before kernels)
    from repro.kernels.decode_attention import paged_kernel as pk
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    from repro.quant import kv as kvq

    m, s = cfg["model"], cfg["serving"]
    heads, kvh, d = (m["num_attention_heads"] // shards,
                     m["num_key_value_heads"] // shards, m["head_dim"])
    window, page = m["sliding_window"], s["page_size"]
    slots, n_blocks = s["num_slots"], -(-s["max_len"] // page)
    width = kvh * d
    itemsize = 2 if cache is None else 1
    ppb = pk.pages_per_step(page, width, itemsize, n_blocks, window)
    chunked = width % pk.LANES == 0
    steps = slots if chunked else slots * pk.max_live_blocks(page, n_blocks,
                                                             window)
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    kernel = jax.jit(lambda q, k, v, t, p, *sc: pk.paged_decode_attention(
        q, k, v, t, p, window=window,
        **dict(zip(("k_scales", "v_scales"), sc))))
    rows = []
    for prof, pos in profiles(cfg, mix).items():
        table, n_pages = layout(pos, page, n_blocks, window, rng)
        kq, kk, kv = jax.random.split(jax.random.fold_in(key, len(rows)), 3)
        q = jax.random.normal(kq, (slots, heads, d), jnp.bfloat16)
        kp = jax.random.normal(kk, (n_pages, page, kvh, d), jnp.bfloat16)
        vp = jax.random.normal(kv, (n_pages, page, kvh, d), jnp.bfloat16)
        scales = ()
        if cache is not None:
            (kp, ks), (vp, vs) = (kvq.kv_quantize(kp, cache),
                                  kvq.kv_quantize(vp, cache))
            scales = (ks, vs)
        lanes = lambda a: a.reshape(n_pages, page, width)
        args = (q, lanes(kp), lanes(vp), jnp.asarray(table),
                jnp.asarray(pos, jnp.int32)) + scales
        secs = time_calls(kernel, args)
        out = np.asarray(kernel(*args), np.float32)
        with jax.default_matmul_precision("highest"):
            f32 = lambda a: a.astype(jnp.float32)
            ref = np.asarray(paged_decode_attention_ref(
                f32(q), f32(kp), f32(vp), args[3], args[4],
                k_scales=scales[0] if scales else None,
                v_scales=scales[1] if scales else None, window=window))
        _, live, chunks = pk.live_walk(pos, page, window, ppb)
        rows.append({
            "cell": name, "profile": prof, "shards": shards,
            "cache": cache or "bf16", "width": width, "slots": slots,
            "n_blocks": n_blocks, "window": window,
            "walk": "chunk" if chunked else "page", "pages_per_step": ppb,
            "mean_pos": float(pos.mean()), "live_blocks": int(live.sum()),
            "walked_blocks": int(chunks.sum()) * ppb, "grid_steps": steps,
            "us_per_call": secs * 1e6, "us_per_grid_step": secs * 1e6 / steps,
            "max_abs_err": float(np.abs(out - ref).max())})
        del q, kp, vp, args, scales
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default=None,
                    help="comma-separated configuration names (default all)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shards", type=int, default=1,
                    help="time one of this many tensor-parallel shards")
    ap.add_argument("--cache", choices=("fp8", "int8"), default=None,
                    help="quantized K/V pools (default bf16)")
    ap.add_argument("--out", default=None, help="write the rows as JSON here")
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"paged_walk: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    known = cells()
    want = args.cells.split(",") if args.cells else list(known)
    rows = []
    for name in want:
        cfg, mix = known[name]
        for r in measure(name, cfg, mix, args.seed, args.shards,
                         args.cache):
            r["device"] = dev.device_kind
            print(json.dumps(r), flush=True)
            rows.append(r)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
