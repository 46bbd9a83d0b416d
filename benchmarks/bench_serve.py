"""Serving benchmark: continuous batching vs a static-batch baseline.

Replays the same arrival trace (staggered arrivals, heterogeneous output
lengths) through two servers built on the same params:

* **static** — the pre-``repro.serve`` discipline: wait for a full batch
  of requests, prefill them together, decode until the LAST member
  finishes, repeat.  Short requests ride along to the batch straggler's
  horizon and late arrivals wait for the next batch boundary.
* **continuous** — the ``ServeEngine``: requests join mid-flight via
  prefill-into-free-slots and retire individually, so the persistent
  decode step stays full.

Both paths keep the token pick on device (greedy argmax folded into the
step) and sync to host only at poll points.  Compile time is excluded:
the engine's table is AOT-compiled up front and the static step fns are
warmed on a dummy batch before the clock starts.

Writes BENCH_serve.json: tokens/s + p50/p99 per-request latency vs
offered load, alongside the decode-phase bandwidth roofline
(analysis.roofline.decode_bandwidth_bound).

  PYTHONPATH=src python benchmarks/bench_serve.py [--arch yi-6b]
"""
from __future__ import annotations

import argparse
import json
import platform
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.roofline import decode_bandwidth_bound, peaks
from repro.configs import reduced_config
from repro.data.pipeline import MarkovLM
from repro.models import lm
from repro.serve import ServeEngine, default_geometry

OUT = Path(__file__).resolve().parents[1] / "BENCH_serve.json"
# the decode roofline is priced for this chip, not the host that runs here
ROOFLINE_KIND = "TPU v5 lite"


def _trace(args):
    """[(arrival_step, prompt list, max_new)] — arrivals staggered every
    ``gap`` steps, output lengths alternating long/short so a static
    batch always carries straggler padding."""
    gen = MarkovLM(args.vocab, seed=args.seed)
    prompts = gen.sample(args.requests, args.prompt_len + 1,
                         step=0)[:, :args.prompt_len].tolist()
    return [(i * args.gap, p,
             args.max_new if i % 2 == 0 else max(args.max_new // 8, 1))
            for i, p in enumerate(prompts)]


def _percentiles(lat):
    return (float(np.percentile(lat, 50)), float(np.percentile(lat, 99)))


def bench_continuous(cfg, params, trace, args) -> dict:
    geom = default_geometry(num_slots=args.slots, page_size=args.page_size,
                            max_context=args.max_context)
    eng = ServeEngine(cfg, geom=geom, params=params, chunk=args.chunk)
    eng.compile_table()
    # untimed warm session: every executable (admit buckets + chunked
    # decode) runs once before the clock starts, so first-execution
    # lazy-init cost is excluded along with compile time
    for _ in range(args.slots + 1):
        eng.submit(trace[0][1], max_new=2)
    eng.drain(poll_every=1)
    eng.clock = eng.decode_steps = 0
    eng._slot_uses = [0] * args.slots

    pending = list(trace)
    arrived, finished = {}, {}
    t0 = time.perf_counter()
    while pending or eng.scheduler.queue or eng._live:
        # arrivals are in decode steps; one engine step is `chunk` of them
        while pending and pending[0][0] <= eng.clock * args.chunk:
            _, prompt, max_new = pending.pop(0)
            req = eng.submit(prompt, max_new=max_new)
            arrived[req.rid] = time.perf_counter() - t0
        eng.step(1)
        # poll at chunk boundaries: the host sync amortizes over the chunk
        for req in eng.poll():
            finished[req.rid] = time.perf_counter() - t0
    for req in eng.poll():
        finished[req.rid] = time.perf_counter() - t0
    wall = time.perf_counter() - t0
    lat = [finished[r] - arrived[r] for r in finished]
    toks = sum(r[2] for r in trace)
    p50, p99 = _percentiles(lat)
    return {"mode": "continuous", "gap_steps": args.gap,
            "requests": len(trace), "new_tokens": toks,
            "chunk": args.chunk,
            "decode_steps": eng.decode_steps,
            "slots_reused": eng.stats()["slots_reused"],
            "tokens_per_s": round(toks / wall, 2), "wall_s": round(wall, 3),
            "p50_s": round(p50, 4), "p99_s": round(p99, 4)}


def bench_static(cfg, params, trace, args) -> dict:
    """Full-batch prefill + decode-to-the-last-straggler baseline."""
    B = args.slots
    V = cfg.vocab_size
    max_len = args.prompt_len + args.max_new

    prefill = jax.jit(lambda p, b, c: lm.prefill(p, b, cfg, c))
    decode = jax.jit(lambda p, c, t: lm.decode_step(p, c, t, cfg))
    pick = jax.jit(lambda lg: jnp.argmax(lg[..., :V], -1).astype(jnp.int32))

    def run_batch(prompts):
        cache = lm.init_cache(cfg, B, max_len)
        logits, cache = prefill(params, {"tokens": jnp.asarray(
            prompts, jnp.int32)}, cache)
        tok = pick(logits)
        steps = 1
        for _ in range(args.max_new - 1):   # the whole batch rides to the
            logits, cache = decode(params, cache, tok)      # longest req
            tok = pick(logits)
            steps += 1
        jax.block_until_ready(tok)
        return steps

    run_batch([trace[0][1]] * B)            # jit warmup, excluded

    pending = list(trace)
    waiting, lat = [], []
    total_steps = 0
    clock = 0                               # arrival clock in decode steps
    t0 = time.perf_counter()
    while pending or waiting:
        while pending and pending[0][0] <= clock:
            _, prompt, max_new = pending.pop(0)
            waiting.append((time.perf_counter() - t0, prompt))
        if len(waiting) >= B or (not pending and waiting):
            batch = waiting[:B]
            waiting = waiting[B:]
            prompts = [p for _, p in batch]
            prompts += [prompts[-1]] * (B - len(prompts))   # tail padding
            total_steps += run_batch(prompts)
            clock = total_steps
            now = time.perf_counter() - t0
            lat.extend(now - t_arr for t_arr, _ in batch)
        else:
            clock += 1                      # idle tick waiting for a batch
    wall = time.perf_counter() - t0
    toks = sum(r[2] for r in trace)
    p50, p99 = _percentiles(lat)
    return {"mode": "static", "gap_steps": args.gap,
            "requests": len(trace), "new_tokens": toks,
            "decode_steps": total_steps,
            "tokens_per_s": round(toks / wall, 2), "wall_s": round(wall, 3),
            "p50_s": round(p50, 4), "p99_s": round(p99, 4)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--max-context", type=int, default=64)
    ap.add_argument("--gaps", type=int, nargs="+", default=[1, 2, 4],
                    help="offered loads: one request every N decode steps")
    ap.add_argument("--chunk", type=int, default=4,
                    help="decode steps per dispatch for the continuous "
                         "engine (multi-step scheduling)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(OUT))
    args = ap.parse_args()

    cfg = reduced_config(args.arch)
    args.vocab = cfg.vocab_size
    params = lm.init_lm(jax.random.key(args.seed), cfg)

    rows = []
    for gap in args.gaps:
        args.gap = gap
        trace = _trace(args)
        rows.append(bench_continuous(cfg, params, trace, args))
        rows.append(bench_static(cfg, params, trace, args))

    hbm_bw = peaks(ROOFLINE_KIND).hbm_bw
    rec = {
        "arch": args.arch, "requests": args.requests,
        "prompt_len": args.prompt_len, "max_new": args.max_new,
        "slots": args.slots, "page_size": args.page_size,
        "max_context": args.max_context,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "platform": platform.platform(),
        "jax_version": jax.__version__,
        "roofline": {
            "device_kind": ROOFLINE_KIND,
            "hbm_bw": hbm_bw,
            "decode_tokens_per_s_bound": round(decode_bandwidth_bound(
                cfg, args.slots, args.max_context,
                bw=hbm_bw), 2),
        },
        "rows": rows,
    }
    Path(args.out).write_text(json.dumps(rec, indent=2) + "\n")
    for r in rows:
        print(f"{r['mode']:>10} gap={r['gap_steps']} "
              f"tok/s={r['tokens_per_s']:8.1f}  p50={r['p50_s']*1e3:7.1f}ms "
              f"p99={r['p99_s']*1e3:7.1f}ms  steps={r['decode_steps']}")
    bound = rec["roofline"]["decode_tokens_per_s_bound"]
    print(f"roofline decode bound (batch={args.slots}, "
          f"ctx={args.max_context}): {bound:.0f} tok/s")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
