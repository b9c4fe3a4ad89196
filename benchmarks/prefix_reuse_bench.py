"""Serving prefix-KV reuse through the unified repository (ISSUE 10,
DESIGN.md §17).

A zipfian stream of requests over a small population of long prompt
prefixes (the shared-system-prompt regime) is served twice with the SAME
`ServeSession.serve` path: once cold (kv=None) and once with a
`KVRepository` attached.  Greedy decodes must be bit-identical; the
reuse arm reports wall speedup, reused-token fraction, and p50/p95
per-request latency.  The full-size entry is gated by
``tools/check_bench.py`` (``prefix_runs``: >= 2x wall speedup and
>= 0.5 reused-token fraction; bit-identity at any size).

Env knobs (CI runs a small labelled entry, nightly the full size):
  PREFIX_BENCH_REQUESTS  stream length            (default 48)
  PREFIX_BENCH_PROMPTS   distinct prefixes        (default 8)
  PREFIX_BENCH_PREFIX    prefix tokens            (default 1024)
  PREFIX_BENCH_SUFFIX    per-request suffix tokens (default 16)
  PREFIX_BENCH_DECODE    greedy decode tokens     (default 2)
  PREFIX_BENCH_ZIPF      zipf exponent            (default 1.1)
  PREFIX_BENCH_EVERY_K   alias stride             (default 64)
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, "src")

import numpy as np                                        # noqa: E402
import jax                                                # noqa: E402

from benchmarks.common import emit                        # noqa: E402
from repro.configs import get_config                      # noqa: E402
from repro.models.api import build                        # noqa: E402
from repro.serve.kv_repo import KVRepository              # noqa: E402
from repro.serve.session import ServeSession              # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_core.json")


def _zipf_ranks(n_requests: int, n_prompts: int, a: float, rng):
    """Zipf-distributed prefix choices clipped to the population."""
    w = 1.0 / np.arange(1, n_prompts + 1) ** a
    w /= w.sum()
    return rng.choice(n_prompts, size=n_requests, p=w)


def run(label: str | None = None, out_path: str = OUT):
    n_requests = int(os.environ.get("PREFIX_BENCH_REQUESTS", 48))
    n_prompts = int(os.environ.get("PREFIX_BENCH_PROMPTS", 8))
    prefix_len = int(os.environ.get("PREFIX_BENCH_PREFIX", 1024))
    suffix_len = int(os.environ.get("PREFIX_BENCH_SUFFIX", 16))
    n_decode = int(os.environ.get("PREFIX_BENCH_DECODE", 2))
    zipf_a = float(os.environ.get("PREFIX_BENCH_ZIPF", 1.1))
    # alias stride: prefix_len must be a multiple so the shared-prefix
    # boundary has an alias to hit
    every_k = int(os.environ.get("PREFIX_BENCH_EVERY_K", 64))

    cfg = get_config("qwen3-1.7b", smoke=True)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    max_len = prefix_len + suffix_len + n_decode + 2

    prefixes = [rng.integers(1, cfg.vocab_size, prefix_len)
                for _ in range(n_prompts)]
    ranks = _zipf_ranks(n_requests, n_prompts, zipf_a, rng)
    prompts = [np.concatenate(
        [prefixes[r], rng.integers(1, cfg.vocab_size, suffix_len)])
        for r in ranks]

    def run_arm(kv):
        sess = ServeSession(model, params, max_len=max_len, kv=kv,
                            every_k=every_k)
        # warm BOTH prefill shapes (full prompt + residual suffix) off
        # the clock with a disposable prefix that matches nothing later
        warm_prefix = rng.integers(1, cfg.vocab_size, prefix_len)
        for _ in range(2):
            sess.serve(np.concatenate(
                [warm_prefix,
                 rng.integers(1, cfg.vocab_size, suffix_len)]), n_decode)
        outs, stats, laps = [], [], []
        t0 = time.perf_counter()
        for p in prompts:
            t1 = time.perf_counter()
            o, s = sess.serve(p, n_decode)
            laps.append(time.perf_counter() - t1)
            outs.append(o)
            stats.append(s)
        return outs, stats, laps, time.perf_counter() - t0

    outs_plain, _, _, t_plain = run_arm(None)
    kv = KVRepository(model_version=cfg.name)
    outs_reuse, stats, laps, t_reuse = run_arm(kv)
    identical = all((a == b).all()
                    for a, b in zip(outs_plain, outs_reuse))
    assert identical, "prefix reuse must not change greedy decodes"

    reused = sum(s.reused_tokens for s in stats)
    total = sum(s.reused_tokens + s.prefilled_tokens for s in stats)
    speedup = t_plain / max(t_reuse, 1e-9)
    frac = reused / max(total, 1)
    lap_ms = np.asarray(laps) * 1e3

    rec = {"label": label or "run",
           "n_requests": n_requests, "n_prompts": n_prompts,
           "prefix_len": prefix_len, "suffix_len": suffix_len,
           "n_decode": n_decode, "zipf_a": zipf_a,
           "t_noreuse_s": round(t_plain, 6),
           "t_reuse_s": round(t_reuse, 6),
           "wall_speedup": round(speedup, 4),
           "reused_token_frac": round(frac, 4),
           "p50_reuse_ms": round(float(np.percentile(lap_ms, 50)), 3),
           "p95_reuse_ms": round(float(np.percentile(lap_ms, 95)), 3),
           "kv_entries": len(kv), "kv_bytes": kv.total_bytes,
           "exact_hits": kv.stats()["exact_hits"],
           "semantic_hits": kv.stats()["semantic_hits"],
           "identical": identical}
    emit("serve/prefix_stream", t_reuse,
         f"noreuse={t_plain:.4f}s;speedup={speedup:.2f};"
         f"reused_frac={frac:.2f};p95={rec['p95_reuse_ms']:.1f}ms;"
         f"identical={identical}")

    doc = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            doc = json.load(f)
    runs = doc.setdefault("prefix_runs", [])
    # keep the last 2 prior same-label entries (the nightly regression
    # gate compares consecutive same-label entries)
    same = [r for r in runs if r["label"] == rec["label"]][-2:]
    doc["prefix_runs"] = [r for r in runs
                          if r["label"] != rec["label"]] + same + [rec]
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    emit("serve/prefix_done", 0.0, f"out={out_path}")
    return rec


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(label=sys.argv[1] if len(sys.argv) > 1 else None)
