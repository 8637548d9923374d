"""Serving driver: continuous-batching decode engine demo.

    PYTHONPATH=src python -m repro.launch.serve --arch minicpm-2b --requests 8
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..configs import ARCH_IDS, get_config
from ..models import build
from ..serve import DecodeEngine, Request
from .compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="minicpm-2b", choices=ARCH_IDS)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=16)
    a = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(a.arch, smoke=True)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = DecodeEngine(model, params, max_batch=a.max_batch, max_seq=a.max_seq)
    rng = np.random.default_rng(0)
    t0 = time.time()
    for rid in range(a.requests):
        prompt = rng.integers(0, cfg.vocab_size, size=(4 + rid % 13,)).astype(np.int32)
        eng.submit(Request(rid, prompt, max_new_tokens=a.new_tokens))
    done = eng.run()
    dt = time.time() - t0
    total_tokens = sum(len(r.out_tokens) for r in done)
    print(f"served {len(done)} requests, {total_tokens} tokens in {dt:.1f}s "
          f"({total_tokens/dt:.1f} tok/s, continuous batching over "
          f"{a.max_batch} slots)")
    for r in done[:3]:
        print(f"  req {r.rid}: {r.out_tokens[:8]}...")


if __name__ == "__main__":
    main()
