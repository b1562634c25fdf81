"""Serving launcher: batched generation with the slot-based engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
        --requests 6 --prompt-len 16 --max-new 24
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import numpy as np

from repro import sharding, utils
from repro.configs import registry
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.serve.engine import ContinuousBatcher, Engine, ServeConfig

log = logging.getLogger("repro.serve")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b",
                    choices=[a for a in registry.ARCH_IDS])
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--quant", default="int8")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    args = ap.parse_args()

    utils.use_compile_cache()
    logging.basicConfig(level=logging.INFO)
    cfg = registry.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.enc_dec:
        raise SystemExit("use examples/whisper_serve.py for enc-dec archs")
    qcfg = registry.get_quant(args.quant)
    mesh = make_host_mesh()
    sharding.set_mesh(mesh)

    params = lm.lm_init(jax.random.PRNGKey(0), cfg)
    engine = Engine(params, cfg, qcfg,
                    ServeConfig(max_seq=args.max_seq, batch_slots=args.slots))
    batcher = ContinuousBatcher(engine)

    rng = np.random.default_rng(0)
    t0 = time.time()
    ids = [batcher.submit(rng.integers(0, cfg.vocab, args.prompt_len),
                          args.max_new)
           for _ in range(args.requests)]
    results = batcher.run_until_drained()
    dt = time.time() - t0
    total_tokens = sum(len(v) for v in results.values())
    log.info("served %d requests, %d tokens in %.2fs (%.1f tok/s)",
             len(results), total_tokens, dt, total_tokens / dt)
    for rid in ids[:3]:
        log.info("req %d -> %s", rid, results[rid][:16])


if __name__ == "__main__":
    main()
