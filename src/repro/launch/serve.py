"""Serving entry point: the continuous engine with its width planner, swapper
and AOT compile cache, on the published config unless ``--reduced``.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --reduced --requests 8 --new-tokens 16

Every width plan is AOT-compiled before the first request
(``warm_compile``), so serving performs no trace; the run ends with a
drain whose ledger accounts for every request.
"""

from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import numpy as np

from repro.configs import get_config, list_archs, reduced_config
from repro.core.hardware import TPU_V5E, device_hardware
from repro.launch.jax_cache import enable_compile_cache
from repro.models import init_params
from repro.serving import (
    ContinuousServeEngine, Request, ServingWidthPlanner, TrafficClass,
    WidthSwapper, WidthVariantCompileCache, serving_templates,
)


def build_engine(params, cfg, hw, *, slots: int, max_len: int,
                 prefill_chunk: Optional[int] = None,
                 prefill_bucket_min: int = 8, boundary_every: int = 4):
    """The serving stack: a width plan for the decode traffic class
    (Algorithm 2 on ``hw``), a swapper that realizes it on ``params``,
    and a compile cache the engine's executables come from.  Returns
    ``(engine, plans)``; pass the plans to ``engine.warm_compile``.

    The cache prices a boundary compile at zero because ``warm_compile``
    compiles every plan before serving starts, so plans realize sliced."""
    templates, modules = serving_templates(cfg, hw, tokens=slots,
                                           sites=("mlp", "attn"))
    cache = WidthVariantCompileCache(cfg, hw=hw, compile_cost_s=0.0)
    planner = ServingWidthPlanner(hw, templates, modules=modules,
                                  compile_cache=cache)
    plans = list(planner.plan([TrafficClass("decode", slots)]).values())
    engine = ContinuousServeEngine(
        params, cfg, max_len=max_len, batch_slots=slots, planner=planner,
        swapper=WidthSwapper(params, cfg), compile_cache=cache,
        prefill_chunk=prefill_chunk, prefill_bucket_min=prefill_bucket_min,
        boundary_every=boundary_every)
    return engine, plans


def planning_hardware():
    """The spec of the device in use, or on a CPU the explicit v5e
    planning target (never reported as the device)."""
    return device_hardware() or TPU_V5E


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill with this chunk (0: whole prompt)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    if cfg.is_encdec or cfg.rope_kind == "mrope":
        raise SystemExit(f"{cfg.name}: serve CLI demo covers decoder-only "
                         f"text archs; see tests for enc-dec decode")

    enable_compile_cache()
    dev = jax.devices()[0]
    hw = planning_hardware()
    print(f"device: {dev.platform} {dev.device_kind!r}; planning for "
          f"{hw.name}; arch {cfg.name}")

    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    engine, plans = build_engine(
        params, cfg, hw, slots=args.batch_slots,
        max_len=args.prompt_len + args.new_tokens,
        prefill_chunk=args.prefill_chunk or None)
    t0 = time.perf_counter()
    warmed = engine.warm_compile(plans, prefill_lengths=[args.prompt_len])
    traced = engine.compile_cache.tracer.count
    print(f"warm_compile: {warmed} executables in "
          f"{time.perf_counter() - t0:.2f}s")

    rng = np.random.default_rng(args.seed)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size,
                                        size=(args.prompt_len,)).astype(
                        np.int32),
                    max_new_tokens=args.new_tokens,
                    temperature=args.temperature)
            for _ in range(args.requests)]
    t0 = time.perf_counter()
    results = engine.run(reqs)
    dt = time.perf_counter() - t0
    ledger = engine.drain()
    total_new = sum(len(r.tokens) for r in results)
    print(f"{len(reqs)} requests, {total_new} tokens in {dt:.2f}s wall; "
          f"ledger {ledger.finished} finished, {ledger.shed} shed, "
          f"{ledger.failed} failed; boundaries "
          f"{[b.outcome for b in engine.boundary_log]}; traces after "
          f"warm-up {engine.compile_cache.tracer.count - traced}")
    for i, r in enumerate(results[:4]):
        print(f"  req{i}: {r.tokens[:12].tolist()}...")
    return engine, results


if __name__ == "__main__":
    main()
