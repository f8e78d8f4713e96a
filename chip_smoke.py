#!/usr/bin/env python3
"""Proof that the serving path runs on one TPU chip.

    python3 chip_smoke.py                # one TPU: serve qwen1.5-0.5b
    python3 chip_smoke.py --four-chips   # four TPUs: sharded train steps

Serving (the default): qwen1.5-0.5b at its published widths, random
weights from ``--seed``, served through ``launch.serve.build_engine`` —
``ContinuousServeEngine`` with its width planner, swapper and AOT compile
cache — on the wall clock, with chunked prefill.  A full-width probe
request, a few dozen seeded requests that cross into a sliced width plan
with live KV, and a probe under that plan; then a drain.  It fails unless
the ledger is complete with nothing failed, the compile cache recorded no
fault and no fallback, nothing traced after warm-up, every executable
holds a Pallas kernel (``tpu_custom_call``), and both probes' logits
match the float32 reference (``transformer.reference_logits``).

``--four-chips`` runs only this: a few ``launch.train`` steps of the same
model on a mesh over the host's four devices with ``param_shardings``,
the same steps on one device, and a comparison of the losses.

Off a TPU the script refuses to run.  ``--rehearse`` is the exception:
with ``JAX_PLATFORMS=cpu`` it runs everything on a reduced config with
interpret-mode Pallas kernels (for ``--four-chips`` add
``XLA_FLAGS=--xla_force_host_platform_device_count=4``), and never
prints the result line, which only a TPU run prints.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Per-position tolerance on the logits: ||engine - reference|| /
# ||reference|| over the vocabulary.  The engine computes in bfloat16
# (unit roundoff 2^-9); at a reduced size on the CPU its worst position
# is 2.7% off the float32 reference, float16 compute 0.26%, and float8
# (e4m3) compute 39%.  0.1 leaves bfloat16 room for the full depth and
# fails any 8-bit compute.
LOGIT_TOL = 0.1

# Serving shapes: (published size on the chip, CPU rehearsal).
SERVE = {
    False: dict(slots=16, max_len=2048, chunk=512, bucket_min=256,
                requests=32, prompt=(64, 1024), new=(16, 48),
                probe=(1017, 8)),
    True: dict(slots=4, max_len=256, chunk=64, bucket_min=32,
               requests=8, prompt=(8, 128), new=(4, 8), probe=(57, 8)),
}
# Boundaries are considered every 16 engine steps: the full-width probe
# finishes inside the first 16, and the first crossing then lands in the
# served traffic, with requests live.
BOUNDARY_EVERY = 16

# Train steps for --four-chips: (published size, CPU rehearsal).
TRAIN = {False: dict(batch=8, seq=256, steps=3),
         True: dict(batch=8, seq=32, steps=3)}
# Loss agreement between the 4-device mesh and one device: the sharded
# step reduces in another order over bfloat16 activations, so losses
# near ln(vocab) ~ 12 agree to about 1e-3 relative, not bit for bit.
LOSS_TOL = 2e-2


class SmokeFailure(AssertionError):
    pass


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def model_config(rehearse: bool):
    from repro.configs import get_config, reduced_config
    cfg = get_config("qwen1.5-0.5b")
    if rehearse:
        # head_dim 64 as published; d_ff 640 leaves the planner a cut
        cfg = reduced_config(cfg, d_model=256, n_heads=4, n_layers=2,
                             d_ff=640, vocab=512)
    return cfg


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def probe_logits(eng, cfg, params, prompt, n_new: int) -> float:
    """Serve one request alone and compare the logits the engine's own
    executables produced (the final prefill chunk's last row, then every
    decode step's row) with the reference forward over the same tokens.
    Returns the worst per-position relative error."""
    import jax.numpy as jnp
    import numpy as np
    from repro.models.transformer import reference_logits
    from repro.serving import Request

    require(not eng._outstanding(), "probe needs an idle engine")
    seen = {"chunk": [], "decode": []}
    chunk_fn, decode_fn = eng._chunk, eng._decode

    def tap_chunk(*a):
        out = chunk_fn(*a)
        seen["chunk"].append(out[0])
        return out

    def tap_decode(*a):
        out = decode_fn(*a)
        seen["decode"].append(out[0])
        return out

    eng._chunk, eng._decode = tap_chunk, tap_decode
    try:
        [res] = eng.run([Request(prompt=prompt, max_new_tokens=n_new)])
    finally:
        eng._chunk, eng._decode = chunk_fn, decode_fn
    require(len(res.tokens) == n_new and len(seen["decode"]) == n_new - 1,
            f"probe served {len(res.tokens)} tokens in "
            f"{len(seen['decode'])} decode steps")
    v, plen = cfg.vocab_size, len(prompt)
    last = (plen - 1) % eng.prefill_chunk
    got = [seen["chunk"][-1][0, last, :v]] + [d[0, :v]
                                               for d in seen["decode"]]
    got = np.stack([np.asarray(g.astype(jnp.float32)) for g in got])
    toks = np.concatenate([prompt, res.tokens[:-1]]).astype(np.int32)
    ref = reference_logits(params, cfg, jnp.asarray(toks[None]))
    ref = np.asarray(ref[0, plen - 1:, :v], np.float32)
    err = np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    agree = int((got.argmax(-1) == ref.argmax(-1)).sum())
    print(f"  logits vs float32 reference at {len(err)} positions: worst "
          f"relative error {err.max():.5f} (tolerance {LOGIT_TOL}), "
          f"argmax agrees at {agree}/{len(err)}")
    require(float(err.max()) <= LOGIT_TOL,
            f"logits off the reference: {err.max()} > {LOGIT_TOL}")
    return float(err.max())


def serve(args, dev) -> None:
    import jax
    import numpy as np
    from repro.core.hardware import TPU_V5E, hardware_for_kind
    from repro.kernels import ops
    from repro.launch.serve import build_engine
    from repro.models import init_params
    from repro.serving import Request

    sz = SERVE[args.rehearse]
    cfg = model_config(args.rehearse)
    # the rehearsal plans for v5e; it never reports that as its device
    hw = TPU_V5E if args.rehearse else hardware_for_kind(dev.device_kind)
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}"
          f", {cfg.n_heads} heads, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}"
          f"; planning for {hw.name}")
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    eng, plans = build_engine(
        params, cfg, hw, slots=sz["slots"], max_len=sz["max_len"],
        prefill_chunk=sz["chunk"], prefill_bucket_min=sz["bucket_min"],
        boundary_every=BOUNDARY_EVERY)
    cache = eng.compile_cache

    rng = np.random.default_rng(args.seed)
    lens = rng.integers(sz["prompt"][0], sz["prompt"][1] + 1,
                        size=sz["requests"])
    news = rng.integers(sz["new"][0], sz["new"][1] + 1, size=sz["requests"])
    requests = [Request(prompt=rng.integers(0, cfg.vocab_size, size=int(n))
                        .astype(np.int32), max_new_tokens=int(m))
                for n, m in zip(lens, news)]
    plen, n_probe = sz["probe"]
    probes = [rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
              for _ in range(2)]

    t0 = time.perf_counter()
    with ops.kernel_context(force="pallas_interpret" if args.rehearse
                            else None):
        warmed = eng.warm_compile(plans, prefill_lengths=[*lens, plen])
    compile_s = time.perf_counter() - t0
    traced = cache.tracer.count
    print(f"warm_compile: {warmed} executables in {compile_s:.2f}s "
          f"(compile seconds, set-up)")
    for ev in cache.events:
        exe = cache.executable(*ev.key[1:])
        require(args.rehearse or "tpu_custom_call" in exe.as_text(),
                f"no Pallas kernel in the {ev.key[1]} executable "
                f"{ev.key[3]}")
    print(f"  Pallas kernel (tpu_custom_call) in every executable: "
          f"{'not checked in interpret mode' if args.rehearse else 'yes'}")

    print("probe at full width:")
    probe_logits(eng, cfg, params, probes[0], n_probe)
    require(not eng.boundary_log, "a boundary crossed during the probe")

    t0 = time.perf_counter()
    results = eng.run(requests)
    wall = time.perf_counter() - t0
    served = sum(len(r.tokens) for r in results)
    print(f"served {len(results)} requests, {served} new tokens, "
          f"{int(lens.sum())} prompt tokens; smoke wall time {wall:.2f}s "
          f"(not a benchmark)")
    crossed = [b for b in eng.boundary_log if b.outcome == "ok"]
    require(len(crossed) == len(eng.boundary_log) and crossed,
            f"boundaries: {eng.boundary_log}")
    require(crossed[0].carried > 0, "the crossing carried no live KV")
    require(not eng._masked_active
            and cache.active_key != cache.full_key,
            "the active plan is not a sliced executable")
    print(f"  crossed to plan {crossed[0].plan_name!r} at step "
          f"{crossed[0].step} carrying {crossed[0].carried} live requests;"
          f" query heads per layer now {sorted({int(h) for h in eng._heads_active})}")

    print("probe under the sliced plan:")
    probe_logits(eng, cfg, eng.params_active, probes[1], n_probe)

    ledger = eng.drain()
    print(f"ledger: {ledger}")
    require(ledger.complete and ledger.failed == 0
            and ledger.finished == ledger.submitted, "ledger")
    faults = [e for e in cache.events if e.outcome == "fault"]
    require(cache.stats["fallbacks"] == 0 and not faults,
            f"compile cache faults: {cache.stats} {faults}")
    require(cache.tracer.count == traced,
            f"{cache.tracer.count - traced} traces after warm-up")
    require(cache.stats["misses"] == 0, f"cache misses: {cache.stats}")
    print(f"compile cache: {cache.stats}; traces after warm-up 0")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}")


# ---------------------------------------------------------------------------
# --four-chips
# ---------------------------------------------------------------------------
def train_steps(cfg, mesh, batches, seed: int):
    """``launch.train``'s step on ``mesh``: losses, compile seconds, and
    the set of device counts the parameters span."""
    import jax
    import jax.numpy as jnp
    from repro.launch.train import init_train_state, train_config
    from repro.parallel import sharding as shlib
    from repro.train import build_train_step, cosine_schedule

    tc = train_config()
    lr = cosine_schedule(3e-3, 1, len(batches))
    with shlib.activity(mesh, {}):
        params, opt = init_train_state(cfg, tc, mesh, seed)
        spans = {len(x.sharding.device_set)
                 for x in jax.tree.leaves(params)}
        split = sum(not x.sharding.is_fully_replicated
                    for x in jax.tree.leaves(params))
        step_fn = jax.jit(build_train_step(cfg, tc, lr),
                          donate_argnums=(0, 1))
        t0 = time.perf_counter()
        compiled = step_fn.lower(params, opt, batches[0],
                                 jnp.asarray(0)).compile()
        compile_s = time.perf_counter() - t0
        losses = []
        for i, b in enumerate(batches):
            params, opt, m = compiled(params, opt, b, jnp.asarray(i))
            losses.append(float(m["loss"]))
    return losses, compile_s, spans, split


def four_chips(args) -> None:
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_host_mesh, make_mesh
    from repro.train import DataConfig, make_source

    devs = jax.devices()
    require(len(devs) == 4, f"--four-chips needs 4 devices, not "
                            f"{len(devs)}")
    cfg = model_config(args.rehearse)
    sz = TRAIN[args.rehearse]
    src = make_source(DataConfig(vocab_size=cfg.vocab_size, seq_len=sz["seq"],
                                 global_batch=sz["batch"], seed=args.seed))
    batches = [{k: jnp.asarray(v) for k, v in src.batch(i).items()}
               for i in range(sz["steps"])]
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}"
          f"; {sz['steps']} train steps of batch {sz['batch']} x "
          f"{sz['seq']} tokens")

    mesh4 = make_host_mesh()
    l4, c4, spans, split = train_steps(cfg, mesh4, batches, args.seed)
    print(f"mesh {dict(mesh4.shape)}: losses {l4}, compile {c4:.2f}s; "
          f"every parameter spans {sorted(spans)} devices, {split} "
          f"leaves split across them")
    require(spans == {4} and split > 0, "parameters do not span 4 devices")
    mesh1 = make_mesh((1, 1), ("data", "model"), devices=devs[:1])
    l1, c1, _, _ = train_steps(cfg, mesh1, batches, args.seed)
    print(f"one device: losses {l1}, compile {c1:.2f}s")
    diff = max(abs(a - b) for a, b in zip(l4, l1))
    print(f"largest loss difference {diff:.6f} (tolerance {LOSS_TOL})")
    require(diff <= LOSS_TOL, "losses disagree between 4 devices and 1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="sharded train steps on 4 devices vs one")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: reduced config, interpret-mode "
                         "kernels, no result line")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.jax_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    dev = jax.devices()[0]
    if (dev.platform == "tpu") == args.rehearse:
        print(f"chip_smoke: platform {dev.platform!r}; the smoke needs a "
              f"TPU, and --rehearse needs JAX_PLATFORMS=cpu",
              file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind!r} x "
          f"{len(jax.devices())}; compile cache {cache_dir}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args)
    else:
        serve(args, dev)
    print(f"chip_smoke: all checks passed in "
          f"{time.perf_counter() - t0:.2f}s")
    if not args.rehearse:
        print(json.dumps({"ok": True, "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
