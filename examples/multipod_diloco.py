"""Multi-pod training with DiLoCo outer sync + int8-EF compression.

    PYTHONPATH=src python examples/multipod_diloco.py

Simulates 2 pods on 8 fake host devices: each pod runs H=4 independent
inner AdamW steps (compiled with ZERO cross-pod collectives — asserted by
parsing the HLO), then pods synchronize once via the compressed outer
Nesterov step.  Cross-pod traffic: params x 1 byte / (H steps), vs
params x 4 bytes / step for naive DP — a ~16x DCI reduction.
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
sys.path.insert(0, "src")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config, reduced_config  # noqa: E402
from repro.core.hlo_analysis import parse_collectives  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.parallel import diloco  # noqa: E402
from repro.parallel.compression import wire_bytes  # noqa: E402
from repro.train import (  # noqa: E402
    DataConfig, SyntheticLM, TrainConfig, adamw_init, build_train_step,
    cosine_schedule,
)


def main():
    n_pods, h, rounds = 2, 4, 6
    mesh = make_mesh((n_pods, 2, 2), ("pod", "data", "model"))
    cfg = reduced_config(get_config("qwen1.5-0.5b"), d_model=64,
                         n_layers=2, vocab=512)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tc = TrainConfig(moe_strategy="dense")
    step = build_train_step(cfg, tc, cosine_schedule(3e-3, 4, 200))
    inner = jax.jit(diloco.build_inner_steps(step, h))

    pp = diloco.replicate_for_pods(params, n_pods)
    oo = diloco.replicate_for_pods(adamw_init(params), n_pods)
    shard = lambda t: jax.device_put(t, NamedSharding(mesh, P("pod")))
    pp, oo = jax.tree.map(shard, pp), jax.tree.map(shard, oo)
    outer = diloco.init_outer_state(params)
    dcfg = diloco.DilocoConfig(inner_steps=h, compress=True)

    # prove the inner loop never crosses pods
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                                  global_batch=n_pods * h * 4))
    def pod_batches(r):
        b = data.batch(r)
        return jax.tree.map(
            lambda x: shard(jnp.asarray(x).reshape(n_pods, h, 4,
                                                   *x.shape[1:])), b)
    lowered = jax.jit(diloco.build_inner_steps(step, h)).lower(
        pp, oo, pod_batches(0), jnp.asarray(0))
    colls = parse_collectives(lowered.compile().as_text())
    max_group = max((o.group_size for o in colls.ops), default=1)
    assert max_group <= 4, "inner steps leaked cross-pod collectives!"
    print(f"inner-step collectives confined to pods "
          f"(max group {max_group} <= data*model=4)")

    naive = wire_bytes(params, "f32") * h
    ours = wire_bytes(params, "int8")
    print(f"cross-pod bytes per {h} steps: naive DP={naive/1e6:.2f}MB, "
          f"DiLoCo+int8EF={ours/1e6:.2f}MB ({naive/ours:.0f}x less)")

    for r in range(rounds):
        pp, oo, losses = inner(pp, oo, pod_batches(r), jnp.asarray(r * h))
        pp, outer = diloco.outer_step(pp, outer, dcfg, mesh)
        lm = np.asarray(losses).mean(axis=1)
        print(f"round {r}: per-pod inner-loss means "
              f"{np.round(lm, 3).tolist()}")
    print("OK: multi-pod DiLoCo training ran end-to-end")


if __name__ == "__main__":
    main()
