"""Multi-device semantics, run in subprocesses with 8 fake host devices
(XLA locks device count at first init, so these cannot share the main
pytest process)."""

import subprocess
import sys
import textwrap

import pytest

# full XLA compiles in subprocesses: quick tier skips with -m "not slow"
pytestmark = pytest.mark.slow

PREAMBLE = """
import os
# pin the CPU backend: without it jax probes for a TPU first (minutes of
# retried metadata fetches in this container) before falling back
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
"""


def run_sub(body: str):
    code = PREAMBLE + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=540,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"})
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


class TestFlashDecodeSharded:
    def test_matches_replicated(self):
        run_sub("""
        from repro.models.attention import flash_decode_sharded, \\
            decode_attention, update_cache_sharded
        mesh = make_mesh((2, 4), ("data", "model"))
        b, s, h, kv, dh = 4, 64, 8, 2, 16
        q = jax.random.normal(jax.random.PRNGKey(0), (b, h, dh))
        k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kv, dh))
        v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kv, dh))
        clen = jnp.asarray(40)
        out = jax.jit(lambda q,k,v: flash_decode_sharded(
            q, k, v, clen, mesh))(q, k, v)
        expect = decode_attention(q, k, v, clen)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(expect, np.float32),
                                   rtol=2e-2, atol=2e-2)
        # sharded cache write: only the owning shard commits
        new = jax.random.normal(jax.random.PRNGKey(3), (b, kv, dh))
        c2 = jax.jit(lambda c, n: update_cache_sharded(
            c, n, jnp.asarray(40), mesh))(k, new)
        ref = k.at[:, 40].set(new)
        np.testing.assert_allclose(np.asarray(c2), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        print("flash-decode OK")
        """)


class TestMoeEP:
    def test_ep_matches_single(self):
        run_sub("""
        from repro.models import moe as moe_lib
        from repro.parallel import sharding as shlib
        mesh = make_mesh((2, 4), ("data", "model"))
        d, e, f, k = 32, 8, 64, 2
        p = moe_lib.init_moe(jax.random.PRNGKey(0), d, e, f, False, f)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, d),
                              jnp.bfloat16)
        y1, _ = moe_lib.apply_moe_capacity(p, x, k, capacity_factor=8.0)
        with shlib.activity(mesh, {}):
            y2, _ = jax.jit(lambda p, x: moe_lib.apply_moe_capacity(
                p, x, k, capacity_factor=8.0, mesh=mesh))(p, x)
        np.testing.assert_allclose(np.asarray(y1, np.float32),
                                   np.asarray(y2, np.float32),
                                   rtol=6e-2, atol=6e-2)
        print("moe EP OK")
        """)


class TestShardedTrainStep:
    def test_tiny_arch_on_mesh(self):
        """Full train step on a (2,4) mesh with FSDP+TP param shardings;
        result must match the single-device step."""
        run_sub("""
        from repro.configs import get_config, reduced_config
        from repro.models import init_params
        from repro.train import TrainConfig, adamw_init, \\
            build_train_step, cosine_schedule
        from repro.parallel import sharding as shlib
        from repro.parallel.sharding import param_shardings
        cfg = reduced_config(get_config("qwen1.5-0.5b"), d_model=64,
                             n_layers=2, vocab=256)
        params = init_params(jax.random.PRNGKey(0), cfg)
        tc = TrainConfig(moe_strategy="dense")
        step = build_train_step(cfg, tc, cosine_schedule(1e-3, 2, 50))
        batch = {
          "tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                       cfg.vocab_size),
          "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0,
                                       cfg.vocab_size)}
        opt = adamw_init(params)
        p_ref, _, m_ref = jax.jit(step)(params, opt, batch, jnp.asarray(0))

        mesh = make_mesh((2, 4), ("data", "model"))
        with shlib.activity(mesh, {}):
            sh = param_shardings(params, mesh)
            params_s = jax.device_put(params, sh)
            opt_s = adamw_init(params_s)
            p_m, _, m_m = jax.jit(step)(params_s, opt_s, batch,
                                        jnp.asarray(0))
        assert abs(float(m_ref["loss"]) - float(m_m["loss"])) < 1e-2, (
            float(m_ref["loss"]), float(m_m["loss"]))
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_m)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=3e-2, atol=3e-2)
        print("sharded train step OK, loss", float(m_m["loss"]))
        """)


class TestDiloco:
    def test_inner_steps_have_no_pod_collectives(self):
        run_sub("""
        from repro.configs import get_config, reduced_config
        from repro.models import init_params
        from repro.train import TrainConfig, adamw_init, \\
            build_train_step, cosine_schedule
        from repro.parallel import diloco
        from repro.core.hlo_analysis import parse_collectives
        from jax.sharding import NamedSharding
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = reduced_config(get_config("qwen1.5-0.5b"), d_model=32,
                             n_layers=2, vocab=128)
        params = init_params(jax.random.PRNGKey(0), cfg)
        tc = TrainConfig(moe_strategy="dense")
        step = build_train_step(cfg, tc, cosine_schedule(1e-3, 2, 50))
        H, n_pods = 2, 2
        inner = diloco.build_inner_steps(step, H)
        pp = diloco.replicate_for_pods(params, n_pods)
        oo = diloco.replicate_for_pods(adamw_init(params), n_pods)
        batches = {
          "tokens": jax.random.randint(jax.random.PRNGKey(1),
                                       (n_pods, H, 4, 16), 0, 128),
          "labels": jax.random.randint(jax.random.PRNGKey(2),
                                       (n_pods, H, 4, 16), 0, 128)}
        shard = lambda t: jax.device_put(t, NamedSharding(mesh, P("pod")))
        pp = jax.tree.map(shard, pp)
        oo = jax.tree.map(shard, oo)
        batches = jax.tree.map(shard, batches)
        lowered = jax.jit(inner).lower(pp, oo, batches, jnp.asarray(0))
        compiled = lowered.compile()
        colls = parse_collectives(compiled.as_text())
        # inner steps must not communicate across pods: every collective
        # group must be a within-pod group (size <= 4 = data*model)
        for op in colls.ops:
            assert op.group_size <= 4, (op.kind, op.group_size, op.line)
        # run it + outer step
        pp2, oo2, losses = jax.jit(inner)(pp, oo, batches, jnp.asarray(0))
        outer = diloco.init_outer_state(params)
        pp3, outer2 = diloco.outer_step(pp2, outer, diloco.DilocoConfig(),
                                        mesh)
        # all pods equal after sync
        l0 = jax.tree.leaves(pp3)[0]
        np.testing.assert_allclose(np.asarray(l0[0], np.float32),
                                   np.asarray(l0[1], np.float32))
        print("diloco OK, inner losses", np.asarray(losses).ravel()[:2])
        """)


class TestElasticRestore:
    def test_checkpoint_rescales_onto_mesh(self, tmp_path):
        """Save unsharded (1-device layout), restore onto a (2,4) mesh with
        FSDP+TP shardings — the elastic-scaling path."""
        run_sub(f"""
        from repro.configs import get_config, reduced_config
        from repro.models import init_params
        from repro.train import checkpoint, adamw_init
        from repro.parallel.sharding import param_shardings
        from repro.parallel import sharding as shlib
        cfg = reduced_config(get_config("qwen1.5-0.5b"), d_model=64,
                             n_layers=2, vocab=256)
        params = init_params(jax.random.PRNGKey(0), cfg)
        checkpoint.save(r"{tmp_path}", 7, params)

        mesh = make_mesh((2, 4), ("data", "model"))
        with shlib.activity(mesh, {{}}):
            sh = param_shardings(params, mesh)
            restored = checkpoint.restore(r"{tmp_path}", 7, params,
                                          shardings=sh)
        for (a, b) in zip(jax.tree.leaves(params),
                          jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # restored leaves actually carry the mesh shardings
        leaf = jax.tree.leaves(restored)[0]
        assert len(leaf.sharding.device_set) >= 1
        some_sharded = any(
            l.sharding.num_devices if hasattr(l.sharding, 'num_devices')
            else len(l.sharding.device_set) > 1
            for l in jax.tree.leaves(restored))
        assert some_sharded
        print("elastic restore OK")
        """)


class TestCompressedPsum:
    def test_ef_converges_to_true_mean(self):
        run_sub("""
        from repro.compat import shard_map
        from repro.parallel.compression import compressed_psum_tree
        mesh = make_mesh((8,), ("pod",))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 1, 64))
        true_mean = jnp.mean(x, 0)   # (1, 64)

        def f(x_loc, e_loc):
            out, e_new = compressed_psum_tree({"w": x_loc}, {"w": e_loc},
                                              "pod")
            return out["w"], e_new["w"]

        sm = jax.jit(shard_map(
            f, mesh=mesh, in_specs=(P("pod"), P("pod")),
            out_specs=(P(None), P("pod"))))
        e = jnp.zeros((8, 1, 64))
        outs = []
        for i in range(30):
            out, e = sm(x, e)
            outs.append(out)
        one_shot = np.abs(np.asarray(outs[0] - true_mean)).max()
        # with error feedback, the *time average* converges to the truth
        avg = jnp.mean(jnp.stack(outs), 0)
        err_final = np.abs(np.asarray(avg - true_mean)).max()
        assert err_final <= one_shot + 1e-6
        assert err_final < 0.02, err_final
        print("compressed psum OK", err_final)
        """)
