"""Compiles for a described TPU v5e, no chip attached.

The TPU compiler refuses what interpret mode accepts: tiles that overrun
the scoped VMEM a kernel is granted, slices not aligned to the tiling.
These tests compile the Pallas kernels of the serving path at
qwen1.5-0.5b's published shapes, one whole chunked-prefill step and one
whole decode step, for the chip and check that the kernel is in the
compiled program and that decode keeps the KV cache where it lies.  The
topology is described inside a module fixture only (one process at a
time may load the TPU library), and every such compile lives in this
one file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import TPU_V5E
from repro.kernels import ops
from repro.kernels.autotune import _vmem_budget, autotune_matmul
from repro.models import init_params
from repro.models import transformer as tfm
from repro.serving import WidthVariantCompileCache
from repro.serving.compile_cache import decode_state_struct

D, F = 1024, 2816          # qwen1.5-0.5b d_model, d_ff
KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without
    # one: keep the persistent compilation cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m,n,k", [
    (16, F, D), (16, D, F),            # decode, 16 slots: up/gate, down
    (256, F, D), (512, D, F),          # prefill chunk and its tail bucket
    (8192, F, D), (2048, D, F),        # long whole-prompt prefill
])
def test_matmul_autotuned_compiles(one_chip, m, n, k):
    text = _compiled_text(
        lambda x, w: ops.matmul(x, w, hw=TPU_V5E, force="pallas"),
        _sds((m, k), one_chip), _sds((k, n), one_chip))
    assert KERNEL in text
    assert "%matmul_tiled" in text        # the kernel's name in the HLO


@pytest.mark.parametrize("s", [512, 2048])
def test_flash_attention_compiles(one_chip, s):
    q = _sds((1, s, 16, 64), one_chip)
    text = _compiled_text(
        lambda q, k, v: ops.flash_attention(q, k, v, hw=TPU_V5E,
                                            force="pallas"), q, q, q)
    assert KERNEL in text
    assert "%flash_attention" in text


def test_autotuned_bf16_cube_fits_the_compiler(one_chip):
    """Regression: with the VMEM filter at the chip's 128 MiB the
    autotuner chose (1024, 1024, 1024) blocks here, which the compiler
    refuses (out of scoped VMEM).  Its choice must compile."""
    cfg = autotune_matmul(TPU_V5E, 2048, 2048, 2048, dtype_bits=16)
    assert cfg.vmem_bytes <= _vmem_budget(TPU_V5E)
    bm, bn, bk = cfg.blocks
    text = _compiled_text(
        lambda x, w: ops.matmul(x, w, block_m=bm, block_n=bn, block_k=bk,
                                force="pallas"),
        _sds((2048, 2048), one_chip), _sds((2048, 2048), one_chip))
    assert KERNEL in text


def test_full_width_chunk_step_compiles(one_chip):
    """The serving engine's prefill-chunk executable at the published
    widths, as ``warm_compile`` builds it, with the MLP on the kernel."""
    cfg = get_config("qwen1.5-0.5b")
    place = lambda t: jax.tree.map(lambda s: _sds(s.shape, one_chip,
                                                  s.dtype), t)
    params = place(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    state = place(decode_state_struct(cfg, 1, 2048))
    cache = WidthVariantCompileCache(cfg, hw=TPU_V5E)
    toks = _sds((1, 512), one_chip, jnp.int32)
    pos = _sds((), one_chip, jnp.int32)
    with ops.kernel_context(force="pallas"):
        assert cache.precompile("chunk", cache.full_key, (1, 512),
                                (params, toks, pos, state))
    exe = cache.executable("chunk", cache.full_key, (1, 512))
    text = exe.as_text()
    assert KERNEL in text and "%matmul_tiled" in text
    # the executable is named for its kind, as the trace's modules read
    assert text.startswith("HloModule jit_chunk,")


def _stack_ops(text, stack):
    """(opcode, shape) of every instruction outside a fusion whose shape
    is the stacked KV cache or one layer's slab of it: what the program
    moves through memory, not what a fused loop computes."""
    fused = set(re.findall(r" fusion\(.*calls=(%[\w.-]+)", text))
    slabs = {stack, stack[1:], (1,) + stack[1:]}
    out = []
    for comp in re.split(r"\n(?=\S)", text):
        if comp.split(" ", 1)[0] in fused:
            continue
        for dims, op in re.findall(r"= \w+\[([\d,]+)\]\S* ([\w-]+)\(",
                                   comp):
            shape = tuple(int(d) for d in dims.split(","))
            if shape in slabs:
                out.append((op, shape))
    return out


def test_full_width_decode_step_keeps_the_cache_layout(one_chip):
    """The engine's decode executable at the published widths, 16 slots
    of 2048 rows, ragged positions.  The v5e lays the cache out with the
    sequence axis minor; decode reads each layer's slab where it lies
    and writes the new rows with dynamic-update-slices, so no layer's
    slab is copied into another layout and back, and nothing broadcasts
    a fresh stack.  Undonated, the output stack is one copy of the
    input; with the state donated there is no copy at all."""
    cfg = get_config("qwen1.5-0.5b")
    b, max_len = 16, 2048
    place = lambda t: jax.tree.map(lambda s: _sds(s.shape, one_chip,
                                                  s.dtype), t)
    params = place(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    state = place(decode_state_struct(cfg, b, max_len))
    toks = _sds((b,), one_chip, jnp.int32)
    stack = state["stack"]["u0"]["k"].shape
    cache = WidthVariantCompileCache(cfg, hw=TPU_V5E)
    with ops.kernel_context(force="pallas"):
        assert cache.precompile("decode", cache.full_key, (b,),
                                (params, toks, toks, state))
        donated = jax.jit(
            lambda p, t, pos, st: tfm.decode_step(p, cfg, t, pos, st),
            donate_argnums=(3,)).lower(params, toks, toks, state)
        donated = donated.compile().as_text()
    text = cache.executable("decode", cache.full_key, (b,)).as_text()
    assert text.startswith("HloModule jit_decode,") and KERNEL in text
    moved = [o for o in _stack_ops(text, stack)
             if o[0] in ("copy", "broadcast", "scatter", "transpose")]
    assert moved == [("copy", stack)] * 2          # the output, k and v
    assert not [o for o in _stack_ops(donated, stack)
                if o[0] in ("copy", "broadcast", "scatter", "transpose")]
    assert ("dynamic-update-slice", stack) in _stack_ops(donated, stack)
