"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the engine serves them
and the plain reference reads the same arrays, so the reference takes
nothing the program has made.  The tree follows the layout the program's
``init_params`` declares (read with ``jax.eval_shape``: shapes only, no
values); every leaf is filled here by a rule on its name.  A leaf this file
has no rule for stops the run: the layout changed and the benchmark (and
its reference) must learn it first.

Scales: every projection N(0, 1/fan_in), except W_q and W_k at twice that
variance, so attention scores spread with a standard deviation of 2 and
each query attends to a few keys, not to the average of all; norm scales
1 + N(0, 0.1^2) and biases N(0, 0.1^2), so that neither a norm scale nor a
bias can be dropped unseen; the embedding N(0, 1/d_model^2).  With a
larger embedding the input token's own vector dominates the last residual
stream, a tied head echoes it, and the served tokens barely depend on the
context the KV cache holds; with near-uniform attention they depend on it
no more.  Either way a broken cache would go unseen by the check.
"""

from __future__ import annotations

import math

import numpy as np


QK_GAIN = 2.0


def _fan_in(name: str, shape: tuple) -> int:
    if name in ("wq", "wk", "wv"):         # (..., d, heads, dh)
        return shape[-3]
    if name == "wo":                       # (..., heads, dh, d)
        return shape[-3] * shape[-2]
    if name in ("w_up", "w_gate", "w_down", "out_emb"):   # (..., in, out)
        return shape[-2]
    raise KeyError(name)


def key_for_seed(seed: int):
    """A JAX PRNG key from any whole number, 64 bits and beyond."""
    import jax
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def make_weights(cfg, seed: int, init_params):
    """The parameter tree for ``cfg`` in float32 (the type the program
    keeps and serves), on the default device.  ``init_params`` is the
    program's initializer, used only for its tree's shapes."""
    import jax
    import jax.numpy as jnp

    key = key_for_seed(seed)
    struct = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(struct)
    names = [str(getattr(path[-1], "key", path[-1])) for path, _ in flat]
    shapes = [tuple(leaf.shape) for _, leaf in flat]
    for n, (_, leaf) in zip(names, flat):
        if leaf.dtype != jnp.float32:
            raise TypeError(f"parameter {n} is {leaf.dtype}, not float32")
        if n not in ("scale", "bq", "bk", "bv", "tok_emb") \
                and not _is_matrix(n):
            raise KeyError(f"no weight rule for parameter {n!r}: the "
                           f"model layout changed")

    def fill(k):
        leaves = []
        for i, (n, shp) in enumerate(zip(names, shapes)):
            z = jax.random.normal(jax.random.fold_in(k, i), shp, jnp.float32)
            if n == "scale":
                leaves.append(1.0 + 0.1 * z)
            elif n in ("bq", "bk", "bv"):
                leaves.append(0.1 * z)
            elif n == "tok_emb":
                leaves.append(z / shp[-1])
            elif n in ("wq", "wk"):
                leaves.append(z * math.sqrt(QK_GAIN / _fan_in(n, shp)))
            else:
                leaves.append(z / math.sqrt(_fan_in(n, shp)))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(fill)(key)


def _is_matrix(name: str) -> bool:
    try:
        _fan_in(name, (1, 1, 1, 1))
        return True
    except KeyError:
        return False
