"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the engine serves them
and the plain reference reads the same arrays, so the reference takes
nothing the program has made.  The tree follows the layout the program's
``init_params`` declares (read with ``jax.eval_shape``: shapes only, no
values); every leaf is filled here by a rule on its name.  A leaf this file
has no rule for takes the rule of the same name in the reference module's
``WEIGHT_RULES`` (``{name: fn(key, shape) -> float32 array}``), so a model
with new leaves (a recurrent layer's gates and decays) brings their scales
with its own reference; a leaf neither has a rule for stops the run: the
layout changed and the benchmark (and its reference) must learn it first.

Scales: every projection N(0, 1/fan_in), except W_q and W_k at twice that
variance, so attention scores spread with a standard deviation of 2 and
each query attends to a few keys, not to the average of all; norm scales
1 + N(0, 0.1^2) and biases N(0, 0.1^2) (a norm's bias too), so that
neither a norm scale nor a bias can be dropped unseen; the embedding
N(0, 1/d_model^2).  With a larger embedding the input token's own vector
dominates the last residual stream, a tied head echoes it, and the
served tokens barely depend on the context the KV cache holds; with
near-uniform attention they depend on it no more.  Either way a broken
cache would go unseen by the check.

The MoE router (d_model, n_experts) is N(0, 1/d_model), the rule of a
projection.  It reads the normed residual stream, whose entries have a
mean square near 1, so each expert's logit is about N(0, 1), independent
of the other experts' and of the other tokens': every expert is as likely
as any other to be in a token's top k, so the load spreads over all of
them and no expert takes the batch.  Nor does a token's weight collapse
onto one expert: the top 8 of 64 such logits average 2.34 down to 1.18,
so the renormalised gates run from about 0.25 to 0.075, and every chosen
expert adds enough to the output that a wrong one shows in the logits.
A larger scale would make the gates near one-hot, a smaller one near
uniform over the k chosen.
"""

from __future__ import annotations

import math

import numpy as np


QK_GAIN = 2.0


OWN = ("scale", "bias", "bq", "bk", "bv", "tok_emb")   # besides matrices


def _fan_in(name: str, shape: tuple) -> int:
    if name in ("wq", "wk", "wv"):         # (..., d, heads, dh)
        return shape[-3]
    if name == "wo":                       # (..., heads, dh, d)
        return shape[-3] * shape[-2]
    if name in ("w_up", "w_gate", "w_down", "out_emb",    # (..., in, out)
                "router"):
        return shape[-2]
    raise KeyError(name)


def key_for_seed(seed: int):
    """A JAX PRNG key from any whole number, 64 bits and beyond."""
    import jax
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def make_weights(cfg, seed: int, init_params, reference=None):
    """The parameter tree for ``cfg`` in float32 (the type the program
    keeps and serves), on the default device.  ``init_params`` is the
    program's initializer, used only for its tree's shapes;
    ``reference`` the reference module, whose ``WEIGHT_RULES`` (if any)
    fill the leaves this file has no rule for."""
    import jax
    import jax.numpy as jnp

    key = key_for_seed(seed)
    rules = dict(getattr(reference, "WEIGHT_RULES", {}))
    struct = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(struct)
    names = [str(getattr(path[-1], "key", path[-1])) for path, _ in flat]
    shapes = [tuple(leaf.shape) for _, leaf in flat]
    for n, (_, leaf) in zip(names, flat):
        if leaf.dtype != jnp.float32:
            raise TypeError(f"parameter {n} is {leaf.dtype}, not float32")
        if not _has_rule(n) and n not in rules:
            raise KeyError(f"no weight rule for parameter {n!r}: the "
                           f"model layout changed")

    def fill(k):
        leaves = []
        for i, (n, shp) in enumerate(zip(names, shapes)):
            ki = jax.random.fold_in(k, i)
            if not _has_rule(n):
                leaves.append(jnp.asarray(rules[n](ki, shp), jnp.float32))
                continue
            z = jax.random.normal(ki, shp, jnp.float32)
            if n == "scale":
                leaves.append(1.0 + 0.1 * z)
            elif n in ("bq", "bk", "bv", "bias"):
                leaves.append(0.1 * z)
            elif n == "tok_emb":
                leaves.append(z / shp[-1])
            elif n in ("wq", "wk"):
                leaves.append(z * math.sqrt(QK_GAIN / _fan_in(n, shp)))
            else:
                leaves.append(z / math.sqrt(_fan_in(n, shp)))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(fill)(key)


def _has_rule(name: str) -> bool:
    """This file has a rule for the leaf ``name``."""
    try:
        _fan_in(name, (1, 1, 1, 1))
        return True
    except KeyError:
        return name in OWN
