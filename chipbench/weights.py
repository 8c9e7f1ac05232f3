"""Weights of a served model, drawn on the device from the run's seed.

The benchmark makes the weights itself, so the reference can read the very
same arrays without taking anything the program made.  The tree follows
the layout the serving program takes for a dense all-attention model
(stacked layers under ``blocks/b0``); ``check_layout`` holds it against the
program's own abstract parameters, so a layout change fails loudly.

Scales follow fan-in (``1/sqrt(fan_in)`` for projections, 0.02 for the
embedding and the head), which keeps activations and logits at the sizes
of a trained model; norm weights are drawn around 1 so that a norm that
drops its weight does not go unnoticed.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.costs import Dims

NORM, NORMAL = "norm", "normal"


def layout(d: Dims) -> dict:
    """``{path: (shape, kind, std)}`` of every leaf."""
    L, D, H, K, hd, F, V = (d.layers, d.d_model, d.heads, d.kv_heads,
                            d.head_dim, d.d_ff, d.vocab)
    s_in, s_o, s_f = 1 / math.sqrt(D), 1 / math.sqrt(H * hd), 1 / math.sqrt(F)
    return {
        "embed": ((V, D), NORMAL, 0.02),
        "final_norm": ((D,), NORM, 0.1),
        "lm_head": ((D, V), NORMAL, 0.02),
        "blocks/b0/norm1": ((L, D), NORM, 0.1),
        "blocks/b0/norm2": ((L, D), NORM, 0.1),
        "blocks/b0/attn/wq": ((L, D, H, hd), NORMAL, s_in),
        "blocks/b0/attn/wk": ((L, D, K, hd), NORMAL, s_in),
        "blocks/b0/attn/wv": ((L, D, K, hd), NORMAL, s_in),
        "blocks/b0/attn/wo": ((L, H, hd, D), NORMAL, s_o),
        "blocks/b0/mlp/w_gate": ((L, D, F), NORMAL, s_in),
        "blocks/b0/mlp/w_up": ((L, D, F), NORMAL, s_in),
        "blocks/b0/mlp/w_down": ((L, F, D), NORMAL, s_f),
    }


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flatten(v, f"{prefix}{k}/"))
        else:
            out[prefix + k] = v
    return out


def key_for(seed: int, model: int):
    return jax.random.fold_in(jax.random.key(seed), model)


def draw(d: Dims, key, dtype=jnp.bfloat16) -> dict:
    """All weights in one jitted call on the default device."""
    lay = layout(d)

    def gen(key):
        keys = jax.random.split(key, len(lay))
        flat = {}
        for k, (path, (shape, kind, std)) in zip(keys, sorted(lay.items())):
            z = jax.random.normal(k, shape, jnp.float32) * std
            flat[path] = (1.0 + z if kind == NORM else z).astype(dtype)
        return _nest(flat)
    return jax.jit(gen)(key)


def check_layout(tree: dict, abstract: dict) -> None:
    """Fail unless ``tree`` has exactly the program's leaves, shapes and
    dtypes (``abstract`` is the program's ``ShapeDtypeStruct`` tree)."""
    got = {p: (tuple(x.shape), str(x.dtype)) for p, x in
           flatten(tree).items()}
    want = {p: (tuple(x.shape), str(x.dtype)) for p, x in
            flatten(abstract).items()}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise ValueError(f"weight layout differs from the program's: {diff}")
