"""Weights of a served model, drawn on the device from the run's seed.

The benchmark makes the weights itself, so the reference can read the very
same arrays without taking anything the program made.  The tree follows
the layout the serving program takes for the configuration's family
(``layout`` of ``families/<family>.py``); ``check_layout`` holds it
against the program's own abstract parameters, so a layout change fails
loudly.

A family's scales follow fan-in (``1/sqrt(fan_in)`` for projections, 0.02
for the embedding and the head), which keeps activations and logits at
the sizes of a trained model; norm weights are drawn around 1 (``NORM``)
so that a norm that drops its weight does not go unnoticed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NORM, NORMAL = "norm", "normal"


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


def draw(lay: dict, key, dtype=jnp.bfloat16) -> dict:
    """All weights of ``lay`` (``{path: (shape, kind, std)}``, a family's
    ``layout``) in one jitted call on the default device: one key per
    leaf, split from ``key`` in the order of the sorted paths."""

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
