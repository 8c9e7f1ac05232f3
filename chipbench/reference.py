"""Plain reference of the served model, and the lower-precision control.

The numerics every family's reference shares (``_mm``, ``_rms``,
``_rope``, the head and the gap); a family's ``hidden`` in
``families/<family>.py`` writes its layers from the published equations
with them, in ``jax.numpy`` alone: no import of the program, no cache, no
kernels, no batching of requests.

It reads the benchmark's own weights (``weights.py``) and runs in
float32 at ``Precision.HIGHEST``, one layer at a time over every sequence
(only one layer's weights are on the device at once), so it fits beside
nothing else once the program's state is freed.

``fp8=True`` is the control: every matmul of the projections, the MLP and
the head takes its operands through float8 (e4m3, one scale per tensor),
the step below bfloat16 that would tempt a later change.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
BLOCK = 512                     # sequences pad to a multiple of this
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fp8(x):
    """Round ``x`` through float8 e4m3 with one scale for the tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(eq, a, b, fp8):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate-half RoPE; x: (T, H, hd) at positions 0..T-1."""
    T, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv      # (T, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


ROWS = 256                      # compared positions pad to this many


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def head(x, rows, norm, w, *, eps, fp8):
    """Final norm and logits at ``rows`` ((ROWS,) int32) of one
    sequence's hidden states x: (T, D) float32 -> (ROWS, V) float32."""
    h = _rms(x[rows], norm.astype(jnp.float32), eps)
    return _mm("nd,dv->nv", h, w.astype(jnp.float32), fp8)


@jax.jit
def _gap(lg, toks):
    """How far below the row's best logit the logit of ``toks`` lies."""
    return jnp.max(lg, -1) - jnp.take_along_axis(lg, toks[:, None], -1)[:, 0]


def _pad(a: np.ndarray) -> np.ndarray:
    if len(a) > ROWS:
        raise ValueError(f"{len(a)} compared positions; at most {ROWS}")
    return np.concatenate([a, np.repeat(a[-1:], ROWS - len(a))]).astype(
        np.int32)


def served_gaps(flat: dict, family, d, raw: dict,
                prompts: list[np.ndarray], outputs: list[np.ndarray],
                control: bool = False) -> dict:
    """For each request, the gap by which each served token's reference
    logit lies below the reference's best at that position.

    Request i is its prompt followed by its served tokens; the logits at
    positions ``len(prompt) - 1 + j`` predict served token j.  With
    ``control`` the same is read for the token the float8 control puts
    first at each position (teacher-forced on the same tokens), under
    key ``"control"``.  ``family`` is the configuration's family module,
    ``d`` its dims and ``raw`` the configuration file."""
    eps = float(raw["rms_norm_eps"])
    seqs = [np.concatenate([p, o[:-1]]).astype(np.int32)
            for p, o in zip(prompts, outputs)]
    rows = [_pad(np.arange(len(p) - 1, len(p) - 1 + len(o)))
            for p, o in zip(prompts, outputs)]
    n = [len(o) for o in outputs]
    norm = jax.device_put(flat["final_norm"])
    w = jax.device_put(flat["lm_head"])
    top = []
    if control:
        xs = family.hidden(flat, d, raw, seqs, fp8=True)
        top = [jnp.argmax(head(x, r, norm, w, eps=eps, fp8=True), -1)
               .astype(jnp.int32) for x, r in zip(xs, rows)]
        del xs
    xs = family.hidden(flat, d, raw, seqs)
    out = {"served": [], "control": []}
    for i, (x, r) in enumerate(zip(xs, rows)):
        lg = head(x, r, norm, w, eps=eps, fp8=False)
        out["served"].append(np.asarray(
            _gap(lg, jnp.asarray(_pad(outputs[i]))))[:n[i]])
        if control:
            out["control"].append(np.asarray(_gap(lg, top[i]))[:n[i]])
    return out
