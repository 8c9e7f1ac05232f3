"""The dense, all-attention decoder family: every layer is RMSNorm, causal
multi-head (or grouped-query) attention with rotate-half RoPE, RMSNorm and
a SwiGLU MLP, with no biases and an untied head.  DeepSeek LLM 7B
(arXiv:2401.02954; the Llama layer of its ``config.json``) is one.

A configuration file names its family (``"family": "dense"``) and the
harness reads only these names from the family's module:

    resolve(raw, name) -> (dims, arch)  widths from the file's published
                                        keys, checked against the program
    layout(dims)                        every weight leaf
    hidden(flat, dims, raw, seqs, fp8)  the plain float32 reference
    KERNELS                             the kernels whose roofline is read
    request_work(dims, ...)             model FLOPs and kernels' least time

and ``dims.layers``, ``dims.d_model``, ``dims.vocab`` and
``dims.kv_bytes_per_token``.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import BLOCK, HI, _mm, _rms, _rope
from chipbench.weights import NORM, NORMAL

# trace names of the paged attention kernels, by the key a reader uses
KERNELS = {"paged_decode": "paged_decode_attention",
           "paged_verify": "paged_verify_attention"}


@dataclass(frozen=True)
class Dims:
    """The widths a cost function needs (a dense, all-attention model)."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    kv_bytes: int = 2           # bf16 cache
    act_bytes: int = 2          # bf16 activations

    @classmethod
    def of(cls, c: dict) -> "Dims":
        """From a configuration file's published keys."""
        h = c["num_attention_heads"]
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=h, kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim", c["hidden_size"] // h),
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"])

    @property
    def layer_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        return (d * (self.heads + 2 * self.kv_heads) * hd
                + self.heads * hd * d + 3 * d * self.d_ff)

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * self.kv_bytes


def resolve(raw: dict, name: str):
    """``(dims, arch)``: the file's widths, held against the program's
    ``raw["arch"]``, and that arch as served (the file's depth, norm eps,
    RoPE base and weight type; bf16 activations)."""
    from repro.configs import get_arch, override
    d = Dims.of(raw)
    base = get_arch(raw["arch"])
    widths = {"d_model": d.d_model, "num_heads": d.heads,
              "num_kv_heads": d.kv_heads, "head_dim": d.head_dim,
              "d_ff": d.d_ff, "vocab_size": d.vocab}
    bad = {k: (getattr(base, k), v) for k, v in widths.items()
           if getattr(base, k) != v}
    if bad:
        raise ValueError(f"{name}: widths differ from the program's "
                         f"{raw['arch']!r} (program, file): {bad}")
    if raw.get("tie_word_embeddings") or raw.get("attention_bias"):
        raise ValueError(f"{name}: the served layer has no biases and an "
                         "untied head")
    arch = override(base, num_layers=d.layers, norm_eps=raw["rms_norm_eps"],
                    rope_theta=float(raw["rope_theta"]),
                    param_dtype=raw["torch_dtype"], dtype="bfloat16",
                    **widths)
    return d, arch


# ---------------------------------------------------------------- weights
def layout(d: Dims) -> dict:
    """``{path: (shape, kind, std)}`` of every leaf, in the program's tree
    (stacked layers under ``blocks/b0``)."""
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


# -------------------------------------------------------------- reference
@functools.partial(jax.jit, static_argnames=("eps", "theta", "fp8", "qb"))
def layer(x, w, *, eps, theta, fp8, qb=BLOCK):
    """One decoder layer over one sequence; x: (T, D) float32."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    T = x.shape[0]
    h = _rms(x, w["norm1"], eps)
    q = _rope(_mm("td,dhk->thk", h, w["wq"], fp8), theta)
    k = _rope(_mm("td,dhk->thk", h, w["wk"], fp8), theta)
    v = _mm("td,dhk->thk", h, w["wv"], fp8)
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    scale = 1.0 / np.sqrt(q.shape[-1])
    outs = []
    for s in range(0, T, qb):               # query blocks bound the scores
        qs = q[s:s + qb]
        sc = jnp.einsum("qhk,thk->hqt", qs, k, precision=HI) * scale
        mask = (s + jnp.arange(qs.shape[0]))[:, None] >= jnp.arange(T)
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqt,thk->qhk", p, v, precision=HI))
    a = jnp.concatenate(outs, 0)
    x = x + _mm("thk,hkd->td", a, w["wo"], fp8)
    h = _rms(x, w["norm2"], eps)
    g = _mm("td,df->tf", h, w["w_gate"], fp8)
    u = _mm("td,df->tf", h, w["w_up"], fp8)
    return x + _mm("tf,fd->td", jax.nn.silu(g) * u, w["w_down"], fp8)


_LAYER_KEYS = {"norm1": "blocks/b0/norm1", "norm2": "blocks/b0/norm2",
               "wq": "blocks/b0/attn/wq", "wk": "blocks/b0/attn/wk",
               "wv": "blocks/b0/attn/wv", "wo": "blocks/b0/attn/wo",
               "w_gate": "blocks/b0/mlp/w_gate",
               "w_up": "blocks/b0/mlp/w_up",
               "w_down": "blocks/b0/mlp/w_down"}


def hidden(flat: dict, d: Dims, raw: dict, seqs: list[np.ndarray],
           fp8: bool = False) -> list:
    """Last-layer hidden states of each token sequence, on the device,
    one layer at a time over every sequence.  ``flat`` maps leaf paths to
    host arrays (``weights.flatten``)."""
    eps, theta = float(raw["rms_norm_eps"]), float(raw["rope_theta"])
    emb = np.asarray(flat["embed"])
    xs = []
    for toks in seqs:
        T = -(-len(toks) // BLOCK) * BLOCK
        x = np.zeros((T, d.d_model), np.float32)
        x[:len(toks)] = emb[toks].astype(np.float32)
        xs.append(jax.device_put(x))
    for li in range(d.layers):
        w = {k: jax.device_put(np.asarray(flat[p][li]))
             for k, p in _LAYER_KEYS.items()}
        xs = [layer(x, w, eps=eps, theta=theta, fp8=fp8) for x in xs]
        del w
    return xs


# ------------------------------------------------------------------ costs
def paged_decode(d: Dims, pos: int) -> tuple[float, float]:
    """(flops, bytes) of one row of ``paged_decode_attention`` in one
    layer: the query at position ``pos`` attends keys ``0..pos``."""
    n = pos + 1
    flops = 4.0 * d.heads * d.head_dim * n
    nbytes = (2.0 * n * d.kv_heads * d.head_dim * d.kv_bytes
              + 2.0 * d.heads * d.head_dim * d.act_bytes)       # q + out
    return flops, nbytes


def paged_verify(d: Dims, pos: int, n: int) -> tuple[float, float]:
    """(flops, bytes) of one row of ``paged_verify_attention`` in one
    layer: ``n`` valid block queries at ``pos..pos+n-1`` read the ``pos``
    cached keys and the block's own keys under a causal mask."""
    keys = n * pos + n * (n + 1) / 2.0
    flops = 4.0 * d.heads * d.head_dim * keys
    nbytes = (2.0 * pos * d.kv_heads * d.head_dim * d.kv_bytes
              + 2.0 * n * d.heads * d.head_dim * d.act_bytes     # q + out
              + 2.0 * n * d.kv_heads * d.head_dim * d.act_bytes)  # block k/v
    return flops, nbytes


def token_flops(d: Dims, pos: int, logits: bool) -> float:
    """Model FLOPs of one token at ``pos``: the layers' matmuls, causal
    attention over ``pos + 1`` keys, and the head when it is needed."""
    f = d.layers * (2.0 * d.layer_params
                    + 4.0 * d.heads * d.head_dim * (pos + 1))
    return f + (2.0 * d.d_model * d.vocab if logits else 0.0)


def request_work(d: Dims, prompt: int, outputs: int, chunk: int,
                 peak: dict, w):
    """Add one request (``prompt`` tokens, ``outputs`` served) to ``w``
    (a ``costs.Work``).

    Prefill runs in ``chunk``-token blocks through the verify kernel;
    the first output token comes from the last prompt position; each
    later one from a decode step at positions ``prompt .. prompt +
    outputs - 2``.  A kernel's least time is summed call by call, each
    the larger of its operations and its bytes over the peaks."""
    pf, pb = peak["bf16_flops"], peak["hbm_bytes_per_s"]
    kern = w.kernel
    for start in range(0, prompt, chunk):
        n = min(chunk, prompt - start)
        f, b = paged_verify(d, start, n)
        kern["paged_verify"] = (kern.get("paged_verify", 0.0)
                                + d.layers * max(f / pf, b / pb))
    # sum of token_flops(d, t, t == prompt - 1) over t < prompt
    w.prefill_flops += (prompt * d.layers * 2.0 * d.layer_params
                        + d.layers * 4.0 * d.heads * d.head_dim
                        * prompt * (prompt + 1) / 2.0
                        + 2.0 * d.d_model * d.vocab)
    for pos in range(prompt, prompt + outputs - 1):
        f, b = paged_decode(d, pos)
        kern["paged_decode"] = (kern.get("paged_decode", 0.0)
                                + d.layers * max(f / pf, b / pb))
        w.decode_flops += token_flops(d, pos, True)
    return w
