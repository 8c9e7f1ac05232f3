"""A family of layers for the CPU tests alone: attention as in
``families/dense.py`` and, in every layer, a mixture of experts (a softmax
router over all experts, the top-k probabilities renormalised to sum to
one, SwiGLU experts), served by the program's one-chip MoE path.  The
tests copy this file into a temporary benchmark tree as
``families/moe_probe.py``: a family enters the benchmark by adding a file.

The configuration's keys follow Qwen3-MoE's ``config.json``
(``num_experts``, ``num_experts_per_tok``, ``moe_intermediate_size``,
``norm_topk_prob``).
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

KERNELS = {"paged_decode": "paged_decode_attention",
           "paged_verify": "paged_verify_attention",
           "gmm": "gmm"}


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    top_k: int
    d_expert: int
    vocab: int

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * 2

    @property
    def active_params(self) -> int:
        """Per layer: attention, the router, and the top-k experts."""
        d, hd = self.d_model, self.head_dim
        return (d * (2 * self.heads + 2 * self.kv_heads) * hd
                + d * self.experts + self.top_k * 3 * d * self.d_expert)


def resolve(raw: dict, name: str):
    from repro.configs import get_arch, override
    d = Dims(layers=raw["num_hidden_layers"], d_model=raw["hidden_size"],
             heads=raw["num_attention_heads"],
             kv_heads=raw["num_key_value_heads"], head_dim=raw["head_dim"],
             experts=raw["num_experts"], top_k=raw["num_experts_per_tok"],
             d_expert=raw["moe_intermediate_size"], vocab=raw["vocab_size"])
    base = get_arch(raw["arch"])
    got = (base.d_model, base.num_heads, base.num_kv_heads, base.head_dim,
           base.moe.num_experts, base.moe.top_k, base.moe.d_ff_expert,
           base.vocab_size)
    want = (d.d_model, d.heads, d.kv_heads, d.head_dim, d.experts, d.top_k,
            d.d_expert, d.vocab)
    if got != want or not raw["norm_topk_prob"]:
        raise ValueError(f"{name}: widths differ from the program's "
                         f"{raw['arch']!r} (program, file): {got}, {want}")
    arch = override(base, num_layers=d.layers, norm_eps=raw["rms_norm_eps"],
                    rope_theta=float(raw["rope_theta"]),
                    param_dtype=raw["torch_dtype"], dtype="bfloat16")
    return d, arch


def layout(d: Dims) -> dict:
    L, D, H, K, hd, E, F, V = (d.layers, d.d_model, d.heads, d.kv_heads,
                               d.head_dim, d.experts, d.d_expert, d.vocab)
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
        "blocks/b0/moe/w_router": ((L, D, E), NORMAL, s_in),
        "blocks/b0/moe/w_gate": ((L, E, D, F), NORMAL, s_in),
        "blocks/b0/moe/w_up": ((L, E, D, F), NORMAL, s_in),
        "blocks/b0/moe/w_down": ((L, E, F, D), NORMAL, s_f),
    }


@functools.partial(jax.jit, static_argnames=("eps", "theta", "top_k", "fp8"))
def layer(x, w, *, eps, theta, top_k, fp8):
    """One layer over one sequence; x: (T, D) float32."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    T = x.shape[0]
    h = _rms(x, w["norm1"], eps)
    q = _rope(_mm("td,dhk->thk", h, w["wq"], fp8), theta)
    k = _rope(_mm("td,dhk->thk", h, w["wk"], fp8), theta)
    v = _mm("td,dhk->thk", h, w["wv"], fp8)
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    sc = jnp.einsum("qhk,thk->hqt", q, k, precision=HI) / np.sqrt(
        q.shape[-1])
    mask = jnp.arange(T)[:, None] >= jnp.arange(T)
    p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    a = jnp.einsum("hqt,thk->qhk", p, v, precision=HI)
    x = x + _mm("thk,hkd->td", a, w["wo"], fp8)
    h = _rms(x, w["norm2"], eps)
    probs = jax.nn.softmax(
        jnp.einsum("td,de->te", h, w["w_router"], precision=HI), -1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    comb = jnp.zeros_like(probs).at[jnp.arange(T)[:, None], top_i].set(top_p)
    g = _mm("td,edf->tef", h, w["w_gate"], fp8)
    u = _mm("td,edf->tef", h, w["w_up"], fp8)
    y = _mm("tef,efd->ted", jax.nn.silu(g) * u, w["w_down"], fp8)
    return x + jnp.einsum("ted,te->td", y, comb, precision=HI)


def hidden(flat: dict, d: Dims, raw: dict, seqs: list, fp8: bool = False):
    eps, theta = float(raw["rms_norm_eps"]), float(raw["rope_theta"])
    emb = np.asarray(flat["embed"])
    xs = []
    for toks in seqs:
        x = np.zeros((-(-len(toks) // BLOCK) * BLOCK, d.d_model), np.float32)
        x[:len(toks)] = emb[toks].astype(np.float32)
        xs.append(jax.device_put(x))
    for li in range(d.layers):
        w = {p.rsplit("/", 1)[1]: jax.device_put(np.asarray(v[li]))
             for p, v in flat.items() if p.startswith("blocks/b0/")}
        xs = [layer(x, w, eps=eps, theta=theta, top_k=d.top_k, fp8=fp8)
              for x in xs]
    return xs


def request_work(d: Dims, prompt: int, outputs: int, chunk: int,
                 peak: dict, w):
    """Model FLOPs of the active parameters (attention's own left out) and
    the attention kernels' least time by their bytes alone."""
    per_tok = 2.0 * d.layers * d.active_params
    head = 2.0 * d.d_model * d.vocab
    w.prefill_flops += prompt * per_tok + head
    w.decode_flops += (outputs - 1) * (per_tok + head)
    kv = 2.0 * d.layers * d.kv_heads * d.head_dim * 2
    for key, toks in (("paged_verify", sum(range(0, prompt, chunk))),
                      ("paged_decode", sum(range(prompt,
                                                 prompt + outputs - 1)))):
        w.kernel[key] = (w.kernel.get(key, 0.0)
                         + kv * toks / peak["hbm_bytes_per_s"])
    return w
