"""The dense family's plain reference follows the published layer: at toy
widths and in float32 it gives the serving program's own full forward
pass, and its float8 control lands far from it."""
from chipbench_testkit import family
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference, weights

dense = family("dense")
D = dense.Dims(layers=2, d_model=64, heads=4, kv_heads=4, head_dim=16,
               d_ff=128, vocab=512)
EPS, THETA = 1e-6, 10000.0
RAW = {"rms_norm_eps": EPS, "rope_theta": THETA}


def _program_logits(tree, tokens):
    from repro.configs import get_arch, override
    from repro.models.model import build_model
    cfg = override(get_arch("deepseek-7b"), num_layers=D.layers,
                   d_model=D.d_model, num_heads=D.heads,
                   num_kv_heads=D.kv_heads, head_dim=D.head_dim,
                   d_ff=D.d_ff, vocab_size=D.vocab, norm_eps=EPS,
                   rope_theta=THETA, dtype="float32",
                   param_dtype="float32")
    m = build_model(cfg, cache_dtype=jnp.float32)
    weights.check_layout(tree, m.abstract())
    import repro.kernels as kernels
    mode = kernels.get_mode()
    kernels.set_mode("off")
    try:
        with jax.default_matmul_precision("highest"):
            lg, _ = m.forward(tree, jnp.asarray(tokens)[None])
    finally:
        kernels.set_mode(mode)
    return np.asarray(lg[0])


def _reference_logits(flat, tokens, fp8=False):
    x = dense.hidden(flat, D, RAW, [tokens], fp8=fp8)[0]
    rows = reference._pad(np.arange(len(tokens)))
    lg = reference.head(x, jnp.asarray(rows),
                        jnp.asarray(flat["final_norm"]),
                        jnp.asarray(flat["lm_head"]), eps=EPS, fp8=fp8)
    return np.asarray(lg)[:len(tokens)]


@pytest.fixture(scope="module")
def tree():
    return weights.draw(dense.layout(D), weights.key_for(2 ** 31 + 5, 0),
                        jnp.float32)


def test_reference_matches_the_program_forward(tree):
    toks = np.random.default_rng(0).integers(0, D.vocab, 200)
    want = _program_logits(tree, toks)
    got = _reference_logits(weights.flatten(jax.device_get(tree)), toks)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_served_gaps_are_zero_for_the_reference_own_tokens(tree):
    flat = weights.flatten(jax.device_get(tree))
    prompt = np.random.default_rng(1).integers(0, D.vocab, 100)
    # the reference's own greedy continuation, teacher-forced
    seq = list(prompt)
    for _ in range(6):
        seq.append(int(_reference_logits(flat, np.array(seq))[-1]
                       .argmax()))
    out = np.array(seq[len(prompt):])
    g = reference.served_gaps(flat, dense, D, RAW, [prompt], [out],
                              control=True)
    assert g["served"][0].shape == (6,)
    assert np.abs(g["served"][0]).max() <= 1e-5
    assert (g["control"][0] >= 0).all()


def test_float8_control_departs_from_the_reference(tree):
    flat = weights.flatten(jax.device_get(tree))
    toks = np.random.default_rng(2).integers(0, D.vocab, 250)
    f32 = _reference_logits(flat, toks)
    f8 = _reference_logits(flat, toks, fp8=True)
    rel = np.linalg.norm(f8 - f32) / np.linalg.norm(f32)
    assert 1e-2 < rel < 0.5
    assert (f8.argmax(-1) != f32.argmax(-1)).mean() > 0.05
