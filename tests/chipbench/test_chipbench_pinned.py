"""The dense family reproduces, bit for bit, the numbers the benchmark read
before the family had a module of its own: each weight leaf the seed
draws, the work of three requests at ``deepseek-7b.l15``'s widths, and
the reference's gaps over one seeded sequence.  A change that moves any
of them moves every reading of both cells."""
from chipbench_testkit import TINY_WIDTHS, family
import hashlib

import jax
import numpy as np
import pytest

from chipbench import costs, reference, weights
from chipbench.spec import Benchmark

dense = family("dense")
SEED = 2 ** 31 + 5
TINY = dense.Dims(layers=2, d_model=TINY_WIDTHS["hidden_size"],
                  heads=4, kv_heads=4, head_dim=16, d_ff=128,
                  vocab=TINY_WIDTHS["vocab_size"])

# sha256 of each leaf's bf16 bytes, weights.draw at TINY, key_for(SEED, 0)
DRAW_SHA256 = {
    "blocks/b0/attn/wk":
        "3afa885afb91ecc1a8ed9b292ec38a794084a45410267af2b4056e7facf1955f",
    "blocks/b0/attn/wo":
        "aaf469dafb88a027d2c9dd944b6f707e751315d3f8f78f07745b1556ed346f7c",
    "blocks/b0/attn/wq":
        "cf7e7a1ee3a9826ad552097eb5a51247eeedc6874691e06a201a8ebd764ed2d4",
    "blocks/b0/attn/wv":
        "e244cf8675e7fc6a414dc30d9e01b89409286d0fe4417f8f86893849d50acba5",
    "blocks/b0/mlp/w_down":
        "373edb97fcbcc29101a0d62b3e6d9d7ed482a96119b47f93985ea304cf49046b",
    "blocks/b0/mlp/w_gate":
        "048dd1cf78232d717c779e54e06abd3802a6c07de2612513d17d74d9a653ea78",
    "blocks/b0/mlp/w_up":
        "932b7927ee379d1ef76fd78de11d08a2dc20a2a2a7dac80eb6c3cded0eeda637",
    "blocks/b0/norm1":
        "1c9f1b2dd5d02ee487f8e22d0c10831ba664381685f46bfc8bff7adeb484dc24",
    "blocks/b0/norm2":
        "4f3f440fd1567904a67b9a10179842a61fc1e1ceeb2a0d4fd0ae5b76499c4378",
    "embed":
        "e260c4adc88def425686186854a3aea5c0f46fa139f518bf04b0e83ef7e9ed13",
    "final_norm":
        "dac89ca4cab4a466c41262fcccf12dc7185fc3c1a6bbde9d941a7c485820e1c2",
    "lm_head":
        "e5bfbc787157adf0d4389f2d07889c7fae9755c361670dec4f2f344cdab8a880",
}
# request_work of (prompt, outputs) (1020, 129), (64, 8), (1792, 256) at
# deepseek-7b.l15, 256-token chunks, the v5e's peaks
WORK = {"prefill_flops": 17986747269120.0, "decode_flops": 2849501675520.0,
        "paged_decode": 0.1888307012454219,
        "paged_verify": 0.003800127765567766}
# reference.served_gaps over a 300-token prompt and 8 served tokens drawn
# from default_rng(SEED), weights as above
GAPS = {"served": [0.2963586747646332, 0.6226276755332947,
                   0.20765255391597748, 0.3777996599674225,
                   0.2801903188228607, 0.3495694398880005,
                   0.28660857677459717, 0.6307370066642761],
        "control": [0.01203891634941101, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0,
                    0.0]}


@pytest.fixture(scope="module")
def flat():
    tree = weights.draw(dense.layout(TINY), weights.key_for(SEED, 0))
    return weights.flatten(jax.device_get(tree))


@pytest.mark.parametrize("path", sorted(DRAW_SHA256))
def test_draw_is_bitwise_the_same(flat, path):
    assert set(flat) == set(DRAW_SHA256)
    got = hashlib.sha256(np.asarray(flat[path]).tobytes()).hexdigest()
    assert got == DRAW_SHA256[path]


def test_work_of_three_requests_is_the_same():
    d, _ = dense.resolve(Benchmark().config("deepseek-7b.l15"), "l15")
    w = costs.Work()
    for prompt, outputs in ((1020, 129), (64, 8), (1792, 256)):
        dense.request_work(d, prompt, outputs, 256,
                           costs.PEAKS["TPU v5 lite"], w)
    assert {"prefill_flops": w.prefill_flops,
            "decode_flops": w.decode_flops, **w.kernel} == WORK


def test_reference_gaps_are_the_same(flat):
    rng = np.random.default_rng(SEED)
    prompt = rng.integers(0, TINY.vocab, 300)
    served = rng.integers(0, TINY.vocab, 8)
    g = reference.served_gaps(flat, dense, TINY,
                              {"rms_norm_eps": 1e-6, "rope_theta": 10000.0},
                              [prompt], [served], control=True)
    assert {k: [float(x) for x in v[0]] for k, v in g.items()} == GAPS
