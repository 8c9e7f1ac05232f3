"""Operations and bytes of the dense family's paged kernels and model
step, counted by hand at small sizes; the table of peaks refuses an
unknown device."""
from chipbench_testkit import family
import pytest

from chipbench import costs

dense = family("dense")
D = dense.Dims(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4,
               d_ff=16, vocab=10)


def test_paged_decode_by_hand():
    # query at position 3 reads keys 0..3: QK and PV are 2*4 flops per
    # key per head each -> 2 heads * 4 keys * 16 = 128
    f, b = dense.paged_decode(D, 3)
    assert f == 2 * 4 * (2 * 4 + 2 * 4)
    # k and v of 4 keys, 1 kv head, 4 wide, 2 bytes; q and out 2x4x2
    assert b == 2 * 4 * 1 * 4 * 2 + 2 * 2 * 4 * 2


def test_paged_verify_by_hand():
    # 3 block queries at positions 5, 6, 7 see 6, 7, 8 keys = 21 keys
    f, b = dense.paged_verify(D, 5, 3)
    assert f == 21 * 2 * 16
    cache = 2 * 5 * 1 * 4 * 2          # 5 cached keys, k and v
    qo = 2 * 3 * 2 * 4 * 2             # 3 queries' q and out, 2 heads
    blk = 2 * 3 * 1 * 4 * 2            # the block's own k and v
    assert b == cache + qo + blk


def test_model_flops_by_hand():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, mlp 3 x 8x16 = 576 params
    assert D.layer_params == 64 + 32 + 32 + 64 + 384
    # token at pos 0 without the head: 2 flops per param per layer plus
    # attention over 1 key (4 * heads * head_dim)
    assert dense.token_flops(D, 0, False) == 2 * (2 * 576 + 4 * 2 * 4)
    assert dense.token_flops(D, 0, True) == \
        dense.token_flops(D, 0, False) + 2 * 8 * 10


def test_request_work_adds_chunks_prefill_and_decode():
    peak = {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e3}
    w = dense.request_work(D, prompt=5, outputs=3, chunk=2, peak=peak,
                           w=costs.Work())
    # chunks (0,2) (2,2) (4,1); decode at positions 5 and 6
    want_v = sum(D.layers * max(f / 1e3, b / 1e3) for f, b in
                 (dense.paged_verify(D, 0, 2), dense.paged_verify(D, 2, 2),
                  dense.paged_verify(D, 4, 1)))
    want_d = sum(D.layers * max(f / 1e3, b / 1e3) for f, b in
                 (dense.paged_decode(D, 5), dense.paged_decode(D, 6)))
    assert w.kernel["paged_verify"] == pytest.approx(want_v)
    assert w.kernel["paged_decode"] == pytest.approx(want_d)
    assert w.prefill_flops == pytest.approx(
        sum(dense.token_flops(D, t, t == 4) for t in range(5)))
    assert w.decode_flops == pytest.approx(
        dense.token_flops(D, 5, True) + dense.token_flops(D, 6, True))


def test_peaks_are_keyed_by_device_kind():
    assert costs.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        costs.peaks("cpu")
