"""Operations and bytes from shapes, and the metric arithmetic on them."""
import pytest

import _paths  # noqa: F401
import reduce
import work

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
MISTRAL = work.Shapes(layers=40, d=5120, ff=14336, heads=32, kv_heads=8,
                      head_dim=128, vocab=131072)


def test_gemm_counts():
    ops, nbytes = work.gemm(8, 1024, 4096)
    assert ops == 2 * 8 * 1024 * 4096
    # 2-bit weights, f32 scales, bf16 activations in and out
    assert nbytes == 1024 * 4096 / 4 + 4 * 4096 + 2 * 8 * 1024 + 2 * 8 * 4096


def test_least_time_takes_the_binding_roof():
    ops, nbytes = work.gemm(8, 5120, 14336)          # decode: bandwidth
    assert work.least_time(ops, nbytes, PEAKS) == nbytes / 819e9
    ops, nbytes = work.gemm(8192, 5120, 14336)       # prefill: compute
    assert work.least_time(ops, nbytes, PEAKS) == ops / 197e12


def test_mistral_packed_weights_per_decode_step():
    """One decode step streams every packed weight once: about 2.9 GB."""
    w = work.StepWork(MISTRAL, PEAKS)
    w.forward(32, 32 * 384)
    weight_bytes = MISTRAL.matmul_params / 4
    assert 2.85e9 < weight_bytes < 2.95e9
    assert weight_bytes < w.gemm.bytes < weight_bytes * 1.05
    assert MISTRAL.matmul_params == pytest.approx(11.6e9, rel=0.01)


def test_paged_attention_counts_the_attended_cache():
    ops, nbytes = work.paged_attention(rows=2, attended=300, heads=32,
                                       kv_heads=8, head_dim=128)
    assert ops == 4 * 300 * 32 * 128
    assert nbytes == 2 * 300 * 8 * 128 * 2 + 2 * 2 * 32 * 128 * 2


def test_window_rows_read_their_slot_pages_once():
    """A chunk window's queries share their slot's pages: the keys are
    read once a row, while every query's attention is counted."""
    ops, nbytes = work.paged_attention(rows=128, attended=128 * 1000,
                                       heads=32, kv_heads=8, head_dim=128,
                                       keys=1064)
    assert ops == 4 * 128 * 1000 * 32 * 128
    assert nbytes == 2 * 1064 * 8 * 128 * 2 + 2 * 128 * 32 * 128 * 2
    assert work.paged_attention(2, 300, 32, 8, 128) == \
        work.paged_attention(2, 300, 32, 8, 128, keys=300)
    w = work.StepWork(MISTRAL, PEAKS)
    w.forward(128, 128 * 1000, keys=1064)
    assert w.attn.bytes == 40 * nbytes


def test_useful_work_leaves_pad_rows_out():
    w = work.StepWork(MISTRAL, PEAKS)
    w.useful(tokens=10, attended=1000)
    assert w.useful_ops == (2 * MISTRAL.matmul_params * 10
                            + 4 * 40 * 32 * 128 * 1000)


def _ctx(busy, window, kernels, gemm_least=0.0, useful=0.0):
    w = work.StepWork(MISTRAL, PEAKS)
    w.gemm.least_s = gemm_least
    w.useful_ops = useful
    return {"trace": {"busy_s": busy, "window_s": window,
                      "kernels": kernels}, "work": w, "peaks": PEAKS}


def test_shares():
    ctx = _ctx(8.0, 10.0, {"gemm": 4.0}, gemm_least=1.0, useful=197e12)
    assert reduce.idle_share(ctx) == pytest.approx(20.0)
    assert reduce.roofline(ctx, "gemm") == pytest.approx(25.0)
    assert reduce.mfu(ctx) == pytest.approx(100.0 / 8.0)
    # nothing to read: no number, never 0
    assert reduce.roofline(ctx, "attn") is None
    assert reduce.mfu(_ctx(0.0, 10.0, {})) is None
    assert reduce.mean_share([0.5, 1.0]) == 75.0
    assert reduce.median_ms([0.003, 0.001, 0.002]) == pytest.approx(2.0)
    assert reduce.median_ms([]) is None
