"""Trace reduction: busy/idle union, kernel time by class, idle gaps put
down to the driver's spans."""
import json
import os

import pytest

import _paths
import reduce

MS = 1_000_000


def _trace():
    # window 0..100 ms; device ops on chip 0 (a loop op encloses two)
    device = {"/device:TPU:0": [
        (0 * MS, 30 * MS, "%while.5 = (s32[]) while(%x)"),
        (2 * MS, 10 * MS, "%ternary_gemm_pallas.3 = bf16[8,4096] custom-call()"),
        (12 * MS, 20 * MS, "%paged_decode_attention_pallas.9 = bf16[8,16,64]"
                           " custom-call(%ternary_gemm_pallas.3)"),
        (40 * MS, 50 * MS, "%fused_mlp_pallas.1 = bf16[8,1024] custom-call()"),
        (45 * MS, 55 * MS, "%copy.2 = bf16[1,9] copy(%y)"),
        (90 * MS, 130 * MS, "%fusion.7 = f32[] fusion()"),
        (-20 * MS, -10 * MS, "%fusion.8 = f32[] fusion()"),
    ]}
    host = [(0, 100 * MS, "bench.window"),
            (0, 56 * MS, "bench.engine_step"),
            (56 * MS, 80 * MS, "bench.idle_wait"),
            (80 * MS, 100 * MS, "bench.engine_step"),
            (81 * MS, 83 * MS, "bench.submit")]
    return device, host


def test_busy_is_the_union_inside_the_window():
    device, host = _trace()
    r = reduce.reduce_events(device, host, reduce.load_kernel_classes())
    assert r["window_s"] == pytest.approx(0.100)
    # 0-30 (loop) + 40-55 + 90-100 (clipped at the window's end)
    assert r["busy_s"] == pytest.approx(0.055)


def test_kernel_time_by_instruction_name():
    device, host = _trace()
    r = reduce.reduce_events(device, host, reduce.load_kernel_classes())
    # the attention op's operand names a GEMM: only its own name counts
    assert r["kernels"]["gemm"] == pytest.approx(0.008 + 0.010)
    assert r["kernels"]["attn"] == pytest.approx(0.008)
    fams = dict(r["device_ops"])
    assert "while" not in fams
    assert fams["copy"] == pytest.approx(0.010)
    assert fams["fusion"] == pytest.approx(0.010)


def test_idle_gaps_go_to_what_the_host_was_doing():
    device, host = _trace()
    r = reduce.reduce_events(device, host, reduce.load_kernel_classes())
    gaps = {n.split(" (")[0]: s for n, s in r["idle_gaps"]}
    # 30-40 falls in a step; 55-90 is one gap, its middle in the wait
    assert gaps["bench.idle_wait"] == pytest.approx(0.035, abs=1e-9)
    assert gaps["bench.engine_step"] == pytest.approx(0.010, abs=1e-9)
    assert sum(gaps.values()) == pytest.approx(0.100 - r["busy_s"])


def test_no_window_span_is_an_error():
    device, host = _trace()
    with pytest.raises(ValueError):
        reduce.reduce_events(device, host[1:], {})


def test_union_and_gaps():
    u = reduce.union([(5, 8), (0, 2), (1, 3), (8, 9)])
    assert u == [(0, 3), (5, 9)]
    assert reduce.gaps(u, 0, 12) == [(3, 5), (9, 12)]
    assert reduce.op_name("%copy.43 = bf16[1] copy(%a)") == "copy.43"
    assert reduce.op_family("%constant_dynamic-slice_fusion.25 = x") == \
        "constant_dynamic-slice_fusion"


def test_recorded_chip_trace():
    """A 0.15 s window of ternary-paper.rag-prefill recorded on a TPU v5e:
    the reduction gives what it gave on the chip."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "trace_v5e.json")) as f:
        fx = json.load(f)
    device = {k: [tuple(e) for e in v] for k, v in fx["device"].items()}
    host = [tuple(e) for e in fx["host"]]
    r = reduce.reduce_events(device, host, reduce.load_kernel_classes())
    assert r["window_s"] == pytest.approx(0.223430973)
    assert r["busy_s"] == pytest.approx(0.215650384)
    assert r["kernels"]["gemm"] == pytest.approx(0.004976094)
    assert r["kernels"]["attn"] == pytest.approx(0.142515746)
    fams = [n for n, _ in r["device_ops"]]
    assert fams[0] == "paged_decode_attention_pallas"
    assert {"ternary_gemm_pallas", "fused_mlp_pallas"} <= set(fams)
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"])
