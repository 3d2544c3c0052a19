"""The trace reducer on hand-made events, and the FLOP and byte
arithmetic against numbers worked by hand for Mistral-7B."""

import json
import os

import pytest

from benchmark import costs, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1e-3
# one device: a loop op holding two kernels, a gap, then a fusion
OPS = {0: [("while.1", 0 * MS, 6 * MS), ("flash_fwd", 1 * MS, 2 * MS),
           ("flash_fwd", 3 * MS, 2 * MS), ("fusion.7", 10 * MS, 4 * MS),
           ("all-reduce.3", 14 * MS, 1 * MS)],
       1: [("fusion.7", 0 * MS, 10 * MS)]}
SPANS = [("trace_window", 0.0, 20 * MS), ("step", 0.0, 8 * MS),
         ("report", 6 * MS, 1 * MS), ("step", 9 * MS, 11 * MS)]


def test_busy_is_the_union_not_the_sum():
    assert trace.busy_seconds(OPS[0], (0.0, 20 * MS)) == pytest.approx(11 * MS)
    assert trace.busy_seconds(OPS[0], (2 * MS, 12 * MS)) == pytest.approx(
        6 * MS)      # cut to the window on both sides


def test_self_seconds_take_nested_operations_out():
    own = trace.self_seconds(OPS[0])
    assert own["while.1"] == pytest.approx(2 * MS)
    assert own["flash_fwd"] == pytest.approx(4 * MS)
    assert trace.named_seconds(OPS, ("flash_fwd",)) == pytest.approx(2 * MS)
    assert trace.named_calls(OPS, ("flash_fwd",)) == pytest.approx(1.0)
    assert trace.named_seconds(
        OPS, ("all-reduce", "all-gather")) == pytest.approx(0.5 * MS)


def test_idle_gaps_are_named_by_the_innermost_open_span():
    gaps = dict(trace.idle_gaps(OPS[0], SPANS, (0.0, 20 * MS)))
    # 6..10 ms: at 8 ms only the window is open; 15..20: in `step`
    assert gaps == {"trace_window": pytest.approx(4 * MS),
                    "step": pytest.approx(5 * MS)}
    assert trace.span_at(SPANS, 6.5 * MS) == "report"   # inside `step`
    assert trace.span_at(SPANS, 8.5 * MS) == "trace_window"
    assert trace.span_at(SPANS, 25 * MS) == "no_benchmark_span_open"
    tiny = trace.idle_gaps([("a", 0.0, 1 * MS), ("b", 1 * MS + 5e-6, 1 * MS)],
                           SPANS, (0.0, 2 * MS + 5e-6))
    assert tiny == [["gaps_under_20_us", pytest.approx(5e-6)]]


def test_summary_averages_over_devices_and_counts_whole_spans():
    s = trace.summary(OPS, SPANS, "trace_window")
    assert s["window_s"] == pytest.approx(20 * MS)
    assert s["busy_s"] == pytest.approx((11 + 10) / 2 * MS)
    assert trace.count_spans(SPANS, "step", s["window"]) == 2
    assert trace.count_spans(SPANS, "step", (0.0, 15 * MS)) == 1
    assert s["breakdown"]["device_ops"][0] == [
        "fusion.7", pytest.approx(7 * MS)]
    assert len(s["breakdown"]["idle_gaps"]) <= 10
    with pytest.raises(LookupError):
        trace.summary(OPS, SPANS, "no_such_window")


@pytest.fixture(scope="module")
def mistral():
    def load(name):
        with open(os.path.join(ROOT, "benchmark", "configs", name)) as f:
            return json.load(f)
    return load


def test_mistral_7b_by_hand(mistral):
    l3 = mistral("mistral-7b-v0.1-l3.json")
    # 4096*4096*2 + 4096*1024*2 + 3*4096*14336 + 2*4096
    assert costs.layer_params(l3) == 218_112_000
    assert costs.total_params(l3) == 3 * 218_112_000 + 2 * 131_072_000 + 4096
    assert costs.matmul_params(l3) == 3 * 218_103_808 + 131_072_000
    # 6 * 785.4 M + 3 layers * 3 * 2*2*4096*4096/2 = 4.712 G + 0.302 G
    assert costs.train_flops_per_token(l3, 4096) == pytest.approx(
        5.014e9, rel=1e-3)
    l12 = mistral("mistral-7b-v0.1-l12-serve.json")
    assert costs.matmul_params(l12) == pytest.approx(2.748e9, rel=1e-3)
    assert costs.total_params(l12) == pytest.approx(2.879e9, rel=1e-3)
    assert costs.train_flops_per_token(l12, 4096) == pytest.approx(
        17.7e9, rel=3e-3)


def test_flash_cost_and_roofline(mistral):
    l3 = mistral("mistral-7b-v0.1-l3.json")
    fwd = costs.flash_cost(l3, 1, 4096, backward=False)
    bwd = costs.flash_cost(l3, 1, 4096, backward=True)
    # two matmuls of 2*S*S*d/2 FLOPs; q and o at 32 heads, k and v at 8
    assert fwd["flops"] == 2 * 2 * 4096 * 4096 * 4096 / 2
    assert fwd["bytes"] == 2 * (2 * 4096 * 4096) + 2 * (2 * 4096 * 1024)
    assert bwd["flops"] == 2.5 * fwd["flops"]
    peaks = costs.chip_peaks("TPU v5 lite")
    least = costs.roofline_seconds(fwd, peaks)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(fwd["flops"] / 197e12)
    assert costs.roofline_seconds(fwd, peaks, chips=4)["seconds"] == \
        pytest.approx(least["seconds"] / 4)


def test_an_unknown_chip_is_an_error():
    assert costs.chip_peaks("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.chip_peaks("cpu")


def test_operations_are_named_from_their_hlo_line():
    from benchmark import xplane
    kernels = xplane.kernel_signatures()
    assert set(kernels.values()) == {"flash_fwd", "flash_bwd_dq",
                                     "flash_bwd_dkv"}
    layout = "{2,1,0:T(8,128)(2,1)}"
    arg = f"bf16[32,4096,128]{layout} %bitcast.1"
    tail = ('), custom_call_target="tpu_custom_call", '
            "operand_layout_constraints={bf16[32,4096,128]{2,1,0}}")
    fwd = (f"%branch_0_fun.12 = (bf16[32,4096,128]{layout}, f32[32,1,4096]) "
           f"custom-call({arg}, {arg}, {arg}{tail}")
    dq = (f"%branch_0_fun.16 = bf16[32,4096,128]{layout} "
          f"custom-call({', '.join([arg] * 6)}{tail}")
    dkv = (f"%branch_0_fun.17 = (bf16[32,4096,128]{layout}, bf16[32,4096,128]) "
           f"custom-call({', '.join([arg] * 6)}{tail}")
    assert xplane.op_name(fwd, kernels) == "flash_fwd"
    assert xplane.op_name(dq, kernels) == "flash_bwd_dq"
    assert xplane.op_name(dkv, kernels) == "flash_bwd_dkv"
    assert xplane.op_name(
        "%fusion.243 = (f32[4096]) fusion(f32[4] %p), kind=kOutput",
        kernels) == "fusion.243"
    other = fwd.replace("tpu_custom_call", "Sharding")
    assert xplane.op_name(other, kernels) == "branch_0_fun.12"
