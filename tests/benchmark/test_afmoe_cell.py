"""What the Trinity cell adds to the yardstick: its driver end to end at
a tiny size on the CPU with the facts its readers count from, each new
reader on hand-made events, the cost functions against counts by hand,
the kernels' signatures, and the reference's weights."""

import importlib.util
import json
import math
import os

import numpy as np
import pytest

from benchmark import (check, costs, costs_layers, moe_route, run,
                       traced_forwards, xplane)
from benchmark import program_spans as ps
from benchmark.drivers import serve_openloop, serve_prefill
from benchmark.reference import afmoe as ref

ROOT = run.ROOT
CELL, LONG = "serve_trinity_large_l5_ep8_long", "serve_mistral7b_l12_long"
TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 128,
        "moe_intermediate_size": 64, "sliding_window": 16, "num_experts": 2,
        "vocab_size": 96, "torch_dtype": "float32"}
PEAKS = costs.chip_peaks("TPU v5 lite")
MS = 1e-3


def cell_config():
    return run.load_job(ROOT, CELL)["config"]


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_under_test", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# ---- the configuration and the reference's weights ------------------------

def test_the_configuration_keeps_every_published_width():
    c = cell_config()
    assert (c["hidden_size"], c["num_attention_heads"], c["head_dim"],
            c["num_key_value_heads"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["route_scale"], c["sliding_window"], c["rms_norm_eps"]) == (
        3072, 48, 128, 8, 12288, 3072, 4, 2.448, 4096, 1e-5)
    assert c["num_experts"] * c["expert_parallel"]["size"] == 256
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"] == 200192
    assert c["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["configs"]
                     if e["name"] == "trinity-large-preview-l5-ep8")
    assert sorted(entry["reduced"]) == sorted(c["published"]) == sorted([
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"])
    assert "8 chips" in c["stands_for"] and c["deployment"]


def test_the_share_is_4_32_billion_parameters():
    c = cell_config()
    sz = ref.Sizes.from_config(c)
    table = ref.leaf_table(sz)
    held = sum(math.prod(shape) for _p, shape, _k in table)
    assert held == costs_layers.total_params(c) == 4_321_903_872
    by_name = {}
    for path, shape, _k in table:
        if path[0] == "blocks" and path[1] == 1:
            by_name[path[2]] = math.prod(shape)
    assert by_name["experts_wg"] == 32 * 3072 * 3072
    assert by_name["router"] == 3072 * 256
    assert by_name["wgate"] == by_name["wq"] == 3072 * 6144


def test_reference_weights_from_the_seed_in_the_stated_type():
    import jax
    sz = ref.Sizes.from_config(dict(cell_config(), **TINY))
    a = ref.make_weights(ref.seed_key(2**31 + 3), sz)
    b = ref.make_weights(ref.seed_key(2**31 + 3), sz)
    c = ref.make_weights(ref.seed_key(3), sz)
    for x, y, z in zip(*(jax.tree.leaves(t) for t in (a, b, c))):
        assert x.dtype == np.float32
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert x.ndim == 1 and x.shape[0] != 16 or not np.allclose(x, z)
    bias = np.asarray(a["blocks"][1]["router_bias"])
    assert bias.shape == (16,) and 0 < np.abs(bias).max() < 0.1
    bf16 = ref.make_weights(ref.seed_key(3), sz._replace(dtype="bfloat16"))
    assert all(leaf.dtype == "bfloat16" for leaf in jax.tree.leaves(bf16))


# ---- the driver -----------------------------------------------------------

def tiny_job():
    job = run.load_job(ROOT, CELL)
    job["config"].update(TINY)
    job["traffic"].update(
        rate_per_s=10.0, pad_to=[64, 128], check_requests=6,
        prompt_tokens={"median": 56, "sigma": 0.4, "min": 24, "max": 128})
    return job


@pytest.fixture(scope="module")
def outcome():
    import time
    job = tiny_job()
    return job, serve_prefill.run({
        "cell": job["cell"], "config": job["config"],
        "traffic": job["traffic"], "seed": 2**31 + 5, "seconds": 2.0,
        "trace": False, "process_start": time.perf_counter(),
        "extras": ["int8"]})


def test_cell_end_to_end_at_a_tiny_size(outcome):
    job, out = outcome
    verdict = check.judge(out["numbers"], job["limits"], out["attempted"],
                          out["failed"])
    assert verdict["correct"], verdict["checks"]
    assert out["attempted"] == 20 and out["failed"] == 0
    assert out["end_to_end"]["serve_ttft_p50_ms"] > 0
    assert out["end_to_end"]["setup_s"] > 0
    # float32 at this size: the program is the reference to round-off,
    # the int8 control is not
    assert out["numbers"]["logit_err"] < 1e-4
    assert out["extras"]["int8"]["logit_err"] > 100 * out["numbers"][
        "logit_err"]


def test_facts_hold_every_forward_of_the_window(outcome):
    job, out = outcome
    facts = out["facts"]
    plan = serve_openloop.schedule(
        dict(job["traffic"], vocab=96), 2**31 + 5, 2.0)
    forwards = facts["forwards"]
    assert sorted(f["id"] for f in forwards) == list(range(20))
    for f in forwards:
        assert f["real"] == plan["lengths"][f["id"]]
        assert f["padded"] == min(n for n in (64, 128) if n >= f["real"])
        # four routed layers, two of sixteen experts held, top 4
        assert 0 <= f["rows_held"] <= 4 * 4 * f["padded"]
        assert 0 <= f["experts_hit"] <= 4 * 2
    assert facts["prompt_tokens_answered"] == sum(f["real"] for f in forwards)
    assert len(facts["late_ms"]) == 20 and facts["window_s"] == 2.0


def test_the_program_recorded_one_route_a_forward(outcome):
    _job, out = outcome
    ctx = {"facts": out["facts"]}
    counts = moe_route.window_counts(ctx)
    assert len(counts) == 20
    by_rows = sorted(c["rows_held"] for c in counts)
    assert by_rows == sorted(f["rows_held"] for f in out["facts"]["forwards"])
    assert all(c["layers"] == 4 and c["rows_total"] in (
        4 * 4 * 64, 4 * 4 * 128) for c in counts)
    share = reader("moe_rows_held_share")(ctx)
    assert 2.0 < share < 40.0          # two of sixteen: 12.5 % if even
    assert reader("moe_load_max_over_mean")(ctx) >= 1.0


def test_schedule_of_the_long_cells():
    for cell, lo, hi, median in ((CELL, 4608, 16384, 8192),
                                 (LONG, 1024, 4096, 2048)):
        traffic = dict(run.load_job(ROOT, cell)["traffic"], vocab=25024)
        plan = serve_openloop.schedule(traffic, 7, 50.0)
        lengths = plan["lengths"]
        assert lengths.min() >= lo and lengths.max() <= hi
        assert 0.9 * median <= np.median(lengths) <= 1.1 * median
        assert max(traffic["pad_to"]) == hi
        assert len(plan["due"]) == round(traffic["rate_per_s"] * 50.0)
        assert traffic["schedule_seed"] == 20260930
        assert max(max(t) for t in plan["tokens"]) < 25024
    # every Trinity prompt outgrows the window: no sliding layer is full
    assert lo > cell_config()["sliding_window"] or cell == LONG


def test_a_program_without_the_pattern_fails_before_anything_starts(
        monkeypatch):
    import ray_tpu.models
    monkeypatch.delattr(ray_tpu.models, "config_from_hf")
    with pytest.raises(ImportError):
        serve_prefill.run({"config": {}, "traffic": {}, "seed": 1,
                           "seconds": 1.0})


# ---- the cost functions, by hand ------------------------------------------

def test_visible_pairs_by_hand():
    assert costs_layers.visible_pairs(4, None) == 10
    assert costs_layers.visible_pairs(4, 8) == 10
    assert costs_layers.visible_pairs(4, 2) == 1 + 2 + 2 + 2
    s, w = 8192, 4096
    assert costs_layers.visible_pairs(s, w) == sum(
        min(i + 1, w) for i in range(s))
    assert abs(costs_layers.visible_pairs(s, w) - (s * w - w * w / 2)) <= w


def test_costs_by_hand():
    c = cell_config()
    assert costs_layers.attention_params(c) == 3 * 3072 * 6144 + \
        2 * 3072 * 1024 == 62_914_560
    assert costs_layers.expert_params(c) == 28_311_552
    assert costs_layers.dense_mlp_params(c) == 113_246_208
    assert costs_layers.router_width(c) == 256
    assert costs_layers.layer_kinds(c) == [(4096, False)] + \
        [(4096, True)] * 3 + [(None, True)]
    flash = costs_layers.flash_cost(c, 8192, 4096)
    assert flash["flops"] == 4 * costs_layers.visible_pairs(8192, 4096) * 6144
    assert flash["bytes"] == 2 * 8192 * (2 * 6144 + 2 * 1024)
    full = costs_layers.flash_cost(c, 8192, None)
    assert full["flops"] == pytest.approx(0.82e12, rel=0.01)
    assert flash["flops"] == pytest.approx(0.62e12, rel=0.01)
    gmm = costs_layers.gmm_cost(c, 4096, 32)
    assert gmm["flops"] == pytest.approx(0.23e12, rel=0.01)
    assert gmm["bytes"] == pytest.approx(1.81e9 + 0.05e9, rel=0.01)
    assert costs.roofline_seconds(gmm, PEAKS)["bound"] == "memory"
    # a forward: the matrices at every token, the pairs, the rows, the head
    s, rows = 8192, 16000
    by_hand = (2 * s * (62_914_560 + 113_246_208)
               + 4 * 2 * s * (62_914_560 + 3072 * 256 + 28_311_552)
               + 4 * flash["flops"] + full["flops"]
               + 2 * rows * 28_311_552 + 2 * 3072 * 25024)
    assert costs_layers.forward_flops(c, s, rows) == pytest.approx(by_hand)


# ---- the readers on hand-made events --------------------------------------

def traced_ctx():
    """Three forwards in the profile; the first began before the window
    and is left out. Each whole forward ran five flash calls and twelve
    grouped matmuls."""
    def ops_of(start):
        return ([("flash_fwd", start + i * MS, 0.5 * MS) for i in range(5)]
                + [(f"moe_gmm.{3 + i}", start + (6 + i) * MS, 0.25 * MS)
                   for i in range(12)]
                + [("fusion.1", start + 20 * MS, 5 * MS)])
    spans = [("trace_window", 0.1, 1.0), ("forward", 0.05, 0.1),
             ("forward", 0.2, 0.1), ("forward", 0.5, 0.1)]
    ops = ops_of(0.06) + ops_of(0.2) + ops_of(0.5)
    forwards = [{"id": i, "padded": p, "real": p - 100, "rows_held": r,
                 "experts_hit": 128}
                for i, (p, r) in enumerate([(6144, 10000), (6144, 12000),
                                            (8192, 16000), (16384, 33000)])]
    window = (0.1, 1.1)
    inside = {0: [e for e in ops if window[0] <= e[1] < window[1]]}
    return {"job": {"config": cell_config()}, "chips": 1, "peaks": PEAKS,
            "facts": {"forwards": forwards, "window_s": 50.0,
                      "late_ms": np.zeros(4)},
            "trace": {"spans": spans, "window": window, "inside": inside,
                      "busy_s": 0.05, "window_s": 1.0}}


def test_whole_forwards_are_the_last_the_replica_ran():
    whole = traced_forwards.whole_forwards(traced_ctx())
    assert [f["facts"]["id"] for f in whole] == [2, 3]
    assert all(len(f["ops"]) == 18 for f in whole)
    assert traced_forwards.kernel_seconds(whole[0], "flash_fwd") == \
        pytest.approx(2.5 * MS)
    ctx = traced_ctx()
    del ctx["facts"]["forwards"]        # serve_openloop gives none
    assert traced_forwards.whole_forwards(ctx) is None
    assert reader("flash_fwd_roofline.serve")(ctx) is None
    assert reader("moe_gmm_roofline.serve")(ctx) is None
    assert reader("serve_mfu_pct.afmoe")(ctx) is None


def test_flash_roofline_counts_each_layer_at_the_forwards_length():
    ctx = traced_ctx()
    c = ctx["job"]["config"]
    least = sum(
        max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
        for s in (8192, 16384)
        for cost in costs_layers.flash_cost_of_forward(c, s))
    assert reader("flash_fwd_roofline.serve")(ctx) == pytest.approx(
        100 * least / (2 * 2.5 * MS))


def test_gmm_roofline_and_device_share():
    ctx = traced_ctx()
    c = ctx["job"]["config"]
    least = sum(costs.roofline_seconds(
        costs_layers.gmm_cost(c, rows, 128), PEAKS)["seconds"]
        for rows in (16000, 33000))
    assert reader("moe_gmm_roofline.serve")(ctx) == pytest.approx(
        100 * least / (2 * 12 * 0.25 * MS))
    # two whole forwards and the tail of none: 24 calls of 0.25 ms
    assert reader("moe_gmm_device_pct")(ctx) == pytest.approx(
        100 * 24 * 0.25 * MS / 0.05)
    ctx["trace"]["inside"] = {0: [("fusion.1", 0.2, 0.01)]}
    assert reader("moe_gmm_device_pct")(ctx) is None


def test_mfu_counts_real_tokens_of_answered_prompts():
    ctx = traced_ctx()
    c = ctx["job"]["config"]
    flops = sum(costs_layers.forward_flops(
        c, f["real"], f["rows_held"] * f["real"] // f["padded"])
        for f in ctx["facts"]["forwards"])
    assert reader("serve_mfu_pct.afmoe")(ctx) == pytest.approx(
        100 * flops / (50.0 * 197e12))


def test_route_readers_on_hand_made_records(monkeypatch):
    def span(counts, name=moe_route.ROUTE):
        return ps.Span(name, 0, 1, None, None, 1, 1, counts)
    records = [span({"layers": 4, "rows_total": 1000, "rows_held": r,
                     "load_max": m, "load_mean": 2.0})
               for r, m in ((50, 9), (100, 4), (125, 3), (150, 5))]
    records.insert(2, span({"bytes": 1}, "serve.request"))
    monkeypatch.setattr(ps, "recorded", lambda: records)
    ctx = {"facts": {"late_ms": np.zeros(3)}}     # the first is warm-up
    assert reader("moe_rows_held_share")(ctx) == pytest.approx(12.5)
    assert reader("moe_load_max_over_mean")(ctx) == pytest.approx(2.0)
    monkeypatch.setattr(ps, "recorded", lambda: None)   # an older program
    assert reader("moe_rows_held_share")(ctx) is None
    assert reader("moe_load_max_over_mean")(ctx) is None


def test_the_grouped_matmul_is_known_by_its_instruction_name():
    """`test_trace_and_costs.py` pins `kernels/` to the three flash
    signatures, so the grouped matmul is not among them: the program
    names its call's HLO instruction `moe_gmm`, `op_name` passes an
    unknown call's instruction name through, and the readers take
    `moe_gmm.<n>`."""
    kernels = xplane.kernel_signatures()
    assert sorted(kernels.values()) == ["flash_bwd_dkv", "flash_bwd_dq",
                                        "flash_fwd"]
    hlo = ("%moe_gmm.9 = bf16[65536,3072]{1,0} custom-call(s32[287]{0} "
           "%a, s32[287]{0} %b, s32[32]{0} %c, s32[32]{0} %d, s32[1]{0} %e, "
           "/*index=5*/bf16[65536,3072]{1,0} %f, bf16[32,3072,3072]{2,1,0} "
           "%g), custom_call_target=\"tpu_custom_call\"")
    assert xplane.op_name(hlo, kernels) == "moe_gmm.9"
    forward = {"ops": [("moe_gmm.9", 0.0, 1.0), ("moe_gmm.12", 1.0, 2.0),
                       ("moe_gmm_other", 3.0, 4.0), ("flash_fwd", 4.0, 8.0)]}
    assert traced_forwards.kernel_seconds(forward, "moe_gmm") == 3.0
    assert traced_forwards.kernel_seconds(forward, "flash_fwd") == 8.0
