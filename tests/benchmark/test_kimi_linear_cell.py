"""What the Kimi-Linear cell adds to the yardstick: its configuration
and the reference's weights, its driver end to end at a tiny size on
the CPU, how it fails at once on a program that cannot read the family,
the cost functions against counts by hand, each new reader on hand-made
events and records, and the kernels' names in a trace. Entries of the
manifest are looked up by name, never by position."""

import importlib.util
import json
import math
import os
import time

import numpy as np
import pytest

from benchmark import check, costs, costs_kimi, run, traced_forwards, xplane
from benchmark import program_spans as ps
from benchmark.drivers import serve_openloop, serve_prefill_family
from benchmark.reference import kimi_linear as ref

ROOT = run.ROOT
CELL = "serve_kimi_linear_l13_ep8_long"
CONFIG = "kimi-linear-48b-a3b-l13-ep8"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = ("https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
          "blob/main/config.json")
# heads of the published sizes, everything else tiny: a dense kda layer,
# two routed kda layers, a routed mla layer; 8 of 64 experts held
TINY = {"hidden_size": 64, "intermediate_size": 128, "vocab_size": 40,
        "num_attention_heads": 2, "num_key_value_heads": 2,
        "num_hidden_layers": 4, "kv_lora_rank": 32,
        "moe_intermediate_size": 32, "num_experts": 8,
        "torch_dtype": "float32",
        "linear_attn_config": {
            "full_attn_layers": [4], "kda_layers": [1, 2, 3],
            "head_dim": 128, "num_heads": 2, "short_conv_kernel_size": 4}}
PEAKS = costs.chip_peaks("TPU v5 lite")
MS = 1e-3
NEW = ["serve_mfu_pct.kimi", "kda_attn_roofline.serve",
       "mla_attn_roofline.serve", "delta_mixers_device_pct",
       "mla_quadratic_flops_share"]
MOE = ["moe_gmm_roofline.serve", "moe_gmm_device_pct",
       "moe_load_max_over_mean", "moe_rows_held_share"]
# lists that older files under tests/benchmark/ pin to their own cells
# (test_setup_spans.py, test_rows_computed.py): the cell is not in them
PINNED = ["setup_before_init_s", "setup_runtime_s", "setup_trace_lower_s",
          "setup_compile_s", "setup_compile_miss_s", "window_compiles",
          "moe_rows_computed_share"]


def cell_config():
    return run.load_job(ROOT, CELL)["config"]


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_under_test", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# ---- the configuration and the reference's weights ------------------------

def test_the_configuration_keeps_every_published_key_but_the_cut():
    c = cell_config()
    entry = next(e for e in manifest()["configs"] if e["name"] == CONFIG)
    if os.path.exists(CATALOG):     # the catalog, where it is installed
        with open(CATALOG) as f:
            row = next(json.loads(line) for line in f
                       if '"Kimi-Linear-48B-A3B-Instruct"' in line)
        changed = {k for k, v in row["config"].items() if c.get(k, "") != v}
        assert changed == set(entry["reduced"])
        assert entry["source"] == row["source_url"]
        # inside the nested group only the two lists of layers change
        assert {k for k, v in row["config"]["linear_attn_config"].items()
                if c["linear_attn_config"][k] != v} == {
            "kda_layers", "full_attn_layers"}
    assert entry["source"] == SOURCE
    assert entry["reduced"] == ["num_hidden_layers", "linear_attn_config",
                                "num_experts", "vocab_size"]
    assert sorted(c["published"]) == sorted(entry["reduced"])
    assert (c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_attention_heads"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["num_experts_per_token"],
            c["routed_scaling_factor"], c["rms_norm_eps"]) == (
        2304, 9216, 1024, 32, 512, 128, 64, 128, 8, 2.446, 1e-5)
    linear = c["linear_attn_config"]
    assert (linear["head_dim"], linear["num_heads"],
            linear["short_conv_kernel_size"]) == (128, 32, 4)
    assert linear["kda_layers"] == [1, 2, 3, 5, 6, 7, 9, 10, 11, 13]
    assert linear["full_attn_layers"] == [4, 8, 12]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        13, 32, 20480)
    assert c["expert_parallel"] == {"size": 8, "rank": 0}
    assert c["published"]["num_hidden_layers"] == 27
    assert c["published"]["num_experts"] == 256
    assert c["published"]["vocab_size"] == 163840
    assert c["architecture"] == c["model_type"] == "kimi_linear"
    assert {"kda", "mla", "mlp", "head", "weights", "arithmetic", "head_dim",
            "kda_scales", "torch_dtype"} <= set(c["assumed"])
    assert "experts 0-31 of 256" in c["stands_for"]
    assert "v5e-16" in c["deployment"] and "6.90 GB" in c["deployment"]
    assert "3,450,587,968" in c["deployment"]


def test_the_program_reads_the_configuration_file():
    from ray_tpu.models import LayerSpec, config_from_hf
    from ray_tpu.models.transformer import KdaSizes, MlaSizes
    cfg = config_from_hf(cell_config(), 32768)
    kinds = [(s.mixer, s.experts) for s in cfg.layers]
    assert kinds == [("kda", False)] + [
        ("mla" if i % 4 == 0 else "kda", True) for i in range(2, 14)]
    assert all(s == LayerSpec(rope=False, mixer=s.mixer, experts=s.experts)
               for s in cfg.layers)
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab_size) == (2304, 32, 128, 9216, 20480)
    assert cfg.kda == KdaSizes(4, 128)
    assert cfg.mla == MlaSizes(512, 128, 64, 128)
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_top_k,
            cfg.d_ff_expert, cfg.n_shared_experts, cfg.route_scale) == (
        256, (0, 32), 8, 1024, 1, 2.446)
    assert str(cfg.dtype) == "bfloat16" and cfg.rms_norm_eps == 1e-5


def test_the_share_is_3_450_587_968_parameters():
    """ISSUE 40's table, part by part."""
    c = cell_config()
    table = ref.leaf_table(ref.Sizes.from_config(c))
    held = sum(math.prod(shape) for _p, shape, _k in table)
    assert held == costs_kimi.total_params(c) == 3_450_587_968
    assert 2 * held == pytest.approx(6.90e9, rel=1e-3)      # bfloat16
    assert costs_kimi.kda_params(c) == 39_518_368
    assert costs_kimi.mla_params(c) == 29_114_880
    by_layer = [sum(math.prod(shape) for p, shape, _k in table
                    if p[:2] == ("blocks", i)) for i in range(13)]
    expert, router, dense = 7_077_888, 590_080, 63_700_992
    assert by_layer[0] == 39_518_368 + dense + 2 * 2304
    assert by_layer[1] == 39_518_368 + 33 * expert + router + 2 * 2304
    assert by_layer[3] == 29_114_880 + 33 * expert + router + 2 * 2304
    assert sorted(set(by_layer)) == sorted({by_layer[0], by_layer[1],
                                            by_layer[3]})
    assert by_layer.count(by_layer[1]) == 9
    assert by_layer.count(by_layer[3]) == 3
    assert held - sum(by_layer) == 2 * 20480 * 2304 + 2304
    # the model whole: 49.1 B, which no v5e chip holds
    whole = dict(c, num_hidden_layers=27, num_experts=256,
                 vocab_size=163840, expert_parallel={"size": 1, "rank": 0},
                 linear_attn_config=dict(
                     c["linear_attn_config"],
                     **c["published"]["linear_attn_config"]))
    assert costs_kimi.total_params(whole) == pytest.approx(49.1e9, rel=2e-3)


def test_reference_weights_from_the_seed_in_the_stated_type():
    import jax
    sz = ref.Sizes.from_config(dict(cell_config(), **TINY))
    a = ref.make_weights(ref.seed_key(2**31 + 3), sz)
    b = ref.make_weights(ref.seed_key(2**31 + 3), sz)
    c = ref.make_weights(ref.seed_key(3), sz)
    for x, y, z in zip(*(jax.tree.leaves(t) for t in (a, b, c))):
        assert x.dtype == np.float32
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert np.all(np.asarray(x) == 1) or not np.allclose(x, z)
    kda, mla = a["blocks"][0], a["blocks"][3]
    assert kda["conv_k"].shape == (2, 128, 4)
    assert kda["wf_b"].shape == kda["wg_b"].shape == (128, 2, 128)
    assert mla["wq"].shape == (64, 2, 192)
    assert mla["wkv_a"].shape == (64, 32 + 64)
    assert mla["wkv_b"].shape == (32, 2, 256) and "wk" not in mla
    assert a["blocks"][1]["experts_wg"].shape == (8, 64, 32)
    assert a["blocks"][1]["router"].shape == (64, 64)
    # the assumed initialisation of the decays
    rate = np.exp(np.asarray(kda["a_log"]))
    step = np.log1p(np.exp(np.asarray(kda["dt_bias"], np.float64)))
    assert 1 <= rate.min() and rate.max() <= 16
    assert 0.999e-3 <= step.min() and step.max() <= 0.1001
    assert 0.4 < float(np.std(kda["conv_q"])) < 0.6
    bf16 = ref.make_weights(ref.seed_key(3), sz._replace(dtype="bfloat16"))
    assert all(leaf.dtype == "bfloat16" for leaf in jax.tree.leaves(bf16))
    broken = dict(cell_config())
    broken["linear_attn_config"] = dict(broken["linear_attn_config"],
                                        full_attn_layers=[4, 8])
    with pytest.raises(ValueError, match="does not name every layer"):
        ref.Sizes.from_config(broken)


# ---- the driver -----------------------------------------------------------

def tiny_job():
    job = run.load_job(ROOT, CELL)
    job["config"].update(TINY)
    job["traffic"].update(
        rate_per_s=6.0, pad_to=[64, 128], check_requests=3,
        prompt_tokens={"median": 56, "sigma": 0.4, "min": 40, "max": 128})
    return job


@pytest.fixture(scope="module")
def outcome():
    job = tiny_job()
    return job, serve_prefill_family.run({
        "cell": job["cell"], "config": job["config"],
        "traffic": job["traffic"], "seed": 2**31 + 5, "seconds": 2.0,
        "trace": False, "process_start": time.perf_counter(),
        "extras": ["int8"]})


def test_cell_end_to_end_at_a_tiny_size(outcome):
    job, out = outcome
    verdict = check.judge(out["numbers"], job["limits"], out["attempted"],
                          out["failed"])
    assert verdict["correct"], verdict["checks"]
    assert out["attempted"] == 12 and out["failed"] == 0
    assert out["end_to_end"]["serve_ttft_p50_ms"] > 0
    assert out["end_to_end"]["setup_s"] > 0
    # float32 at this size: the program is the reference to round-off,
    # the int8 control is not
    assert out["numbers"]["logit_err"] < 2e-4
    assert out["extras"]["int8"]["logit_err"] > 50 * out["numbers"][
        "logit_err"]


def test_run_cell_builds_the_result_line(outcome, monkeypatch):
    """`run.run_cell` with the CPU stand-in for the device: the untraced
    line holds the cell's end-to-end metrics and its checks."""
    job, out = outcome
    monkeypatch.setattr(serve_prefill_family, "run", lambda _job: out)
    result = run.run_cell(job, {"platform": "cpu", "kind": "cpu",
                                "count": 1}, 2**31 + 5, 2.0, False)
    assert result["correct"] and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_ttft_p50_ms", "setup_s"}
    assert set(result["checks"]) == {"token_gap", "logit_err",
                                     "failed_operations"}
    assert result["device"]["memory_peak_bytes"] == out["memory_peak_bytes"]


def test_facts_hold_every_forward_and_its_routed_rows(outcome):
    job, out = outcome
    plan = serve_openloop.schedule(
        dict(job["traffic"], vocab=40), 2**31 + 5, 2.0)
    forwards = out["facts"]["forwards"]
    assert sorted(f["id"] for f in forwards) == list(range(12))
    for f in forwards:
        assert f["real"] == plan["lengths"][f["id"]]
        assert f["padded"] == min(n for n in (64, 128) if n >= f["real"])
        # three routed layers, top-8 of 64 with 8 held: an eighth of the
        # pairs on average
        assert 0 < f["rows_held"] < 3 * f["padded"] * 8
        assert 0 < f["experts_hit"] <= 3 * 8


def test_the_program_recorded_a_plan_for_each_shape(outcome):
    _job, out = outcome
    plans = {s.counts["tokens"]: s.counts for s in ps.recorded()
             if s.name == "model.delta.plan" and s.counts
             and s.counts["kda_layers"] == 3}
    assert {64, 128} <= set(plans)
    config = dict(cell_config(), **TINY)
    for tokens in (64, 128):
        plan = plans[tokens]
        assert (plan["mla_layers"], plan["chunk"], plan["chunks"]) == (
            1, 64, tokens // 64)
        assert plan["kda_flops"] == 3 * costs_kimi.kda_cost(
            config, tokens)["flops"]
        assert plan["mla_pair_flops"] == costs_kimi.mla_cost(
            config, tokens)["flops"]
        assert plan["state_bytes"] == 3 * (2 * 128 * 128 * 4
                                           + 3 * 3 * 2 * 128 * 4)
        assert plan["latent_bytes"] == tokens * (32 + 64) * 4
        assert plan["kv_bytes"] == tokens * 2 * 320 * 4
    share = reader("mla_quadratic_flops_share")({"facts": out["facts"]})
    by_length = {n: 100 * p["mla_pair_flops"]
                 / (p["mla_pair_flops"] + p["kda_flops"])
                 for n, p in plans.items()}
    assert share == pytest.approx(float(np.median(
        [by_length[f["padded"]] for f in out["facts"]["forwards"]])))
    assert by_length[64] < by_length[128] < 100


def test_schedule_of_the_cell():
    traffic = dict(run.load_job(ROOT, CELL)["traffic"], vocab=20480)
    plan = serve_openloop.schedule(traffic, 2**31 + 7, 50.0)
    lengths = plan["lengths"]
    assert lengths.min() >= 8704 and lengths.max() <= 32768
    assert 0.85 * 12288 <= np.median(lengths) <= 1.15 * 12288
    assert traffic["pad_to"] == [12288, 16384, 24576, 32768]
    assert len(plan["due"]) == round(traffic["rate_per_s"] * 50.0) >= 15
    assert traffic["check_requests"] == 4 and traffic["top_k"] == 8
    assert traffic["check_batch"] == 1 and traffic["client_threads"] == 8
    assert traffic["trace_seconds"] == 20.0
    ids = np.concatenate([np.asarray(t) for t in plan["tokens"]])
    assert ids.min() == 0 and ids.max() == 20479    # the vocabulary slice
    # SALA's lengths on purpose, under a schedule seed of its own
    sala = run.load_job(ROOT, "serve_minicpm_sala_l8_long")["traffic"]
    assert traffic["prompt_tokens"] == sala["prompt_tokens"]
    assert traffic["schedule_seed"] != sala["schedule_seed"]


def test_a_program_that_cannot_read_the_family_fails_at_once(monkeypatch):
    """On the parent's program `config_from_hf` raises for
    `kimi_linear` before anything is started."""
    import ray_tpu
    import ray_tpu.models

    def parents(config, max_seq_len):
        raise ValueError("config_from_hf knows the model types 'mistral', "
                         "'afmoe', 'minicpm_sala' and 'evabyte', not "
                         f"{config['model_type']!r}")

    started = []
    monkeypatch.setattr(ray_tpu.models, "config_from_hf", parents)
    monkeypatch.setattr(ray_tpu, "init", lambda *a, **k: started.append(1))
    job = tiny_job()
    began = time.perf_counter()
    with pytest.raises(ValueError, match="kimi_linear"):
        serve_prefill_family.run({
            "cell": job["cell"], "config": job["config"],
            "traffic": job["traffic"], "seed": 1, "seconds": 1.0,
            "trace": False, "process_start": began})
    assert not started and time.perf_counter() - began < 5.0
    assert job["traffic"]["driver"] == "serve_prefill_family"


# ---- the cost functions, by hand ------------------------------------------

def test_costs_by_hand():
    """ISSUE 40's reckoning of one forward at 16,384 tokens."""
    c = cell_config()
    s = 16384
    kda, mla = costs_kimi.kda_cost(c, s), costs_kimi.mla_cost(c, s)
    assert kda["flops"] == 32 * s * 7 * 128 * 128
    # q, k, v, o in bfloat16, the decays in float32, a step a head
    assert kda["bytes"] == s * 32 * (128 * (4 * 2 + 4) + 4)
    assert costs.roofline_seconds(kda, PEAKS)["bound"] == "memory"
    assert costs.roofline_seconds(kda, PEAKS)["seconds"] == pytest.approx(
        0.986e-3, rel=1e-2)
    assert mla["flops"] == s * (s + 1) // 2 * 32 * 2 * (192 + 128)
    assert mla["bytes"] == 2 * s * 32 * 2 * (192 + 128)
    assert costs.roofline_seconds(mla, PEAKS)["bound"] == "compute"
    # three mla layers' causal pairs: 8.2 TFLOP
    assert 3 * mla["flops"] == pytest.approx(8.2e12, rel=1e-2)
    # matrices a token: 206 MFLOP in layer 1, 108.5 a kda and 87.7 an mla
    # expert layer (router, shared expert and mixer; routed rows apart)
    assert 2 * costs_kimi.layer_matmul_params(c, "kda", False) == \
        pytest.approx(206e6, rel=1e-2)
    shared_and_router = 7_077_888 + 2304 * 256
    assert costs_kimi.layer_matmul_params(c, "kda", True) == \
        39_518_368 + shared_and_router
    assert costs_kimi.layer_matmul_params(c, "mla", True) == \
        29_114_880 + shared_and_router
    rows = 12 * s           # an even router: one expert-row a token a layer
    by_hand = (2 * s * (costs_kimi.layer_matmul_params(c, "kda", False)
                        + 9 * costs_kimi.layer_matmul_params(c, "kda", True)
                        + 3 * costs_kimi.layer_matmul_params(c, "mla", True))
               + 10 * kda["flops"] + 3 * mla["flops"]
               + 2 * rows * 3 * 2304 * 1024 + 2 * 2304 * 20480)
    assert costs_kimi.forward_flops(c, s, rows) == pytest.approx(by_hand)
    # 23.7 TFLOP of matrices with the routed rows, as the issue reckons
    assert by_hand - 10 * kda["flops"] - 3 * mla["flops"] == pytest.approx(
        23.7e12, rel=2e-2)


# ---- the readers on hand-made events --------------------------------------

def traced_ctx():
    """Three forwards in the profile; the first began before the window
    and is left out. Each whole forward ran ten delta-rule kernels,
    three flash forwards and the grouped matmuls of twelve layers."""
    def ops_of(start):
        return ([(f"kda_attn.{1 + i}", start + i * MS, 0.8 * MS)
                 for i in range(10)]
                + [("flash_fwd", start + (10 + i) * MS, 0.9 * MS)
                   for i in range(3)]
                + [(f"moe_gmm.{1 + i}", start + (13 + i) * MS, 0.8 * MS)
                   for i in range(36)]
                + [("fusion.1", start + 50 * MS, 0.5 * MS)])
    spans = [("trace_window", 0.1, 1.0), ("forward", 0.05, 0.1),
             ("forward", 0.2, 0.1), ("forward", 0.5, 0.1)]
    ops = ops_of(0.06) + ops_of(0.2) + ops_of(0.5)
    forwards = [{"id": i, "padded": p, "real": p - 1000,
                 "rows_held": 12 * p, "experts_hit": 12 * 32}
                for i, p in enumerate([12288, 12288, 16384, 32768])]
    window = (0.1, 1.1)
    inside = {0: [e for e in ops if window[0] <= e[1] < window[1]]}
    return {"job": {"config": cell_config()}, "chips": 1, "peaks": PEAKS,
            "facts": {"forwards": forwards, "window_s": 50.0,
                      "late_ms": np.zeros(4)},
            "trace": {"spans": spans, "window": window, "inside": inside,
                      "busy_s": 0.08, "window_s": 1.0}}


def test_the_rooflines_count_each_layer_at_the_forwards_length():
    ctx = traced_ctx()
    c = ctx["job"]["config"]
    whole = traced_forwards.whole_forwards(ctx)
    assert [f["facts"]["id"] for f in whole] == [2, 3]
    kda = sum(10 * costs_kimi.kda_cost(c, s)["bytes"] / 819e9
              for s in (16384, 32768))
    assert reader("kda_attn_roofline.serve")(ctx) == pytest.approx(
        100 * kda / (2 * 10 * 0.8 * MS))
    mla = sum(3 * costs_kimi.mla_cost(c, s)["flops"] / 197e12
              for s in (16384, 32768))
    assert reader("mla_attn_roofline.serve")(ctx) == pytest.approx(
        100 * mla / (2 * 3 * 0.9 * MS))


def test_the_mixers_share_of_the_forwards_device_time():
    ctx = traced_ctx()
    # a forward: 10 x 0.8 + 3 x 0.9 ms of the mixers in 40 ms busy
    assert reader("delta_mixers_device_pct")(ctx) == pytest.approx(
        100 * 10.7 / 40.0)
    ctx["trace"]["inside"] = {0: [("fusion.1", 0.2, 0.01),
                                  ("fusion.1", 0.5, 0.01)]}
    assert reader("delta_mixers_device_pct")(ctx) is None


def test_mfu_counts_real_tokens_and_their_share_of_the_routed_rows():
    ctx = traced_ctx()
    c = ctx["job"]["config"]
    flops = sum(costs_kimi.forward_flops(c, f["real"], 12 * f["real"])
                for f in ctx["facts"]["forwards"])
    assert reader("serve_mfu_pct.kimi")(ctx) == pytest.approx(
        100 * flops / (50.0 * 197e12))


def test_the_quadratic_share_is_the_median_over_the_forwards(monkeypatch):
    c = cell_config()
    share = {}
    plans = []
    for n in (12288, 16384, 24576, 32768):
        kda = 10 * costs_kimi.kda_cost(c, n)["flops"]
        mla = 3 * costs_kimi.mla_cost(c, n)["flops"]
        share[n] = 100 * mla / (mla + kda)
        plans.append(ps.Span("model.delta.plan", 0, 0, None, None, 1, 1, {
            "tokens": n, "kda_flops": kda, "mla_pair_flops": mla}))
    monkeypatch.setattr(ps, "recorded", lambda: plans)
    ctx = traced_ctx()
    assert reader("mla_quadratic_flops_share")(ctx) == pytest.approx(
        float(np.median([share[12288], share[12288], share[16384],
                         share[32768]])))
    # the quadratic mechanism's share rises with the length
    assert [round(share[n]) for n in (12288, 16384, 24576, 32768)] == [
        91, 93, 95, 96]
    monkeypatch.setattr(ps, "recorded", lambda: [])
    assert reader("mla_quadratic_flops_share")(ctx) is None     # the parent


def test_the_moe_readers_take_this_configuration_as_it_is():
    ctx = traced_ctx()
    for name in ("moe_gmm_roofline.serve", "moe_gmm_device_pct"):
        assert 0 < reader(name)(ctx) < 100, name


def test_readers_find_nothing_in_an_older_drivers_facts():
    ctx = traced_ctx()
    del ctx["facts"]["forwards"]        # serve_openloop gives none
    for name in NEW:
        assert reader(name)(ctx) is None, name


def test_the_kernels_are_known_in_a_trace():
    """`kernels/` holds the three flash signatures: the latent
    attention's call (three operands, a tuple) is the flash forward's;
    the delta rule's (five operands, one result) matches none, so
    `op_name` passes its instruction name through and the readers take
    `kda_attn.<n>`."""
    kernels = xplane.kernel_signatures()
    tail = '), custom_call_target="tpu_custom_call"'
    wide = "bf16[1,16384,4096]{2,1,0}"
    kda = (f"%kda_attn.3 = {wide} custom-call({wide} %q, {wide} %k, {wide} "
           f"%v, f32[1,16384,4096]{{2,1,0}} %g, f32[1,16384,32]{{2,1,0}} %b"
           + tail)
    mla = ("%branch_0_fun.1 = (bf16[1,32,16384,128]{3,2,1,0}, "
           "f32[1,32,1,16384]{3,2,1,0}) custom-call("
           "bf16[1,32,16384,192]{3,2,1,0} %q, bf16[1,32,16384,192]{3,2,1,0} "
           "%k, bf16[1,32,16384,128]{3,2,1,0} %v" + tail)
    assert xplane.op_name(kda, kernels) == "kda_attn.3"
    assert xplane.op_name(mla, kernels) == costs_kimi.MLA_KERNEL
    forward = {"ops": [("kda_attn.2", 0.0, 1.0), ("kda_attn.17", 1.0, 2.0),
                       ("flash_fwd", 3.0, 4.0), ("kda_attention", 0.0, 9.0)]}
    assert traced_forwards.kernel_seconds(forward,
                                          costs_kimi.KDA_KERNEL) == 3.0
    assert traced_forwards.kernel_seconds(forward,
                                          costs_kimi.MLA_KERNEL) == 4.0


# ---- the manifest ---------------------------------------------------------

def test_the_manifest_names_the_cell_and_its_metrics():
    m = manifest()
    cell = next(c for c in m["workloads"] if c["name"] == CELL)
    assert cell == dict(cell, config=CONFIG,
                        traffic="prefill_9k_32k_kimi_openloop", chips=1)
    assert len(cell["why"]) <= 200
    assert f"{run.load_job(ROOT, CELL)['traffic']['rate_per_s']} requests" \
        in cell["why"]
    job = run.load_job(ROOT, CELL)
    assert set(job["limits"]) == {"token_gap", "logit_err"}
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "reference", "kimi_linear.py"))
    ttft = next(e for e in m["end_to_end"]
                if e["name"] == "serve_ttft_p50_ms")
    assert CELL in ttft["workloads"]
    entries = {e["name"]: e for e in m["per_layer"]}
    assert all(entries[n]["workloads"] == [CELL] for n in NEW)
    assert all(CELL in entries[n]["workloads"] for n in MOE)
    assert not any(CELL in entries[n]["workloads"] for n in PINNED)
    # the thirteen readers every serve cell reports, four of the routed
    # layer's, the five new ones
    assert sum(CELL in e.get("workloads", ()) for e in entries.values()) == \
        13 + 4 + 5
    assert [(entries[n]["moves"], entries[n]["unit"], entries[n]["better"],
             entries[n]["source"], entries[n]["layer"]) for n in NEW] == [
        ("serve_ttft_p50_ms", "%", "higher", "host_clock", "model step"),
        ("serve_ttft_p50_ms", "%", "higher", "device_trace", "kernels"),
        ("serve_ttft_p50_ms", "%", "higher", "device_trace", "kernels"),
        ("serve_ttft_p50_ms", "%", "lower", "device_trace", "mixers"),
        ("serve_ttft_p50_ms", "%", "lower", "program_counter", "mixers")]
    for name in NEW:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py")), name
    reported = [e["name"] for e in run.metrics_of(m, "per_layer", CELL)]
    assert set(NEW + MOE) <= set(reported) and len(reported) == 22
