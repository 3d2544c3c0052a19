"""What the EvaByte cell adds to the yardstick: its configuration and
the reference's weights, its driver end to end at a tiny size on the
CPU, how it fails at once on a program that cannot read the family, the
cost functions against counts by hand, each new reader on hand-made
events and records, and the kernels' names in a trace."""

import importlib.util
import json
import math
import os
import time

import numpy as np
import pytest

from benchmark import check, costs, costs_eva, run, traced_forwards, xplane
from benchmark import program_spans as ps
from benchmark.drivers import serve_openloop, serve_prefill_family
from benchmark.reference import evabyte as ref

ROOT = run.ROOT
CELL = "serve_evabyte_l16_long"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 128,
        "vocab_size": 40, "num_pred_heads": 3, "num_hidden_layers": 2,
        "window_size": 32, "chunk_size": 4, "torch_dtype": "float32"}
PEAKS = costs.chip_peaks("TPU v5 lite")
MS = 1e-3
NEW = ["serve_mfu_pct.eva", "eva_attn_roofline.serve", "eva_device_pct",
       "eva_far_pairs_share"]


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

def test_the_configuration_keeps_every_published_key_but_the_depth():
    c = cell_config()
    if os.path.exists(CATALOG):     # the catalog, where it is installed
        with open(CATALOG) as f:
            row = next(json.loads(line) for line in f
                       if '"EvaByte"' in line)
        changed = {k for k, v in row["config"].items() if c.get(k, "") != v}
        assert changed == {"num_hidden_layers"}
        entry_source = row["source_url"]
    else:
        entry_source = ("https://huggingface.co/EvaByte/EvaByte/blob/main/"
                        "config.json")
    assert (c["hidden_size"], c["intermediate_size"], c["vocab_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["num_pred_heads"], c["window_size"], c["chunk_size"],
            c["rope_theta"], c["rms_norm_eps"], c["num_hidden_layers"]) == (
        4096, 11008, 320, 32, 32, 8, 2048, 16, 100000, 1e-5, 16)
    assert c["attention_class"] == "eva" and c["model_type"] == "evabyte"
    assert c["published"] == {"num_hidden_layers": 32}
    entry = next(e for e in manifest()["configs"]
                 if e["name"] == "evabyte-l16")
    assert entry["reduced"] == ["num_hidden_layers"] == sorted(c["published"])
    assert entry["source"] == entry_source
    assert {"chunk_summaries", "attention", "windows_and_chunks", "norms",
            "residuals_and_mlp", "head", "arithmetic", "weights", "padding",
            "rope_pairing", "torch_dtype"} <= set(c["assumed"])
    assert "NOT SETTLED" in c["assumed"]["chunk_summaries"]
    assert "layers 1-16 of 32" in c["stands_for"]
    assert "3,250,065,408" in c["deployment"] and "6.50 GB" in c["deployment"]


def test_the_program_reads_the_configuration_file():
    from ray_tpu.models import EvaSizes, LayerSpec, config_from_hf
    cfg = config_from_hf(cell_config(), 32768)
    assert cfg.layers == (LayerSpec(mixer="eva"),) * 16
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.vocab_size, cfg.n_pred_heads) == (
        4096, 32, 32, 128, 11008, 320, 8)
    assert cfg.eva == EvaSizes(2048, 16) and cfg.rope_theta == 1e5
    assert cfg.norm_unit_offset and cfg.residual_f32 and cfg.logits_f32
    assert str(cfg.dtype) == "bfloat16" and cfg.rms_norm_eps == 1e-5


def test_the_slice_is_3_250_065_408_parameters():
    c = cell_config()
    table = ref.leaf_table(ref.Sizes.from_config(c))
    held = sum(math.prod(shape) for _p, shape, _k in table)
    assert held == costs_eva.total_params(c) == 3_250_065_408
    assert 2 * held == pytest.approx(6.50e9, rel=1e-3)      # bfloat16
    by_layer = {sum(math.prod(shape) for p, shape, _k in table
                    if p[:2] == ("blocks", i)) for i in range(16)}
    assert by_layer == {202_391_552}
    assert costs_eva.layer_matmul_params(c) == 202_375_168
    assert held - 16 * 202_391_552 == 320 * 4096 + 4096 * 2560 + 4096
    # the published depth would not fit a chip's 16 GB in bfloat16 beside
    # a prefill's temporaries: 6.49 B
    assert costs_eva.total_params(dict(c, num_hidden_layers=32)) == \
        6_488_330_240


def test_reference_weights_from_the_seed_in_the_stated_type():
    import jax
    sz = ref.Sizes.from_config(dict(cell_config(), **TINY))
    a = ref.make_weights(ref.seed_key(2**31 + 3), sz)
    b = ref.make_weights(ref.seed_key(2**31 + 3), sz)
    c = ref.make_weights(ref.seed_key(3), sz)
    for x, y, z in zip(*(jax.tree.leaves(t) for t in (a, b, c))):
        assert x.dtype == np.float32
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert not np.any(x) or not np.allclose(x, z)
    assert a["blocks"][0]["wk"].shape == (64, 4, 16)
    assert a["blocks"][1]["eva_phi"].shape == (4, 16)
    assert a["unembed"].shape == (64, 3 * 40)
    assert 0.01 < float(np.std(a["blocks"][0]["eva_mu"])) < 0.03
    bf16 = ref.make_weights(ref.seed_key(3), sz._replace(dtype="bfloat16"))
    assert all(leaf.dtype == "bfloat16" for leaf in jax.tree.leaves(bf16))
    with pytest.raises(ValueError, match="attention_class"):
        ref.Sizes.from_config(dict(cell_config(), attention_class="mha"))


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
    assert out["numbers"]["logit_err"] < 1e-4
    assert out["extras"]["int8"]["logit_err"] > 100 * out["numbers"][
        "logit_err"]


def test_facts_hold_every_forward_past_one_window(outcome):
    job, out = outcome
    plan = serve_openloop.schedule(
        dict(job["traffic"], vocab=40), 2**31 + 5, 2.0)
    forwards = out["facts"]["forwards"]
    assert sorted(f["id"] for f in forwards) == list(range(12))
    for f in forwards:
        assert f["real"] == plan["lengths"][f["id"]] > 32
        assert f["padded"] == min(n for n in (64, 128) if n >= f["real"])
        assert f["rows_held"] == 0 and f["experts_hit"] == 0


def test_the_program_recorded_a_plan_for_each_shape(outcome):
    _job, out = outcome
    plans = {s.counts["tokens"]: s.counts for s in ps.recorded()
             if s.name == "model.eva.plan" and s.counts
             and s.counts["window"] == 32}
    assert {64, 128} <= set(plans)
    for tokens in (64, 128):
        plan, by_hand = plans[tokens], costs_eva.pairs(
            dict(cell_config(), **TINY), tokens)
        assert (plan["eva_layers"], plan["chunk"], plan["windows"],
                plan["summaries"]) == (2, 4, tokens // 32, tokens // 4)
        assert plan["local_pairs"] == 2 * 4 * by_hand["local"]
        assert plan["far_pairs"] == 2 * 4 * by_hand["far"] > 0
    ctx = {"facts": out["facts"]}
    share = reader("eva_far_pairs_share")(ctx)
    lengths = [f["padded"] for f in out["facts"]["forwards"]]
    far = sum(plans[n]["far_pairs"] for n in lengths)
    seen = far + sum(plans[n]["local_pairs"] for n in lengths)
    assert share == pytest.approx(100 * far / seen) and 0 < share < 100


def test_schedule_of_the_cell():
    traffic = dict(run.load_job(ROOT, CELL)["traffic"], vocab=320)
    plan = serve_openloop.schedule(traffic, 2**31 + 7, 50.0)
    lengths = plan["lengths"]
    assert lengths.min() >= 8704 and lengths.max() <= 32768
    # every prompt spans five windows or more
    assert lengths.min() > 4 * cell_config()["window_size"]
    assert 0.85 * 12288 <= np.median(lengths) <= 1.15 * 12288
    assert traffic["pad_to"] == [12288, 16384, 24576, 32768]
    assert all(n % cell_config()["window_size"] == 0
               for n in traffic["pad_to"])
    assert len(plan["due"]) == round(traffic["rate_per_s"] * 50.0) >= 15
    assert traffic["check_requests"] == 4 and traffic["top_k"] == 8
    assert traffic["check_batch"] == 1 and traffic["client_threads"] == 8
    assert traffic["trace_seconds"] == 20.0
    ids = np.concatenate([np.asarray(t) for t in plan["tokens"]])
    assert ids.min() == 0 and ids.max() == 319      # all 320 rows
    sala = run.load_job(ROOT, "serve_minicpm_sala_l8_long")["traffic"]
    assert traffic["prompt_tokens"] == sala["prompt_tokens"]


def test_a_program_that_cannot_read_the_family_fails_at_once(monkeypatch):
    """On the parent's program `config_from_hf` raises for `evabyte`
    before anything is started."""
    import ray_tpu
    import ray_tpu.models

    def parents(config, max_seq_len):
        raise ValueError("config_from_hf knows the model types 'mistral', "
                         "'afmoe' and 'minicpm_sala', not "
                         f"{config['model_type']!r}")

    started = []
    monkeypatch.setattr(ray_tpu.models, "config_from_hf", parents)
    monkeypatch.setattr(ray_tpu, "init", lambda *a, **k: started.append(1))
    job = tiny_job()
    began = time.perf_counter()
    with pytest.raises(ValueError, match="evabyte"):
        serve_prefill_family.run({
            "cell": job["cell"], "config": job["config"],
            "traffic": job["traffic"], "seed": 1, "seconds": 1.0,
            "trace": False, "process_start": began})
    assert not started and time.perf_counter() - began < 5.0
    assert job["traffic"]["driver"] == "serve_prefill_family"


# ---- the cost functions, by hand ------------------------------------------

def test_pairs_by_hand():
    c = cell_config()
    assert costs_eva.pairs(c, 4) == {"local": 10, "far": 0}
    assert costs_eva.pairs(c, 2048) == {"local": 2048 * 2049 // 2, "far": 0}
    s = 12288
    by_hand = {"local": sum(t % 2048 + 1 for t in range(s)),
               "far": sum(t // 2048 * 128 for t in range(s))}
    assert costs_eva.pairs(c, s) == by_hand
    assert by_hand["local"] == 6 * 2048 * 2049 // 2
    assert by_hand["far"] == 2048 * 128 * (0 + 1 + 2 + 3 + 4 + 5)
    # a part of the sixth window
    assert costs_eva.pairs(c, 10240 + 7) == {
        "local": 5 * 2048 * 2049 // 2 + 7 * 8 // 2, "far": by_hand["far"]
        - 2048 * 128 * 5 + 7 * 128 * 5}
    assert costs_eva.summarised(c, 12288) == 10240
    assert costs_eva.summarised(c, 10241) == 10240
    assert costs_eva.summarised(c, 2048) == 0
    # the program counts the same from its own shapes
    from ray_tpu.ops.eva_attention import pairs
    assert pairs(s, 2048, 16) == (by_hand["local"], by_hand["far"])


def test_costs_by_hand():
    c = cell_config()
    s = 16384
    p = costs_eva.pairs(c, s)
    attn = costs_eva.eva_attn_cost(c, s)
    assert attn["flops"] == 4 * 128 * 32 * (p["local"] + p["far"])
    assert attn["bytes"] == 2 * 32 * 128 * (4 * s + 2 * s // 16)
    assert costs.roofline_seconds(attn, PEAKS)["bound"] == "compute"
    # under a fifth of causal attention's pairs at this length
    assert (p["local"] + p["far"]) / (s * (s + 1) / 2) == pytest.approx(
        0.18, abs=0.001)
    by_hand = (16 * (2 * s * 202_375_168 + 6 * 128 * 32 * 14336
                     + attn["flops"]) + 2 * 4096 * 2560)
    assert costs_eva.forward_flops(c, s) == pytest.approx(by_hand)
    # 404.8 MFLOP a byte and layer in the matrices, as the issue reckons
    assert 2 * 202_375_168 == pytest.approx(404.8e6, rel=1e-3)
    # the attention is 5-7 % of a forward's least work
    assert 16 * attn["flops"] / by_hand == pytest.approx(0.056, abs=0.002)


# ---- the readers on hand-made events --------------------------------------

def traced_ctx():
    """Three forwards in the profile; the first began before the window
    and is left out. Each whole forward ran sixteen layers' summaries
    and attention."""
    def ops_of(start):
        return ([(f"eva_summaries.{1 + i}", start + i * MS, 0.05 * MS)
                 for i in range(16)]
                + [(f"eva_attn.{1 + i}", start + (i + 0.1) * MS, 0.45 * MS)
                   for i in range(16)]
                + [("fusion.1", start + 20 * MS, 12.0 * MS)])
    spans = [("trace_window", 0.1, 1.0), ("forward", 0.05, 0.1),
             ("forward", 0.2, 0.1), ("forward", 0.5, 0.1)]
    ops = ops_of(0.06) + ops_of(0.2) + ops_of(0.5)
    forwards = [{"id": i, "padded": p, "real": p - 1000, "rows_held": 0,
                 "experts_hit": 0}
                for i, p in enumerate([12288, 12288, 16384, 32768])]
    window = (0.1, 1.1)
    inside = {0: [e for e in ops if window[0] <= e[1] < window[1]]}
    return {"job": {"config": cell_config()}, "chips": 1, "peaks": PEAKS,
            "facts": {"forwards": forwards, "window_s": 50.0,
                      "late_ms": np.zeros(4)},
            "trace": {"spans": spans, "window": window, "inside": inside,
                      "busy_s": 0.04, "window_s": 1.0}}


def test_the_roofline_counts_each_layer_at_the_forwards_length():
    ctx = traced_ctx()
    c = ctx["job"]["config"]
    whole = traced_forwards.whole_forwards(ctx)
    assert [f["facts"]["id"] for f in whole] == [2, 3]
    least = sum(16 * costs_eva.eva_attn_cost(c, s)["flops"] / 197e12
                for s in (16384, 32768))
    assert reader("eva_attn_roofline.serve")(ctx) == pytest.approx(
        100 * least / (2 * 16 * 0.45 * MS))


def test_the_mixers_share_of_the_forwards_device_time():
    ctx = traced_ctx()
    # a forward: 16 x (0.05 + 0.45) ms of the mixer in 20 ms busy
    assert reader("eva_device_pct")(ctx) == pytest.approx(100 * 8.0 / 20.0)
    ctx["trace"]["inside"] = {0: [("fusion.1", 0.2, 0.01),
                                  ("fusion.1", 0.5, 0.01)]}
    assert reader("eva_device_pct")(ctx) is None


def test_mfu_counts_real_bytes_of_answered_prompts():
    ctx = traced_ctx()
    c = ctx["job"]["config"]
    flops = sum(costs_eva.forward_flops(c, f["real"])
                for f in ctx["facts"]["forwards"])
    assert reader("serve_mfu_pct.eva")(ctx) == pytest.approx(
        100 * flops / (50.0 * 197e12))


def test_the_far_share_weighs_each_length_by_its_forwards(monkeypatch):
    c = cell_config()
    plans = []
    for n in (12288, 16384, 32768):
        p = costs_eva.pairs(c, n)
        plans.append(ps.Span("model.eva.plan", 0, 0, None, None, 1, 1, {
            "tokens": n, "local_pairs": 512 * p["local"],
            "far_pairs": 512 * p["far"]}))
    monkeypatch.setattr(ps, "recorded", lambda: plans)
    ctx = traced_ctx()
    each = {n: costs_eva.pairs(c, n) for n in (12288, 16384, 32768)}
    far = 2 * each[12288]["far"] + each[16384]["far"] + each[32768]["far"]
    seen = far + (2 * each[12288]["local"] + each[16384]["local"]
                  + each[32768]["local"])
    assert reader("eva_far_pairs_share")(ctx) == pytest.approx(
        100 * far / seen)
    # 24 / 30 / 41 / 48 % at the four padded lengths
    for n, share in ((12288, 24), (16384, 30), (24576, 41), (32768, 48)):
        p = costs_eva.pairs(c, n)
        assert round(100 * p["far"] / (p["far"] + p["local"])) == share
    monkeypatch.setattr(ps, "recorded", lambda: [])
    assert reader("eva_far_pairs_share")(ctx) is None       # the parent


def test_readers_find_nothing_in_an_older_drivers_facts():
    ctx = traced_ctx()
    del ctx["facts"]["forwards"]        # serve_openloop gives none
    for name in NEW:
        assert reader(name)(ctx) is None, name


def test_the_eva_kernels_are_known_by_their_instruction_names():
    """`kernels/` holds the three flash signatures; neither new call
    matches one, so `op_name` passes their instruction names through
    and the readers take `<kernel>.<n>`."""
    kernels = xplane.kernel_signatures()
    assert sorted(kernels) == [(3, True), (6, False), (6, True)]
    tail = '), custom_call_target="tpu_custom_call"'
    attn = ("%eva_attn.3 = bf16[1,32,16384,128]{3,2,1,0} custom-call("
            "bf16[1,32,16384,128]{3,2,1,0} %q, bf16[1,32,16384,128]{3,2,1,0} "
            "%k, bf16[1,32,16384,128]{3,2,1,0} %v, bf16[1,32,1024,128]"
            "{3,2,1,0} %a, bf16[1,32,1024,128]{3,2,1,0} %b" + tail)
    pool = ("%eva_summaries.3 = (bf16[1,32,1024,128]{3,2,1,0}, "
            "bf16[1,32,1024,128]{3,2,1,0}) custom-call("
            "bf16[1,32,16384,128]{3,2,1,0} %k, bf16[1,32,16384,128]{3,2,1,0} "
            "%v, f32[32,1,128]{2,1,0} %p, f32[32,1,128]{2,1,0} %m" + tail)
    assert xplane.op_name(attn, kernels) == "eva_attn.3"
    assert xplane.op_name(pool, kernels) == "eva_summaries.3"
    forward = {"ops": [("eva_attn.2", 0.0, 1.0), ("eva_attn.17", 1.0, 2.0),
                       ("eva_summaries.1", 3.0, 4.0)]}
    assert traced_forwards.kernel_seconds(forward, costs_eva.ATTN) == 3.0
    assert traced_forwards.kernel_seconds(forward,
                                          costs_eva.SUMMARIES) == 4.0


# ---- the manifest ---------------------------------------------------------

def test_the_manifest_appends_the_cell_and_its_metrics():
    m = manifest()
    cell = next(c for c in m["workloads"] if c["name"] == CELL)
    assert cell == dict(cell, config="evabyte-l16",
                        traffic="prefill_bytes_9k_32k_openloop", chips=1)
    assert len(cell["why"]) <= 200 and "RATE" not in cell["why"]
    assert f"{run.load_job(ROOT, CELL)['traffic']['rate_per_s']} requests" \
        in cell["why"]
    assert not any(c["chips"] == 4 for c in m["workloads"])
    lists = {e["name"]: e["workloads"] for e in m["per_layer"]}
    assert [n for n, w in lists.items() if CELL in w][-4:] == NEW
    assert all(lists[n] == [CELL] for n in NEW)
    assert sum(CELL in w for w in lists.values()) == 13 + 4
    moved = {e["name"]: (e["moves"], e["unit"], e["better"], e["source"],
                         e["layer"]) for e in m["per_layer"]}
    assert [moved[n] for n in NEW] == [
        ("serve_ttft_p50_ms", "%", "higher", "host_clock", "model step"),
        ("serve_ttft_p50_ms", "%", "higher", "device_trace", "kernels"),
        ("serve_ttft_p50_ms", "%", "lower", "device_trace", "mixers"),
        ("serve_ttft_p50_ms", "%", "higher", "program_counter", "mixers")]
    for name in ("flash_fwd_roofline.serve", "serve_mfu_pct",
                 "serve_mfu_pct.afmoe", "serve_mfu_pct.sala",
                 "mixers_device_pct", "moe_gmm_roofline.serve"):
        assert CELL not in lists[name]
    ttft = next(e for e in m["end_to_end"]
                if e["name"] == "serve_ttft_p50_ms")
    assert CELL in ttft["workloads"]
    limits = run.load_job(ROOT, CELL)["limits"]
    assert set(limits) == {"token_gap", "logit_err"}
