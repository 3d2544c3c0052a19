"""What the MiniCPM-SALA cell adds to the yardstick: its configuration
and the reference's weights, its driver end to end at a tiny size on the
CPU, how it fails at once on a program that cannot read the family, the
cost functions against counts by hand, each new reader on hand-made
events and records, the kernels' names in a trace, and the planted
faults of the calibration."""

import importlib.util
import json
import math
import os
import time

import numpy as np
import pytest

from benchmark import check, costs, costs_sala, run, traced_forwards, xplane
from benchmark import program_spans as ps
from benchmark.drivers import serve_openloop, serve_prefill_family
from benchmark.reference import minicpm_sala as ref

ROOT = run.ROOT
CELL = "serve_minicpm_sala_l8_long"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "lightning_nh": 4,
        "lightning_nkv": 4, "lightning_head_dim": 16,
        "intermediate_size": 128, "vocab_size": 96,
        "torch_dtype": "float32",
        "sparse_config": {"kernel_size": 8, "kernel_stride": 4,
                          "block_size": 8, "topk": 6, "window_size": 16,
                          "init_blocks": 1, "dense_len": 32}}
PEAKS = costs.chip_peaks("TPU v5 lite")
MS = 1e-3


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

def test_the_configuration_keeps_every_published_width():
    c = cell_config()
    row = None
    if os.path.exists(CATALOG):     # the catalog, where it is installed
        with open(CATALOG) as f:
            row = next((json.loads(line) for line in f
                        if '"MiniCPM-SALA"' in line), None)
    if row is not None:
        changed = {k for k, v in row["config"].items() if c.get(k) != v}
        assert changed == {"num_hidden_layers", "mixer_types"}
        assert c["mixer_types"] == row["config"]["mixer_types"][9:17]
    assert (c["hidden_size"], c["intermediate_size"], c["vocab_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["lightning_nh"], c["lightning_nkv"],
            c["lightning_head_dim"], c["rms_norm_eps"], c["scale_emb"],
            c["scale_depth"], c["dim_model_base"]) == (
        4096, 16384, 73448, 32, 2, 128, 32, 32, 128, 1e-6, 12, 1.4, 256)
    assert c["mixer_types"] == ["minicpm4"] + ["lightning-attn"] * 6 + \
        ["minicpm4"]
    assert c["published"]["mixer_types"][9:17] == c["mixer_types"]
    assert c["published"]["num_hidden_layers"] == 32
    assert c["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "topk": 64, "window_size": 2048, "init_blocks": 1,
        "dense_len": 8192}
    entry = next(e for e in manifest()["configs"]
                 if e["name"] == "minicpm-sala-l8")
    assert entry["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert sorted(entry["reduced"]) == sorted(c["published"])
    assert {"lightning_layer", "lightning_choices", "sparse_layer",
            "sparse_config", "embedding_and_head", "mlp", "arithmetic",
            "weights", "rope_pairing"} <= set(c["assumed"])
    assert "layers 9-16 of 32" in c["stands_for"] and c["deployment"]


def test_the_program_reads_the_configuration_file():
    from ray_tpu.models import LayerSpec, SparseSizes, config_from_hf
    cfg = config_from_hf(cell_config(), 32768)
    sparse = LayerSpec(mixer="sparse", rope=False)
    lightning = LayerSpec(mixer="lightning", rope=True, kv_heads=32)
    assert cfg.layers == (sparse,) + (lightning,) * 6 + (sparse,)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.vocab_size) == (4096, 32, 2, 128, 16384, 73448)
    assert cfg.residual_scale == pytest.approx(0.24749, abs=1e-5)
    assert cfg.logit_scale == 1 / 16 and cfg.embed_scale == 12
    assert cfg.sparse == SparseSizes()


def test_the_slice_is_2_820_5_million_parameters():
    c = cell_config()
    sz = ref.Sizes.from_config(c)
    table = ref.leaf_table(sz)
    held = sum(math.prod(shape) for _p, shape, _k in table)
    assert held == costs_sala.total_params(c) == 2_820_569_088
    matrices = sum(math.prod(shape) for _p, shape, _k in table
                   if len(shape) > 1)
    assert matrices == 2_820_472_832        # the issue's 2,820.5 M
    by_layer = [sum(math.prod(shape) for p, shape, _k in table
                    if p[:2] == ("blocks", i) and len(shape) > 1)
                for i in range(8)]
    assert by_layer == [253_755_392] + [285_212_672] * 6 + [253_755_392]
    assert costs_sala.layer_matmul_params(c, "sparse") == 253_755_392
    assert costs_sala.layer_matmul_params(c, "lightning") == 285_212_672


def test_reference_weights_from_the_seed_in_the_stated_type():
    import jax
    sz = ref.Sizes.from_config(dict(cell_config(), **TINY))
    a = ref.make_weights(ref.seed_key(2**31 + 3), sz)
    b = ref.make_weights(ref.seed_key(2**31 + 3), sz)
    c = ref.make_weights(ref.seed_key(3), sz)
    for x, y, z in zip(*(jax.tree.leaves(t) for t in (a, b, c))):
        assert x.dtype == np.float32
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert x.ndim == 1 or not np.allclose(x, z)
    assert a["blocks"][0]["wk"].shape == (64, 2, 16)
    assert a["blocks"][1]["wk"].shape == (64, 4, 16)
    assert "out_norm" in a["blocks"][1] and "out_norm" not in a["blocks"][0]
    bf16 = ref.make_weights(ref.seed_key(3), sz._replace(dtype="bfloat16"))
    assert all(leaf.dtype == "bfloat16" for leaf in jax.tree.leaves(bf16))


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


def test_facts_hold_every_forward_and_no_routed_row(outcome):
    job, out = outcome
    plan = serve_openloop.schedule(
        dict(job["traffic"], vocab=96), 2**31 + 5, 2.0)
    forwards = out["facts"]["forwards"]
    assert sorted(f["id"] for f in forwards) == list(range(12))
    for f in forwards:
        assert f["real"] == plan["lengths"][f["id"]] > 32   # all sparse
        assert f["padded"] == min(n for n in (64, 128) if n >= f["real"])
        assert f["rows_held"] == 0 and f["experts_hit"] == 0
    assert not [s for s in ps.recorded() if s.name == "model.moe.route"
                and s.counts and s.counts.get("layers") == 0]


def test_the_program_recorded_a_plan_for_each_shape(outcome):
    _job, out = outcome
    plans = {s.counts["tokens"]: s.counts for s in ps.recorded()
             if s.name == "model.mixers.plan" and s.counts}
    assert {64, 128} <= set(plans)
    for tokens in (64, 128):
        plan = plans[tokens]
        assert (plan["linear_layers"], plan["sparse_layers"],
                plan["sparse_mode"], plan["chunk"]) == (6, 2, 1, 128)
        assert 0 < plan["keys_selected"] <= plan["keys_causal"]
        assert plan["keys_causal"] == 4 * tokens * (tokens + 1) // 2
        assert "keys_read" not in plan


def test_schedule_of_the_cell():
    traffic = dict(run.load_job(ROOT, CELL)["traffic"], vocab=73448)
    plan = serve_openloop.schedule(traffic, 2**31 + 7, 50.0)
    lengths = plan["lengths"]
    assert lengths.min() >= 8704 and lengths.max() <= 32768
    # every prompt outgrows dense_len: every sparse layer runs sparse
    assert lengths.min() > cell_config()["sparse_config"]["dense_len"]
    assert 0.85 * 12288 <= np.median(lengths) <= 1.15 * 12288
    assert traffic["pad_to"] == [12288, 16384, 24576, 32768]
    assert len(plan["due"]) == round(traffic["rate_per_s"] * 50.0) >= 20
    assert traffic["schedule_seed"] == 20261002
    assert traffic["check_requests"] == 4 and traffic["top_k"] == 8
    assert 60000 < max(max(t) for t in plan["tokens"]) < 73448


def test_a_program_that_cannot_read_the_family_fails_at_once(monkeypatch):
    """On the parent's program `config_from_hf` raises for
    `minicpm_sala`; `serve_prefill` alone would raise it inside the
    replica's constructor, which the controller tries again for 1,100 s.
    This driver raises before anything is started."""
    import ray_tpu
    import ray_tpu.models

    def parents(config, max_seq_len):
        raise ValueError("config_from_hf knows the model types 'mistral' "
                         f"and 'afmoe', not {config['model_type']!r}")

    started = []
    monkeypatch.setattr(ray_tpu.models, "config_from_hf", parents)
    monkeypatch.setattr(ray_tpu, "init", lambda *a, **k: started.append(1))
    job = tiny_job()
    began = time.perf_counter()
    with pytest.raises(ValueError, match="minicpm_sala"):
        serve_prefill_family.run({
            "cell": job["cell"], "config": job["config"],
            "traffic": job["traffic"], "seed": 1, "seconds": 1.0,
            "trace": False, "process_start": began})
    assert not started and time.perf_counter() - began < 5.0
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "prefill_9k_32k_openloop.json")) as f:
        assert json.load(f)["driver"] == "serve_prefill_family"


# ---- the cost functions, by hand ------------------------------------------

def test_selected_pairs_by_hand():
    c = cell_config()
    # up to dense_len: causal
    assert costs_sala.selected_pairs(c, 4) == 10
    assert costs_sala.selected_pairs(c, 8192) == 8192 * 8193 // 2
    # past it: every key while b_t < 64, then 63 blocks and its own
    s = 12288
    by_hand = sum(t + 1 for t in range(4096)) + sum(
        63 * 64 + t % 64 + 1 for t in range(4096, s))
    assert costs_sala.selected_pairs(c, s) == by_hand
    assert by_hand == 4096 * 4097 // 2 + (s - 4096) * 63 * 64 + \
        (s - 4096) // 64 * (64 * 65 // 2)
    # the mean at 16,384 is the issue's 3,560 keys a query
    assert costs_sala.selected_pairs(c, 16384) / 16384 == pytest.approx(
        3560, rel=0.01)
    assert costs_sala.visible_compressed(c, 8192) == 0
    assert costs_sala.visible_compressed(c, 12288) == sum(
        (t - 31) // 16 + 1 for t in range(31, 12288))


def test_costs_by_hand():
    c = cell_config()
    assert costs_sala.layer_kinds(c) == ["sparse"] + ["lightning"] * 6 + \
        ["sparse"]
    s = 16384
    lightning = costs_sala.lightning_cost(c, s)
    assert lightning["flops"] == 32 * s * 4 * 128 * 128
    assert lightning["bytes"] == 4 * s * 4096 * 2 == 536_870_912
    least = costs.roofline_seconds(lightning, PEAKS)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(0.656e-3, rel=0.01)
    sparse = costs_sala.sparse_attn_cost(c, s)
    assert sparse["flops"] == 4 * 128 * 32 * costs_sala.selected_pairs(c, s)
    assert sparse["flops"] == pytest.approx(0.96e12, rel=0.01)
    assert sparse["bytes"] == 2 * s * 128 * (2 * 32 + 2 * 2)
    assert costs.roofline_seconds(sparse, PEAKS)["bound"] == "compute"
    matrices = 2 * 253_755_392 + 6 * 285_212_672
    by_hand = (2 * s * matrices + 6 * lightning["flops"]
               + 2 * sparse["flops"]
               + 2 * 2 * 128 * 32 * costs_sala.visible_compressed(c, s)
               + 2 * 4096 * 73448)
    assert costs_sala.forward_flops(c, s) == pytest.approx(by_hand)
    # 4.44 GFLOP a token in the matrices, as the issue reckons
    assert 2 * matrices == pytest.approx(4.44e9, rel=0.01)


# ---- the readers on hand-made events --------------------------------------

def traced_ctx():
    """Three forwards in the profile; the first began before the window
    and is left out. Each whole forward ran six lightning calls, two
    choices and two sparse attention calls."""
    def ops_of(start):
        return ([(f"lightning_attn.{3 + i}", start + i * MS, 0.5 * MS)
                 for i in range(6)]
                + [(f"sparse_select.{i}", start + (7 + i) * MS, 0.25 * MS)
                   for i in range(2)]
                + [(f"sparse_attn.{i}", start + (10 + i) * MS, 1.0 * MS)
                   for i in range(2)]
                + [("fusion.1", start + 20 * MS, 14.5 * MS)])
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


def test_rooflines_count_each_layer_at_the_forwards_length():
    ctx = traced_ctx()
    c = ctx["job"]["config"]
    whole = traced_forwards.whole_forwards(ctx)
    assert [f["facts"]["id"] for f in whole] == [2, 3]
    least = sum(6 * costs_sala.lightning_cost(c, s)["bytes"] / 819e9
                for s in (16384, 32768))
    assert reader("lightning_attn_roofline.serve")(ctx) == pytest.approx(
        100 * least / (2 * 6 * 0.5 * MS))
    least = sum(2 * costs_sala.sparse_attn_cost(c, s)["flops"] / 197e12
                for s in (16384, 32768))
    assert reader("sparse_attn_roofline.serve")(ctx) == pytest.approx(
        100 * least / (2 * 2 * 1.0 * MS))


def test_mixers_share_of_the_forwards_device_time():
    ctx = traced_ctx()
    # a forward: 3 + 0.5 + 2 ms of mixers in 20 ms busy
    assert reader("mixers_device_pct")(ctx) == pytest.approx(
        100 * 5.5 / 20.0)
    ctx["trace"]["inside"] = {0: [("fusion.1", 0.2, 0.01),
                                  ("fusion.1", 0.5, 0.01)]}
    assert reader("mixers_device_pct")(ctx) is None


def test_mfu_counts_real_tokens_of_answered_prompts():
    ctx = traced_ctx()
    c = ctx["job"]["config"]
    flops = sum(costs_sala.forward_flops(c, f["real"])
                for f in ctx["facts"]["forwards"])
    assert reader("serve_mfu_pct.sala")(ctx) == pytest.approx(
        100 * flops / (50.0 * 197e12))


def test_readers_find_nothing_in_an_older_drivers_facts():
    ctx = traced_ctx()
    del ctx["facts"]["forwards"]        # serve_openloop gives none
    for name in ("lightning_attn_roofline.serve",
                 "sparse_attn_roofline.serve", "mixers_device_pct",
                 "serve_mfu_pct.sala"):
        assert reader(name)(ctx) is None, name


def test_the_mixers_kernels_are_known_by_their_instruction_names():
    """`kernels/` holds the three flash signatures; none of the new
    calls matches one, so `op_name` passes their instruction names
    through and the readers take `<kernel>.<n>`."""
    kernels = xplane.kernel_signatures()
    assert sorted(kernels) == [(3, True), (6, False), (6, True)]
    tail = '), custom_call_target="tpu_custom_call"'
    lightning = ("%lightning_attn.5 = bf16[1,16384,4096]{2,1,0} custom-call("
                 "f32[32]{0} %a, bf16[1,16384,4096]{2,1,0} %b, "
                 "bf16[1,16384,4096]{2,1,0} %c, bf16[1,16384,4096]{2,1,0} %d"
                 + tail)
    select = ("%sparse_select.1 = s32[1,2,256,16384]{3,2,1,0} custom-call("
              "bf16[1,16384,4096]{2,1,0} %a, bf16[1,2,1280,128]{3,2,1,0} %b"
              + tail)
    attend = ("%sparse_attn.2 = bf16[1,16384,4096]{2,1,0} custom-call("
              "s32[1,2,256,16384]{3,2,1,0} %m, bf16[1,16384,4096]{2,1,0} %a, "
              "bf16[1,16384,256]{2,1,0} %b, bf16[1,16384,256]{2,1,0} %c"
              + tail)
    assert xplane.op_name(lightning, kernels) == "lightning_attn.5"
    assert xplane.op_name(select, kernels) == "sparse_select.1"
    assert xplane.op_name(attend, kernels) == "sparse_attn.2"
    forward = {"ops": [("sparse_attn.2", 0.0, 1.0), ("sparse_attn.7", 1.0, 2.0),
                       ("sparse_select.1", 3.0, 4.0)]}
    assert traced_forwards.kernel_seconds(forward, "sparse_attn") == 3.0
    assert traced_forwards.kernel_seconds(forward, "sparse_select") == 4.0


# ---- the manifest and the planted faults ----------------------------------

def test_the_manifest_appends_the_cell_and_its_metrics():
    m = manifest()
    assert m["workloads"][-1]["name"] == CELL
    assert m["workloads"][-1] == dict(
        m["workloads"][-1], config="minicpm-sala-l8",
        traffic="prefill_9k_32k_openloop", chips=1)
    assert m["configs"][-1]["name"] == "minicpm-sala-l8"
    lists = {e["name"]: e["workloads"] for e in m["per_layer"]}
    assert [n for n, w in lists.items() if CELL in w][-4:] == [
        "serve_mfu_pct.sala", "lightning_attn_roofline.serve",
        "sparse_attn_roofline.serve", "mixers_device_pct"]
    assert all(lists[n] == [CELL] for n in list(lists)[-4:])
    assert sum(CELL in w for w in lists.values()) == 13 + 4
    for name in ("flash_fwd_roofline.serve", "serve_mfu_pct",
                 "serve_mfu_pct.afmoe", "moe_gmm_roofline.serve",
                 "moe_gmm_device_pct", "moe_rows_held_share"):
        assert CELL not in lists[name]
    ttft = next(e for e in m["end_to_end"]
                if e["name"] == "serve_ttft_p50_ms")
    assert ttft["workloads"][-1] == CELL


def test_the_planted_faults_stand_apart_from_the_reference():
    """`no_select` and `no_decay` against `f32` on one prompt past
    `dense_len`, and `no_select` equal to it under `dense_len`, where
    nothing is selected."""
    import jax
    import jax.numpy as jnp
    sz = ref.Sizes.from_config(dict(cell_config(), **TINY))
    weights = ref.make_weights(ref.seed_key(11), sz)
    logits = jax.jit(ref.logits_at, static_argnums=(3, 4))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 96), 0, 96)
    last = jnp.array([95])
    right = logits(weights, tokens, last, sz, "f32")
    for mode in ("no_select", "no_decay", "int8"):
        gap = float(jnp.max(jnp.abs(logits(weights, tokens, last, sz, mode)
                                    - right)))
        assert gap > 0.05, (mode, gap)
    short, at = tokens[:, :24], jnp.array([23])
    np.testing.assert_array_equal(
        logits(weights, short, at, sz, "no_select"),
        logits(weights, short, at, sz, "f32"))
    with pytest.raises(ValueError, match="no mode"):
        ref.logits_at(weights, short, at, sz, "bf16")
