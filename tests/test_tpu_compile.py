"""Ask the TPU compiler, without a TPU, whether the main path's kernels
compile at real widths (guide on-chip-measurement §2.3).

libtpu compiles for a chip that is described and not attached, so these
catch what interpret mode cannot: misaligned slices, too much VMEM, a
Mosaic kernel the partitioner refuses, and a TPU program that carries
the Pallas interpreter in place of the kernel. A compile that passes is
not a chip run — ``python chip_smoke.py`` is.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from ray_tpu.ops import flash_attention, make_attention_fn
from ray_tpu.parallel.mesh import MeshSpec, make_mesh


@pytest.fixture(scope="module")
def v5e():
    """The four described devices of a v5e 2x2 host. The persistent
    compile cache is off around these compiles: an entry written
    without a chip cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:    # no libtpu here: nothing to ask
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _assert_kernel_not_interpreter(lowered):
    text = lowered.as_text()
    assert "tpu_custom_call" in text
    assert "stablehlo.while" not in text    # the interpreter's grid loop
    lowered.compile()


# the benchmark's cells, (batch, tokens, query heads, head size) and
# their 8 KV heads: the train cell's attention with gradients and a
# serve prompt padded to 128, both at the blocks the kernel chooses; the
# long Mistral cell's other two padded lengths at named blocks
TRAIN_CELL, SERVE_CELL = (1, 4096, 32, 128), (1, 128, 32, 128)
LONG_1K, LONG_2K = (1, 1024, 32, 128), (1, 2048, 32, 128)


def _qkv_shapes(shape, kv_heads, device):
    """q at the shape's heads, k and v at ``kv_heads``."""
    one = SingleDeviceSharding(device)
    b, s, _n, h = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((b, s, kv_heads, h), jnp.bfloat16,
                              sharding=one)
    return q, kv, kv


@pytest.mark.parametrize("shape,kv_heads,block,direction", [
    (LONG_1K, 32, 128, "forward"), (LONG_1K, 8, 512, "forward"),
    (LONG_2K, 32, 128, "forward"), (LONG_2K, 8, 512, "forward"),
    (TRAIN_CELL, 32, None, "forward"), (TRAIN_CELL, 32, None, "backward"),
    (TRAIN_CELL, 8, None, "forward"), (TRAIN_CELL, 8, None, "backward"),
    (SERVE_CELL, 32, None, "forward"), (SERVE_CELL, 8, None, "forward"),
    # whole-length K and V past the default VMEM scope, with gradients
    ((1, 8192, 8, 128), 8, None, "backward")])
def test_flash_compiles_for_tpu(v5e, shape, kv_heads, block, direction):
    """K and V at the cells' own 8 KV heads (a group of four query
    heads a grid step), and at the query heads (ring and ulysses call
    the kernel so)."""
    def attend(q, k, v):        # interpret=None: chosen by the lowering
        return flash_attention(q, k, v, True, None, block, block)

    fn = attend if direction == "forward" else jax.grad(
        lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2))
    _assert_kernel_not_interpreter(
        jax.jit(fn).lower(*_qkv_shapes(shape, kv_heads, v5e[0])))


# the Trinity cell's prefills: 48 query heads of 128 over 8 KV heads, a
# group of six a grid step; at 16,384 positions the group's whole-length
# K and V are past the default VMEM scope
@pytest.mark.parametrize("tokens,window", [
    (16384, 4096), (16384, None), (6144, 4096)])
def test_long_windowed_flash_compiles_for_tpu(v5e, tokens, window):
    lowered = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, window=window)
    ).lower(*_qkv_shapes((1, tokens, 48, 128), 8, v5e[0]))
    _assert_kernel_not_interpreter(lowered)
    # what a device profile knows the kernel by: three operands (scratch
    # is none) and a tuple result (benchmark/kernels/flash_fwd.json)
    (call,) = [line for line in lowered.compile().as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    result, _, operands = call.split(" = ", 1)[1].partition(" custom-call(")
    assert result.startswith("(")
    assert operands.split("), custom_call_target")[0].count(" %") + 1 == 3


def _gmm_calls(text):
    """The compiled program's grouped-matmul calls: (instruction name,
    operand count) of each."""
    calls = []
    for line in text.splitlines():
        if "tpu_custom_call" in line and " custom-call(" in line:
            name = line.strip().split(" = ")[0].lstrip("ROT %")
            operands = line.split(" custom-call(")[1].split(
                "), custom_call_target")[0]
            if name.startswith("moe_gmm"):
                calls.append((name, operands.count("%")))
    return calls


def test_grouped_matmul_compiles_for_tpu(v5e):
    """The routed layer's kernel at the cell's widths: every (token,
    expert) row of a 16,384-token prefill, 32 held experts of 3,072 x
    3,072; its HLO instruction carries the kernel's name, which is how
    a device profile knows it."""
    from ray_tpu.ops import gmm
    one = SingleDeviceSharding(v5e[0])
    lhs = jax.ShapeDtypeStruct((65536, 3072), jnp.bfloat16, sharding=one)
    rhs = jax.ShapeDtypeStruct((32, 3072, 3072), jnp.bfloat16, sharding=one)
    rows = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one)
    lowered = jax.jit(gmm).lower(lhs, rhs, rows, rows)
    # (the visits are found by a search, which is a loop of its own: the
    # kernel is told from the interpreter by the compiled call)
    (call,) = _gmm_calls(lowered.compile().as_text())
    assert call[0].startswith("moe_gmm") and call[1] == 7


@pytest.mark.parametrize("tokens", [6144, 8192, 12288, 16384])
def test_routed_layer_compiles_with_its_ladder(v5e, tokens):
    """The Trinity cell's routed layer (32 of 256 experts of 3,072 x
    3,072, top-4) at the cell's four padded lengths: one conditional
    over three rungs, and in each branch the three grouped matmuls under
    the kernel's name, 7 operands to one result, which is how a device
    profile knows them."""
    from ray_tpu.ops.moe import routed_experts
    one = SingleDeviceSharding(v5e[0])

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)

    text = jax.jit(lambda *a: routed_experts(
        *a, held=(0, 32), top_k=4, route_scale=2.448)).lower(
        arg(tokens, 3072), arg(3072, 256), arg(256), arg(32, 3072, 3072),
        arg(32, 3072, 3072), arg(32, 3072, 3072)).compile().as_text()
    assert text.count(" conditional(") == 1
    calls = _gmm_calls(text)
    assert len(calls) == 9 and {n for _name, n in calls} == {7}
    # every rung's operands are there: rows / 4, rows / 2, rows
    for rung in (tokens, 2 * tokens, 4 * tokens):
        assert f"bf16[{rung},3072]" in text


def test_trinity_prefill_fits_as_before_the_ladder(v5e):
    """The cell's longest program whole (16,384 tokens through one dense
    and four routed layers, weights in bfloat16): a conditional a routed
    layer, its branches sharing their temporaries, so libtpu's total
    stays at the parent's 10.785 GB (8.644 of weights + 2.141 of
    temporaries; PERF.md §6, PR 31) but for the conditional's result,
    which is held while a branch runs."""
    import dataclasses
    import json

    from ray_tpu.models import config_from_hf, forward_with_stats, init_params
    here = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs", "trinity-large-preview-l5-ep8.json")
    with open(here) as f:
        config = json.load(f)
    one = SingleDeviceSharding(v5e[0])
    cfg = dataclasses.replace(config_from_hf(config, 16384), use_flash=True,
                              remat=False)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=one),
        jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0)))

    def answer(p, t, last):     # as benchmark/drivers/serve_prefill.py asks
        logits, stats = forward_with_stats(p, t, cfg, logit_positions=last)
        return jax.lax.top_k(logits[0], 8), stats["moe_rows"]

    compiled = jax.jit(answer).lower(
        params, jax.ShapeDtypeStruct((1, 16384), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one)).compile()
    text = compiled.as_text()
    assert text.count(" conditional(") == 4
    assert len(_gmm_calls(text)) == 4 * 9
    memory = compiled.memory_analysis()
    total = (memory.argument_size_in_bytes + memory.output_size_in_bytes
             - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    assert memory.argument_size_in_bytes == 8_643_876_352
    assert total <= 10_785_099_264 + 2 * 16384 * 3072     # one [T, D] more


def _cell_cfg(name, tokens):
    """A serve cell's configuration as its driver runs it."""
    import dataclasses
    import json

    from ray_tpu.models import config_from_hf
    here = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs", f"{name}.json")
    with open(here) as f:
        config = json.load(f)
    return dataclasses.replace(config_from_hf(config, tokens), use_flash=True,
                               remat=False)


def _pallas_calls(text):
    """(name, operands, whether the result is a tuple) of each Pallas
    call in a compiled program's text: what a device profile knows a
    kernel by (``benchmark/xplane.py::op_name``)."""
    calls = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        head, _, rest = line.partition(" = ")
        result, _, call = rest.partition(" custom-call(")
        operands = call.split("), custom_call_target")[0].count("%")
        calls.append((head.strip().lstrip("%").rsplit(".", 1)[0], operands,
                      result.startswith("(")))
    return calls


# the MiniCPM-SALA cell: 32 query heads of 128, 32 KV heads on a
# lightning layer and 2 on a sparse one, its shortest and longest program
@pytest.mark.parametrize("tokens", [12288, 32768])
def test_the_mixers_kernels_compile_for_tpu(v5e, tokens):
    """The chunked scan with its state in VMEM, the choice of blocks and
    the attention over them (a group's K and V whole in VMEM: 16 MB at
    32,768 tokens), each a kernel the program names."""
    from ray_tpu.models import SparseSizes
    from ray_tpu.ops.lightning_attention import (
        decay_slopes, lightning_attention)
    from ray_tpu.ops.sparse_attention import selected_attention
    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((1, tokens, 32, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, tokens, 2, 128), jnp.bfloat16,
                              sharding=one)
    scan = jax.jit(lambda q, k, v: lightning_attention(
        q, k, v, decay_slopes(32))).lower(q, q, q)
    _assert_kernel_not_interpreter(scan)
    assert "lightning_attn" in scan.as_text()
    # as the cell's layers call it: the q/k norm's scales and RoPE's
    # tables and pair swap beside q, k and v
    scales = jax.ShapeDtypeStruct((2, 128), jnp.bfloat16, sharding=one)
    table = jax.ShapeDtypeStruct((1, tokens, 128), jnp.float32, sharding=one)
    swap = jax.ShapeDtypeStruct((128, 128), jnp.bfloat16, sharding=one)
    scan = jax.jit(lambda q, k, v, s, *rope: lightning_attention(
        q, k, v, decay_slopes(32), qk_scales=s, rope=rope)).lower(
            q, q, q, scales, table, table, swap)
    _assert_kernel_not_interpreter(scan)
    assert _pallas_calls(scan.compile().as_text()) == [
        ("lightning_attn", 8, False)]
    sparse = jax.jit(lambda q, k, v: selected_attention(
        q, k, v, SparseSizes())).lower(q, kv, kv)
    _assert_kernel_not_interpreter(sparse)
    assert [c[0] for c in _pallas_calls(sparse.compile().as_text())] == [
        "sparse_select", "sparse_attn"]


def test_minicpm_sala_prefill_fits_one_chip(v5e):
    """The cell's longest program whole (32,768 tokens through two
    sparse and six lightning layers at published widths, the whole
    vocabulary, weights in bfloat16): 5.64 GB of weights and what the
    forward holds beside them stay under the chip's 16 GB."""
    from ray_tpu.models import forward_with_stats, init_params
    one = SingleDeviceSharding(v5e[0])
    cfg = _cell_cfg("minicpm-sala-l8", 32768)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=one),
        jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0)))

    def answer(p, t, last):     # as benchmark/drivers/serve_prefill.py asks
        logits, stats = forward_with_stats(p, t, cfg, logit_positions=last)
        return jax.lax.top_k(logits[0], 8), stats["moe_rows"]

    compiled = jax.jit(answer).lower(
        params, jax.ShapeDtypeStruct((1, 32768), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one)).compile()
    text = compiled.as_text()
    for kernel, calls in (("lightning_attn", 6), ("sparse_select", 2),
                          ("sparse_attn", 2)):
        assert sum(1 for line in text.splitlines()
                   if line.lstrip().startswith(f"%{kernel}.")
                   and "tpu_custom_call" in line) == calls, kernel
    memory = compiled.memory_analysis()
    total = (memory.argument_size_in_bytes + memory.output_size_in_bytes
             - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    # the weights, the tokens and a position
    assert 0 <= memory.argument_size_in_bytes - 2 * 2_820_569_088 < 2 ** 18
    assert total < 13e9, total


# the EvaByte cell: 32 heads of 128, window 2,048, chunk 16, its
# shortest and longest program
@pytest.mark.parametrize("tokens", [12288, 32768])
def test_the_eva_kernels_compile_for_tpu(v5e, tokens):
    """The summaries (a window of K and V a step, its 128 summaries
    written) and the attention over a window's keys and every summary
    before it (two heads' window and summaries in VMEM), each a kernel
    the program names."""
    from ray_tpu.ops.eva_attention import eva_attention
    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((1, tokens, 32, 128), jnp.bfloat16, sharding=one)
    vec = jax.ShapeDtypeStruct((32, 128), jnp.bfloat16, sharding=one)
    lowered = jax.jit(lambda q, k, v, phi, mu: eva_attention(
        q, k, v, phi, mu, 2048, 16)).lower(q, q, q, vec, vec)
    _assert_kernel_not_interpreter(lowered)
    text = lowered.compile().as_text()
    calls = [line.split(" = ")[0].strip().lstrip("%") for line in
             text.splitlines() if 'custom_call_target="tpu_custom_call"'
             in line]
    assert [c.rsplit(".", 1)[0] for c in calls] == ["eva_summaries",
                                                    "eva_attn"]


def test_evabyte_prefill_fits_one_chip(v5e):
    """The cell's longest program whole (32,768 bytes through sixteen
    eva layers at published widths, eight heads of 320, weights in
    bfloat16): 6.50 GB of weights and what the forward holds beside
    them stay under the chip's 16 GB, and sixteen equal layers call one
    lowering of the layer."""
    from ray_tpu.models import forward_with_stats, init_params
    one = SingleDeviceSharding(v5e[0])
    cfg = _cell_cfg("evabyte-l16", 32768)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=one),
        jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0)))

    def answer(p, t, last):     # as benchmark/drivers/serve_prefill.py asks
        logits, stats = forward_with_stats(p, t, cfg, logit_positions=last)
        return jax.lax.top_k(logits[0], 8), stats["moe_rows"]

    lowered = jax.jit(answer).lower(
        params, jax.ShapeDtypeStruct((1, 32768), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one))
    # one function a kind of layer: the kernels' bodies lower once
    assert lowered.as_text().count("tpu_custom_call") < 16
    compiled = lowered.compile()
    text = compiled.as_text()
    for kernel in ("eva_summaries", "eva_attn"):
        assert sum(1 for line in text.splitlines()
                   if line.lstrip().startswith(f"%{kernel}.")
                   and "tpu_custom_call" in line) == 16, kernel
    memory = compiled.memory_analysis()
    total = (memory.argument_size_in_bytes + memory.output_size_in_bytes
             - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    # the weights, the tokens and a position
    assert 0 <= memory.argument_size_in_bytes - 2 * 3_250_065_408 < 2 ** 18
    assert total < 10e9, total


# q and k of the EvaByte cell at 16,384 bytes, q of the Trinity cell at
# 12,288 tokens, k of the long Mistral cell at 4,096
@pytest.mark.parametrize("shape", [(1, 16384, 32, 128), (1, 12288, 48, 128),
                                   (1, 4096, 8, 128)])
def test_rope_compiles_without_a_gather(v5e, shape):
    """RoPE's pair swap is a product with a constant permutation: the
    compiled program holds no gather (what a stride-2 slice of the
    lanes becomes on a TPU) and passes the tensor at most 3.5 times its
    input plus output (2.6-3.0 x; the sliced form read 7.1-8.2 x:
    PERF.md §6, PR 36)."""
    from ray_tpu.models.transformer import rope
    one = SingleDeviceSharding(v5e[0])
    compiled = jax.jit(lambda x, p: rope(x, p, 10_000.0)).lower(
        jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one),
        jax.ShapeDtypeStruct(shape[:2], jnp.int32, sharding=one)).compile()
    assert " gather(" not in compiled.as_text()
    in_and_out = 2 * 2 * shape[1] * shape[2] * shape[3]
    assert compiled.cost_analysis()["bytes accessed"] <= 3.5 * in_and_out


def test_an_evabyte_layer_passes_memory_as_counted(v5e):
    """One layer of the EvaByte cell at 16,384 bytes: the projections
    write q and k once and one fusion each (the small product, the
    multiply-add in its epilogue) writes them heads-first for the
    kernel: 7.77 GB of ``bytes accessed`` (11.15 GB with RoPE's
    gathers and the twenty relayouts round them: PERF.md §6, PR 36)."""
    import functools

    from ray_tpu.models import init_params
    from ray_tpu.models.transformer import _layer_forward
    one = SingleDeviceSharding(v5e[0])
    cfg = _cell_cfg("evabyte-l16", 16384)
    block = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=one),
        jax.eval_shape(lambda k: init_params(k, cfg)["blocks"][0],
                       jax.random.PRNGKey(0)))
    layer = functools.partial(_layer_forward, spec=cfg.layers[0], cfg=cfg,
                              attn_fn=None)     # an eva layer calls none
    compiled = jax.jit(layer).lower(
        block, jax.ShapeDtypeStruct((1, 16384, cfg.d_model), jnp.float32,
                                    sharding=one),
        jax.ShapeDtypeStruct((1, 16384), jnp.int32, sharding=one)).compile()
    assert " gather(" not in compiled.as_text()
    assert compiled.cost_analysis()["bytes accessed"] < 8.5e9


def test_a_lightning_layer_passes_q_and_k_once(v5e):
    """One lightning layer of the MiniCPM-SALA cell at 16,384 tokens:
    the projections write q and k where the kernel reads them, and the
    kernel norms and rotates its tiles in VMEM: 7.06 GB of ``bytes
    accessed`` (12.24 GB with the norm, RoPE's product and four
    relayouts of q and k in XLA: PERF.md §6, PR 38). A device profile
    knows a flash kernel by its operands and result
    (``benchmark/kernels/``): this call may read as none of them."""
    import functools

    from ray_tpu.models import init_params
    from ray_tpu.models.transformer import _layer_forward
    one = SingleDeviceSharding(v5e[0])
    cfg = _cell_cfg("minicpm-sala-l8", 16384)
    assert cfg.layers[1].mixer == "lightning"
    block = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=one),
        jax.eval_shape(lambda k: init_params(k, cfg)["blocks"][1],
                       jax.random.PRNGKey(0)))
    layer = functools.partial(_layer_forward, spec=cfg.layers[1], cfg=cfg,
                              attn_fn=None)     # a lightning layer calls none
    compiled = jax.jit(layer).lower(
        block, jax.ShapeDtypeStruct((1, 16384, cfg.d_model), jnp.bfloat16,
                                    sharding=one),
        jax.ShapeDtypeStruct((1, 16384), jnp.int32, sharding=one)).compile()
    text = compiled.as_text()
    assert " gather(" not in text
    assert compiled.cost_analysis()["bytes accessed"] < 10.0e9
    (name, operands, tuple_result), = _pallas_calls(text)
    assert name == "lightning_attn"
    assert (operands, tuple_result) not in {(6, False), (3, True), (6, True)}


# the Kimi-Linear cell: 32 heads of 128 on a kda layer; on an mla layer
# 32 heads whose keys are 192 wide and whose values are 128
def test_the_delta_rule_and_the_wide_keyed_flash_compile_for_tpu(v5e):
    """At 16,384 tokens: the delta-rule kernel (its state, the decays'
    sums, the triangular system and the pairs in VMEM; five operands
    and one result, none of the signatures a device profile reads as a
    flash kernel) and the flash forward at K 192 / V 128 (three
    operands and a tuple: the flash forward's own; no padded copy of q
    or k: its operands keep their 192 lanes)."""
    from ray_tpu.ops.kda_attention import kda_attention
    one = SingleDeviceSharding(v5e[0])
    wide = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16,
                                sharding=one)
    decays = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.float32,
                                  sharding=one)
    steps = jax.ShapeDtypeStruct((1, 16384, 32), jnp.float32, sharding=one)
    scan = jax.jit(kda_attention).lower(wide, wide, wide, decays, steps)
    _assert_kernel_not_interpreter(scan)
    (call,) = _pallas_calls(scan.compile().as_text())
    assert call == ("kda_attn", 5, False)
    assert call[1:] not in {(6, False), (3, True), (6, True)}
    keys = jax.ShapeDtypeStruct((1, 16384, 32, 192), jnp.bfloat16,
                                sharding=one)
    flash = jax.jit(lambda q, k, v: flash_attention(q, k, v)).lower(
        keys, keys, wide)
    _assert_kernel_not_interpreter(flash)
    text = flash.compile().as_text()
    ((_name, operands, tuple_result),) = _pallas_calls(text)
    assert (operands, tuple_result) == (3, True)
    (line,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert line.count("bf16[1,32,16384,192]") == 2
    assert "16384,256]" not in text


def test_a_kda_layer_s_bytes_are_the_starting_point(v5e):
    """The dense kda layer of the Kimi-Linear cell at 16,384 tokens,
    compiled alone: 11.2 GB of ``bytes accessed`` (PERF.md §6, PR 40),
    of which the kernel's own operands are 1.0 and the SwiGLU some 1.5:
    the yardstick for moving the convolutions, the gates, the L2 norms
    and the output norm out of XLA. (A routed layer's count holds every
    rung of the experts' ladder and says nothing.) And one mla layer,
    its flash call the forward's."""
    import functools

    from ray_tpu.models import init_params
    from ray_tpu.models.transformer import _layer_forward
    one = SingleDeviceSharding(v5e[0])
    cfg = _cell_cfg("kimi-linear-48b-a3b-l13-ep8", 16384)
    assert (cfg.layers[0].mixer, cfg.layers[3].mixer) == ("kda", "mla")
    assert not cfg.layers[0].experts
    from ray_tpu.ops.flash_attention import flash_attention as flash
    found = {}
    for index in (0, 3):
        block = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16,
                                           sharding=one),
            jax.eval_shape(lambda k: init_params(k, cfg)["blocks"][index],
                           jax.random.PRNGKey(0)))
        layer = functools.partial(
            _layer_forward, spec=cfg.layers[index], cfg=cfg,
            attn_fn=lambda q, k, v: flash(q, k, v))
        compiled = jax.jit(layer).lower(
            block, jax.ShapeDtypeStruct((1, 16384, cfg.d_model),
                                        jnp.bfloat16, sharding=one),
            jax.ShapeDtypeStruct((1, 16384), jnp.int32,
                                 sharding=one)).compile()
        calls = _pallas_calls(compiled.as_text())
        found[cfg.layers[index].mixer] = (
            [c for c in calls if c[0] != "moe_gmm"],
            compiled.cost_analysis()["bytes accessed"])
    assert found["kda"][0] == [("kda_attn", 5, False)]
    assert [c[1:] for c in found["mla"][0]] == [(3, True)]
    assert found["kda"][1] < 12.5e9


def test_flash_compiles_under_a_mesh(v5e):
    """The partitioner refuses a bare Mosaic kernel; under a mesh the
    kernel runs per device on its batch/head shard."""
    mesh = make_mesh(MeshSpec(fsdp=2, tp=2), v5e)
    q, kv = (jax.ShapeDtypeStruct(
        (2, 4096, heads, 128), jnp.bfloat16,   # the 2x2 cell's step
        sharding=NamedSharding(mesh, P(("dp", "fsdp"), None, "tp", None)))
        for heads in (32, 8))
    attend = make_attention_fn(mesh, impl="flash")
    _assert_kernel_not_interpreter(jax.jit(attend).lower(q, kv, kv))


def test_scheduler_kernel_compiles_for_tpu(v5e):
    from ray_tpu._private.scheduler.tpu_policy import _schedule_classes_kernel
    one = SingleDeviceSharding(v5e[0])
    n, k, r = 1024, 8, 4

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    _schedule_classes_kernel.lower(
        arg((n, r), jnp.float32), arg((n, r), jnp.float32),
        arg((n,), jnp.bool_), arg((k, r), jnp.float32),
        arg((k,), jnp.int32), arg((k,), jnp.int32), arg((), jnp.float32),
        num_classes=k).compile()


@pytest.mark.parametrize("limit", [16e9, None])
def test_train_step_fits_as_the_remat_plan_counts(v5e, limit):
    """The benchmark's train cell whole (3 layers at published
    Mistral-7B widths, 1 x 4,096 tokens, f32 Adam) under the plan that
    ``remat_plan`` gives for a 16 GB chip (benchmark/costs.CHIP_PEAKS)
    and under "recompute every layer", which a device with no memory
    figure gets: libtpu's total stands within the plan's margin of the
    plan's own count, and under the limit. An activation that grows in
    the layer fails here and not on the chip."""
    from ray_tpu.models import (
        TransformerConfig, init_state, make_optimizer, make_train_step)
    from ray_tpu.models.transformer import (
        KEEP_LAYER, REMAT_MARGIN, remat_plan)
    one = SingleDeviceSharding(v5e[0])
    cfg = TransformerConfig(
        vocab_size=32_000, d_model=4096, n_layers=3, n_heads=32,
        n_kv_heads=8, d_ff=14_336, max_seq_len=4096, dtype=jnp.bfloat16,
        remat=True, use_flash=True)
    tx = make_optimizer(lr=3e-4, weight_decay=0.1, warmup_steps=0,
                        total_steps=10_000)
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(lambda k: init_state(k, cfg, tx),
                       jax.random.PRNGKey(0)))
    held = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(state))
    plan = remat_plan(cfg, 1, 4096, held, limit)
    assert plan.levels == ((KEEP_LAYER,) * 3 if limit else (0, 0, 0))
    tokens = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one)
    step = make_train_step(cfg, tx, donate=True, remat_levels=plan.levels)
    memory = step.lower(state, {"tokens": tokens}).compile().memory_analysis()
    total = (memory.argument_size_in_bytes + memory.output_size_in_bytes
             - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    # 14.090 GB kept whole, 13.010 GB recomputed (PERF.md §6, PR 34)
    assert abs(total - plan.peak_bytes) < REMAT_MARGIN * 16e9
    assert total + memory.generated_code_size_in_bytes < 16e9
