"""Compile for the v5e without one: the TPU's compiler is installed
here and compiles for a chip that is described, not attached. Nothing
runs, so nothing here says a result is right or fast; what it catches
is what the interpreter cannot: a kernel Mosaic refuses at the real
widths, and a program that copies the cache.

The topology is described inside a fixture and never at import (one
process at a time may load the TPU's library); keep such tests in this
one file.
"""

import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _layer_loop():
    # scripts/layer_loop.py: the lowering and the reading of a loop's body
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "layer_loop", os.path.join(ROOT, "scripts", "layer_loop.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def no_compile_cache():
    # an entry written by such a compile cannot be read back without a chip
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


# Moonlight's latent cache as one chip serves it (benchmark/configs/
# moonlight-16b-a3b.json): 9 layers, 3072 blocks of 16, latent 512, rope
# key 64 lane-padded to 128, 64 rows, 16 heads; tp=4 leaves 4 heads
@pytest.mark.parametrize("kv_dtype,heads,width", [
    ("bfloat16", 16, 256), ("bfloat16", 4, 64), ("float8_e4m3fn", 16, 128)])
def test_mla_decode_kernel_compiles_at_moonlights_widths(
        one_chip, no_compile_cache, kv_dtype, heads, width):
    from dynamo_tpu.ops.pallas_decode import mla_paged_decode_attention

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    layers, blocks, page, r, rd, b = 9, 3072, 16, 512, 128, 64
    kv = jnp.dtype(kv_dtype)

    def f(ql, qr, c, kr, bt, ctx, li):
        return mla_paged_decode_attention(ql, qr, c, kr, bt, ctx,
                                          layer_idx=li, scale=192 ** -0.5)

    compiled = jax.jit(f).lower(
        s((b, 1, heads, r), jnp.bfloat16), s((b, 1, heads, rd), jnp.bfloat16),
        s((layers, blocks, 1, page, r), kv), s((layers, blocks, 1, page, rd), kv),
        s((b, width), jnp.int32), s((b,), jnp.int32), s((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_moonlight_decode_step_reads_the_cache_in_place(
        one_chip, no_compile_cache, monkeypatch):
    """The whole trunk of a decode step at the benchmark's size, on the
    routes the chip takes (steered here, in the test: the program asks
    jax.default_backend()): the MLA decode kernel and the grouped
    products are in it, and the latent cache is written and read where
    it lies. A layout the kernel and the scatter disagree on would show
    as a cache-sized temporary (the cache is 0.57 GB)."""
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.models import deepseek

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "moonlight-16b-a3b.json")) as f:
        hf = json.load(f)
    cfg = ModelConfig.from_hf_config(hf)
    serve = hf["serve"]

    def s(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(s, jax.eval_shape(
        lambda: deepseek.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    c, kr = jax.tree.map(s, jax.eval_shape(
        lambda: deepseek.init_kv_cache(cfg, serve["num_kv_blocks"], 16,
                                       jnp.bfloat16)))
    b, w = serve["max_batch_size"], 256

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def step(params, c, kr, tokens, positions, bt, slots, ctx):
        return deepseek.forward_counted(params, cfg, tokens, positions,
                                        (c, kr), bt, slots, ctx)

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, c, kr, i32(b, 1), i32(b, 1), i32(b, w), i32(b, 1), i32(b)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"mla_cache/[^\n]*tpu_custom_call|tpu_custom_call[^\n]*mla_cache", text)) >= 1
    assert text.count("tpu_custom_call") >= 4      # the kernel + three products
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


# Falcon-H1-34B's attention branch as one chip serves it (benchmark/
# configs/falcon-h1-34b.json): 20 query heads over 4 kv heads of 128, a
# query group of 5, which no other configuration runs
def test_decode_and_flash_kernels_compile_at_falcon_h1s_heads(
        one_chip, no_compile_cache):
    from dynamo_tpu.ops.attention import attention

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    layers, blocks, page, h, kvh, d = 6, 3072, 16, 20, 4, 128
    cache = s((layers, blocks, page, kvh, d), jnp.bfloat16)

    def f(q, k, v, bt, pos, ctx, li):
        return attention(q, k, v, bt, pos, ctx, impl="pallas", layer_idx=li)

    for b, sq, w in ((64, 1, 256), (4, 256, 256), (1, 2048, 256)):
        compiled = jax.jit(f).lower(
            s((b, sq, h, d), jnp.bfloat16), cache, cache, s((b, w), jnp.int32),
            s((b, sq), jnp.int32), s((b,), jnp.int32), s((), jnp.int32)).compile()
        assert "tpu_custom_call" in compiled.as_text(), (b, sq)


# Falcon-H1-34B's mixer state as one chip serves it: 6 layers x 64 slots
# of 32 tiles [256, 128] (a head a tile, P on the lanes), a group's 16
# heads a block; float32 as the configuration keeps it, and bfloat16 as
# a records buffer may come
@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16"])
def test_ssm_decode_kernel_compiles_at_falcon_h1s_state(
        one_chip, no_compile_cache, monkeypatch, state_dtype):
    from dynamo_tpu.ops import ssm
    from dynamo_tpu.ops.live_rows import live_row_list

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    layers, slots, h, p, n, g = 6, 64, 32, 128, 256, 2
    f32, act = jnp.float32, jnp.bfloat16
    record = ssm.record_shape(h, p, n, h // g)
    assert record == (32, 256, 128) and ssm.lane_heads(p, h // g) == 1

    def f(x, dt, a, bm, cm, d, records, li, live):
        return ssm.ssm_decode_step(x, dt, a, bm, cm, d, records, li,
                                   live_row_list(live))

    compiled = jax.jit(f, donate_argnums=(6,)).lower(
        s((slots, h, p), act), s((slots, h), f32), s((h,), f32),
        s((slots, g, n), act), s((slots, g, n), act), s((h,), f32),
        s((layers, slots) + record, jnp.dtype(state_dtype)), s((), jnp.int32),
        s((slots,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # a block is a group's 16 tiles of float32 (2 MiB), one group of B
    # and C; of bfloat16 the row's 32 tiles, both groups
    itemsize = jnp.dtype(state_dtype).itemsize
    traced, = [t for t in ssm.blocks_traced() if {
        "heads": h, "p": p, "n": n, "itemsize": itemsize}.items() <= t.items()]
    assert (traced["tiles_per_block"], traced["groups_per_block"]) == \
        ((16, 1) if itemsize == 4 else (32, 2))
    assert traced["block_bytes"] == 2 << 20
    # the records are the kernel's output where they lay: no second buffer
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_falcon_h1_decode_step_updates_state_and_cache_in_place(
        one_chip, no_compile_cache, monkeypatch):
    """The whole trunk of a decode step at the benchmark's size, on the
    routes the chip takes: the attention decode kernel and the mixer's
    state kernel (ops/ssm.ssm_decode_step, its records aliased in to
    out) are in it, and neither the recurrent state (1.62 GB at 64
    slots) nor the pages (0.60 GB) are copied: no operation but the
    kernel and the loop that carries it makes an array of the state's
    shape, and the program's temporaries stay far under either."""
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.models import falcon_h1

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "falcon-h1-34b.json")) as f:
        hf = json.load(f)
    cfg = ModelConfig.from_hf_config(hf)
    serve = hf["serve"]

    def s(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(s, jax.eval_shape(
        lambda: falcon_h1.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    b, w = serve["max_batch_size"], 256
    k_side, v_side = jax.tree.map(s, jax.eval_shape(
        lambda: falcon_h1.init_kv_cache(cfg, serve["num_kv_blocks"], 16,
                                        jnp.bfloat16, num_slots=b)))
    assert k_side.state.shape == (6, 64, 32, 256, 128)
    assert k_side.state.dtype == jnp.float32

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def step(params, k_side, v_side, tokens, positions, bt, slots, ctx):
        return falcon_h1.forward(params, cfg, tokens, positions,
                                 (k_side, v_side), bt, slots, ctx,
                                 return_hidden=True)

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, k_side, v_side, i32(b, 1), i32(b, 1), i32(b, w), i32(b, 1),
        i32(b)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # every operation that takes or makes the state: the loop over the
    # layers and what hands the buffer on, and the kernel; never a copy,
    # a fusion, a dynamic-update-slice or a scatter of it
    state = "f32[6,64,32,256,128]"
    ops = [(re.search(r" ([a-z][a-z\-]*)\(", ln.split(" = ", 1)[1]).group(1), ln)
           for ln in text.splitlines()[1:] if state in ln and " = " in ln]
    assert {op for op, _ in ops} <= {
        "parameter", "get-tuple-element", "tuple", "while", "bitcast",
        "custom-call"}, {op for op, _ in ops}
    kernel = [ln for op, ln in ops if op == "custom-call"]
    assert len(kernel) == 1 and "ssm_decode_step" in kernel[0].split(" = ")[0]
    # result 1 is operand 6 (the grid's bound, two scalars, four blocks)
    assert re.search(r"output_to_operand_aliasing=\{[^=]*\{1\}: \(6, \{\}\)",
                     kernel[0]), kernel[0][-800:]
    mem = compiled.memory_analysis()
    print("decode step: arguments", mem.argument_size_in_bytes,
          "temporaries", mem.temp_size_in_bytes)
    # one layer's state of all slots is 268 MB; the step's temporaries
    # are a few activations (1.1 MB at PR 34)
    assert mem.temp_size_in_bytes < 16 * 2 ** 20
    # embedding 2.67 GB + six layers 5.16 + state 1.62 + pages 0.60 (the
    # head, 2.67 GB more, is the step's and not the trunk's)
    assert 9.9e9 < mem.argument_size_in_bytes < 10.3e9


# Xing4.0-29B-A4B as one chip serves it (benchmark/configs/
# xing4-29b-a4b.json): the MLA decode kernel at 32 heads over 7 layers
# of latent cache, the grouped products at a contraction of 3584 (whole:
# ops/grouped_matmul._tiling) and of 1024, and the Sinkhorn kernel at a
# decode step's 64 tokens and a prefill step's 8192
def test_mla_decode_kernel_compiles_at_xing4s_32_heads(one_chip, no_compile_cache):
    from dynamo_tpu.models.deepseek import mla_softmax_scale
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.ops.pallas_decode import mla_paged_decode_attention

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4-29b-a4b.json")) as f:
        cfg = ModelConfig.from_hf_config(json.load(f))
    # YaRN, factor 64, mscale_all_dim 1: (0.1 ln 64 + 1)^2 = 2.005 on the scale
    assert mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * 1.41589 ** 2, rel=1e-4)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    layers, blocks, page, r, rd, b, heads, width = 7, 3072, 16, 512, 128, 64, 32, 256

    def f(ql, qr, c, kr, bt, ctx, li):
        return mla_paged_decode_attention(ql, qr, c, kr, bt, ctx, layer_idx=li,
                                          scale=mla_softmax_scale(cfg))

    compiled = jax.jit(f).lower(
        s((b, 1, heads, r), jnp.bfloat16), s((b, 1, heads, rd), jnp.bfloat16),
        s((layers, blocks, 1, page, r), jnp.bfloat16),
        s((layers, blocks, 1, page, rd), jnp.bfloat16),
        s((b, width), jnp.int32), s((b,), jnp.int32), s((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [256, 8192])
@pytest.mark.parametrize("k,n", [(3584, 1024), (1024, 3584)])
def test_grouped_products_compile_at_xing4s_expert_shapes(
        one_chip, no_compile_cache, monkeypatch, rows, k, n):
    from dynamo_tpu.ops import grouped_matmul as gm

    # the tiles the chip timing chose; Moonlight's widths keep theirs
    assert gm._tiling(rows, 3584, 1024) == (128, 3584, 512)
    assert gm._tiling(rows, 1024, 3584) == (128, 1024, 512)
    assert gm._tiling(rows, 2048, 1408) == (128, 2048, 512)
    assert gm._tiling(rows, 1408, 2048) == (128, 1408, 512)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(gm.grouped_matmul).lower(
        s((rows, k), jnp.bfloat16), s((6, 64, k, n), jnp.bfloat16),
        s((64,), jnp.int32), s((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("tokens", [64, 8192])
def test_sinkhorn_kernel_compiles_at_xing4s_steps(
        one_chip, no_compile_cache, monkeypatch, tokens):
    from dynamo_tpu.ops.sinkhorn import sinkhorn

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(lambda z: sinkhorn(z, 4, 20, 1e-6, (-30.0, 30.0))).lower(
        jax.ShapeDtypeStruct((16, tokens), jnp.float32, sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_xing4_decode_step_mixes_its_streams_in_a_few_operations(
        one_chip, no_compile_cache, monkeypatch):
    """The whole trunk of a decode step at the benchmark's size: the MLA
    decode kernel, the three grouped products and the two Sinkhorn
    kernels of a layer are in it, the cache is read where it lies, and
    the mixing around a sublayer stays a handful of operations (left to
    XLA the Sinkhorn iterations alone were 79)."""
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.models import deepseek

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4-29b-a4b.json")) as f:
        hf = json.load(f)
    cfg = ModelConfig.from_hf_config(hf)
    serve = hf["serve"]

    def s(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(s, jax.eval_shape(
        lambda: deepseek.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    c, kr = jax.tree.map(s, jax.eval_shape(
        lambda: deepseek.init_kv_cache(cfg, serve["num_kv_blocks"], 16,
                                       jnp.bfloat16)))
    b, w = serve["max_batch_size"], 256

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def step(params, c, kr, tokens, positions, bt, slots, ctx):
        return deepseek.forward_counted(params, cfg, tokens, positions,
                                        (c, kr), bt, slots, ctx)

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, c, kr, i32(b, 1), i32(b, 1), i32(b, w), i32(b, 1), i32(b)).compile()
    text = compiled.as_text()
    # dense group: MLA + 2 Sinkhorn; expert group: MLA + 2 Sinkhorn + 3 products
    assert text.count("tpu_custom_call") == 9
    assert len(re.findall(r"custom-call\([^\n]*mhc_sinkhorn/mhc_sinkhorn", text)) == 4
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 * 2 ** 20
    # weights without the head 10.14 GB + cache 0.44 GB
    assert 10.4e9 < mem.argument_size_in_bytes < 10.8e9


# MiniCPM-SALA as one chip serves it (benchmark/configs/
# minicpm-sala-9b.json): the state kernel at a group a head (32 tiles of
# [128, 128] float32, a whole row a block: the body walks 32 groups of
# one tile), and the trunk's decode and prefill steps at a table 1152
# pages wide
def test_ssm_decode_kernel_compiles_at_lightning_attentions_state(
        one_chip, no_compile_cache, monkeypatch):
    from dynamo_tpu.ops import ssm
    from dynamo_tpu.ops.live_rows import live_row_list

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    layers, slots, h, d = 9, 24, 32, 128
    f32, act = jnp.float32, jnp.bfloat16
    assert ssm.record_shape(h, d, d, 1) == (h, d, d)
    assert ssm._tile_block(h, 1, d * d * 4) == 32

    def f(x, dt, a, bm, cm, skip, records, li, live):
        return ssm.ssm_decode_step(x, dt, a, bm, cm, skip, records, li,
                                   live_row_list(live))

    compiled = jax.jit(f, donate_argnums=(6,)).lower(
        s((slots, h, d), act), s((slots, h), f32), s((h,), f32),
        s((slots, h, d), act), s((slots, h, d), act), s((h,), f32),
        s((layers, slots, h, d, d), f32), s((), jnp.int32),
        s((slots,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20
    traced, = [t for t in ssm.blocks_traced() if {
        "heads": h, "p": d, "n": d, "heads_per_group": 1}.items() <= t.items()]
    assert traced == {
        "heads": h, "p": d, "n": d, "heads_per_group": 1, "itemsize": 4,
        "lane_heads": 1, "tiles_per_block": 32, "groups_per_block": 32,
        "groups_per_turn": 8, "block_bytes": 2 << 20}


def _sala_step(one_chip, rows, tokens, width):
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.models import minicpm_sala

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "minicpm-sala-9b.json")) as f:
        hf = json.load(f)
    cfg = ModelConfig.from_hf_config(hf)
    serve = hf["serve"]

    def s(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(s, jax.eval_shape(
        lambda: minicpm_sala.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    k_side, v_side = jax.tree.map(s, jax.eval_shape(
        lambda: minicpm_sala.init_kv_cache(
            cfg, serve["num_kv_blocks"], 16, jnp.bfloat16,
            num_slots=serve["max_batch_size"])))
    assert k_side.state.shape == (9, 24, 32, 128, 128)
    assert k_side.kv.shape == (3, 26880 * 2, 16, 128)
    assert v_side.state.shape == (3, 26880 * 2, 128)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def step(params, k_side, v_side, toks, positions, bt, slots, ctx, ss):
        return minicpm_sala.forward(params, cfg, toks, positions,
                                    (k_side, v_side), bt, slots, ctx,
                                    return_hidden=True, state_slots=ss)

    return jax.jit(step, donate_argnums=(1, 2)).lower(
        params, k_side, v_side, i32(rows, tokens), i32(rows, tokens),
        i32(rows, width), i32(rows, tokens), i32(rows), i32(rows)).compile()


@pytest.mark.parametrize("width", [512, 1152])
def test_minicpm_sala_decode_step_keeps_state_and_pages_in_place(
        one_chip, no_compile_cache, monkeypatch, width):
    """A decode step at the benchmark's size on the routes the chip
    takes: the state kernel over the nine lightning layers and the paged
    decode kernel over the kept pages of the three attention layers
    (none selected at a table no wider than dense_len), with neither the
    state (0.45 GB) nor the pages (1.32 GB) copied."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _sala_step(one_chip, 24, 1, width)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    # the selection's top-k and the sort that compacts the kept pages
    selects = re.search(r"(sort|topk|TopK)[^\n]*sparse_select", text)
    assert bool(selects) == (width > 512)
    mem = compiled.memory_analysis()
    print("decode step: arguments", mem.argument_size_in_bytes,
          "temporaries", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < 256 * 2 ** 20
    # embedding 0.60 GB + twelve layers 6.66 + state 0.45 + pages 1.32 +
    # page means 0.08
    assert 9.0e9 < mem.argument_size_in_bytes < 9.3e9


def test_minicpm_sala_prefill_chunk_fits_beside_the_model(
        one_chip, no_compile_cache, monkeypatch):
    """A 2048-token chunk at the full table width: the masked dense
    product's tile of scores and the chunked scan's matrices are the
    step's temporaries, and they fit in what the model leaves."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _sala_step(one_chip, 1, 2048, 1152)
    mem = compiled.memory_analysis()
    print("prefill step: arguments", mem.argument_size_in_bytes,
          "temporaries", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < 3 * 2 ** 30


# Trinity-Mini as one chip serves it (benchmark/configs/
# trinity-mini-26b-a3b.json): eight layers of two kinds of attention,
# each over its own stack of pages (two full layers over 18432 pages,
# six window layers over the derived pool of 2193), two dense and six
# expert feed-forwards of 128 experts of 1024, a table of 1152 pages a
# kind
def _trinity_step(one_chip, rows, tokens, width):
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.models import afmoe

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-mini-26b-a3b.json")) as f:
        hf = json.load(f)
    cfg = ModelConfig.from_hf_config(hf)
    serve = hf["serve"]
    pool = EngineConfig(
        model=cfg, **{k: serve[k] for k in (
            "max_model_len", "max_batch_size", "num_kv_blocks",
            "prefill_buckets", "max_prefill_tokens_per_step",
            "max_prefill_batch")}).window_pool_pages()
    assert pool == 1 + 16 * 129 + 128

    def s(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(s, jax.eval_shape(
        lambda: afmoe.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    k_side, v_side = jax.tree.map(s, jax.eval_shape(
        lambda: afmoe.init_kv_cache(cfg, serve["num_kv_blocks"], 16,
                                    jnp.bfloat16, window_blocks=pool)))
    assert k_side.full.shape == (2, 18432, 16, 4, 128)
    assert v_side.window.shape == (6, 2193, 16, 4, 128)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def step(params, k_side, v_side, toks, positions, bt, slots, ctx):
        return afmoe.forward_counted(params, cfg, toks, positions,
                                     (k_side, v_side), bt, slots, ctx)

    return jax.jit(step, donate_argnums=(1, 2)).lower(
        params, k_side, v_side, i32(rows, tokens), i32(rows, tokens),
        i32(rows, 2 * width), i32(rows, tokens), i32(rows)).compile()


@pytest.mark.parametrize("rows,tokens,width", [
    (16, 1, 512), (16, 1, 1152), (1, 2048, 1152)])
def test_trinity_step_keeps_both_page_stacks_in_place(
        one_chip, no_compile_cache, monkeypatch, rows, tokens, width):
    """A decode step of 16 rows and a 2048-token prefill chunk at the
    benchmark's size on the routes the chip takes: the paged decode (or
    flash) kernel once a kind of layer, the three grouped products of
    the experts, and neither the full kind's pages (1.21 GB) nor the
    window kind's (0.43 GB) copied."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _trinity_step(one_chip, rows, tokens, width)
    text = compiled.as_text()
    for scope in ("kv_window", "kv_full", "moe_experts"):
        assert re.search(rf"tpu_custom_call[^\n]*{scope}", text), scope
    mem = compiled.memory_analysis()
    print(f"trinity step {rows}x{tokens}: arguments",
          mem.argument_size_in_bytes, "temporaries", mem.temp_size_in_bytes)
    # weights 11.97 GB without the head's 0.82 (the trunk ends at the
    # hidden state) + full pages 1.21 + window pages 0.43
    assert 12.7e9 < mem.argument_size_in_bytes < 12.9e9
    # a copy of either stack would be 0.4 GB or more
    assert mem.temp_size_in_bytes < (64 if tokens == 1 else 384) * 2 ** 20


# SDAR-30B-A3B-Chat as one chip serves it (benchmark/configs/
# sdar-30b-a3b-chat.json): seven layers of GQA 32 / 4 with q/k norms over
# 128 experts of 768, 6400 pages, a table of 200; a block pass is 32 rows
# of 8 positions (two blocks a row: the whole one it keeps and the one it
# denoises) through the verify kernel under the block mask, a prefill
# chunk 1024 tokens through the flash kernel under it
def _sdar_step(one_chip, rows, tokens, width):
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.models import sdar

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sdar-30b-a3b-chat.json")) as f:
        hf = json.load(f)
    cfg = ModelConfig.from_hf_config(hf)
    assert cfg.model_family == "sdar" and cfg.block_length == 4

    def s(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(s, jax.eval_shape(
        lambda: sdar.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    k_side, v_side = jax.tree.map(s, jax.eval_shape(
        lambda: sdar.init_kv_cache(cfg, hf["serve"]["num_kv_blocks"], 16,
                                   jnp.bfloat16)))
    assert k_side.shape == (7, 6400, 16, 4, 128)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def step(params, k_side, v_side, toks, positions, bt, slots, ctx):
        return sdar.forward_counted(params, cfg, toks, positions,
                                    (k_side, v_side), bt, slots, ctx)

    return jax.jit(step, donate_argnums=(1, 2)).lower(
        params, k_side, v_side, i32(rows, tokens), i32(rows, tokens),
        i32(rows, width), i32(rows, tokens), i32(rows)).compile()


@pytest.mark.parametrize("rows,tokens,width,kernel", [
    (32, 8, 64, "paged_verify_attention"),
    (32, 8, 200, "paged_verify_attention"),
    (1, 1024, 200, "paged_flash_attention")])
def test_sdar_block_pass_and_prefill_compile_under_the_block_mask(
        one_chip, no_compile_cache, monkeypatch, rows, tokens, width, kernel):
    """A block pass of 32 rows and a 1024-token prefill chunk at the
    benchmark's size on the routes the chip takes: the verify (or flash)
    kernel under the block mask, the pass's call inside scope
    ``block_attn``, the three grouped products of the experts, and the
    pages (1.43 GB) not copied."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _sdar_step(one_chip, rows, tokens, width)
    text = compiled.as_text()
    assert re.search(rf"tpu_custom_call[^\n]*{kernel}", text)
    assert bool(re.search(r"tpu_custom_call[^\n]*block_attn", text)) == (tokens == 8)
    assert re.search(r"tpu_custom_call[^\n]*moe_experts", text)
    mem = compiled.memory_analysis()
    print(f"sdar step {rows}x{tokens}: arguments",
          mem.argument_size_in_bytes, "temporaries", mem.temp_size_in_bytes)
    # weights 9.97 GB without the head's 0.62 + pages 1.43
    assert 10.6e9 < mem.argument_size_in_bytes < 10.9e9
    assert mem.temp_size_in_bytes < (64 if tokens == 8 else 256) * 2 ** 20


# The verify kernel reads a kv head's rows apart from the others' with a
# strided load of the 32-bit words that pack them (PR 49): Mosaic has that
# load for 32 bits alone and the interpreter takes any, so the groupings
# are compiled here at SDAR's page (7 layers x 6400 pages of 16 x 4 x 128,
# a table of 200): bfloat16 heads two to a word and taken apart, fp8
# heads four to a word and folded together, a word row in two or four
@pytest.mark.parametrize("kv_dtype,kvh,s,block_len", [
    ("bfloat16", 4, 8, 4), ("bfloat16", 8, 5, 1), ("bfloat16", 2, 17, 1),
    ("float8_e4m3fn", 8, 8, 4), ("float8_e4m3fn", 4, 4, 1)])
def test_verify_kernel_compiles_with_its_heads_read_apart(
        one_chip, no_compile_cache, kv_dtype, kvh, s, block_len):
    from dynamo_tpu.ops.pallas_decode import paged_verify_attention

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    b, h, d, width = 32, 32, 128, 200
    cache = sds((7, 6400, 16, kvh, d), jnp.dtype(kv_dtype))

    def f(q, k, v, bt, base, ctx, li):
        return paged_verify_attention(q, k, v, bt, base, ctx, layer_idx=li,
                                      block_len=block_len)

    compiled = jax.jit(f).lower(
        sds((b, s, h, d), jnp.bfloat16), cache, cache,
        sds((b, width), jnp.int32), sds((b,), jnp.int32),
        sds((b,), jnp.int32), sds((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# The layer loop and its weights (PR 39). A projection whose result is
# reshaped to heads at once has the reshape folded into its dot; the
# compiler then sees the weight as [heads, head_dim, D], which is a
# bitcast only of the weight transposed, and every layer copies its
# slice out of the stack (`constant_dynamic-slice_fusion.N`, 18.9 MB on
# Phi-3) and transposes it (`copy.N`) before the product: six such
# operations a layer for q, k and v, a sixth of the decode step, where
# wo and the MLP's three entered their fusions as the whole stack and
# the layer index. Phi-3 at the benchmark's size on one chip (head 96,
# MHA, 32 rows) and the real tp=4 program of Mistral-7B over the four
# described chips (a shard's widths: wq [4096, 1024], wk / wv
# [4096, 256], head 128, 64 rows).
_DECODE_TRUNKS = {}


# granite-4.0-h-small as one chip serves it (benchmark/configs/
# granite-4.0-h-small-ep2.json): ten layers, nine of them a mixer of 128
# heads of 64 over a state of 128 with one group of B and C for all the
# heads (two heads side by side on a tile's lanes, 32 tiles = 64 heads a
# block of the state kernel, half a row's state), one
# of them NoPE attention over its own stack of pages, 36 of 72 experts
# of 768 held behind each, a contraction of 4096
def test_ssm_decode_kernel_compiles_at_granites_state(
        one_chip, no_compile_cache, monkeypatch):
    from dynamo_tpu.ops import ssm
    from dynamo_tpu.ops.live_rows import live_row_list

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # a tile is two heads of 64 side by side; a block is 32 of the
    # group's 64 tiles: 64 heads, 2 MiB of float32 state
    assert ssm.lane_heads(64, 128) == 2
    assert ssm._tile_block(64, 64, 128 * 128 * 4) == 32
    # Falcon-H1's and lightning attention's keep theirs
    assert ssm._tile_block(32, 16, 256 * 128 * 4) == 16
    assert ssm._tile_block(32, 1, 128 * 128 * 4) == 32

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    layers, slots, h, p, n, g = 9, 64, 128, 64, 128, 1
    f32, act = jnp.float32, jnp.bfloat16
    record = ssm.record_shape(h, p, n, h // g)
    assert record == (64, 128, 128)

    def f(x, dt, a, bm, cm, d, records, li, live):
        return ssm.ssm_decode_step(x, dt, a, bm, cm, d, records, li,
                                   live_row_list(live))

    compiled = jax.jit(f, donate_argnums=(6,)).lower(
        s((slots, h, p), act), s((slots, h), f32), s((h,), f32),
        s((slots, g, n), act), s((slots, g, n), act), s((h,), f32),
        s((layers, slots) + record, f32), s((), jnp.int32),
        s((slots,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20
    traced, = [t for t in ssm.blocks_traced() if {
        "heads": h, "p": p, "n": n, "itemsize": 4}.items() <= t.items()]
    assert traced == {
        "heads": h, "p": p, "n": n, "heads_per_group": 128, "itemsize": 4,
        "lane_heads": 2, "tiles_per_block": 32, "groups_per_block": 1,
        "groups_per_turn": 1, "block_bytes": 2 << 20}


@pytest.mark.parametrize("rows", [640, 20480])
@pytest.mark.parametrize("k,n", [(4096, 768), (768, 4096)])
def test_grouped_products_compile_at_granites_expert_shapes(
        one_chip, no_compile_cache, monkeypatch, rows, k, n):
    """A decode step's 64 x 10 picks and a 2048-token chunk's, over the
    36 experts held of a run of five layers."""
    from dynamo_tpu.ops import grouped_matmul as gm

    # the contraction of 4096 whole (the chip timing's choice, PR 48)
    assert gm._tiling(rows, 4096, 768) == (128, 4096, 512)
    assert gm._tiling(rows, 768, 4096) == (128, 768, 512)
    # wider than a tile: whole visits where a tile of at least half of
    # _K_TILE divides the contraction (Mixtral's down product as before
    # PR 48), else the last visit masked
    assert [gm._k_tile(k) for k in (14336, 8192, 5120, 4224)] == \
        [3584, 4096, 2560, 4096]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(gm.grouped_matmul).lower(
        s((rows, k), jnp.bfloat16), s((5, 36, k, n), jnp.bfloat16),
        s((36,), jnp.int32), s((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _granite_step(one_chip, rows, tokens, width):
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.models import granite_hybrid

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-small-ep2.json")) as f:
        hf = json.load(f)
    cfg = ModelConfig.from_hf_config(hf)
    serve = hf["serve"]

    def s(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(s, jax.eval_shape(
        lambda: granite_hybrid.init_params(cfg, jax.random.PRNGKey(0),
                                           jnp.bfloat16)))
    cache = jax.tree.map(s, jax.eval_shape(
        lambda: granite_hybrid.init_kv_cache(
            cfg, serve["num_kv_blocks"], 16, jnp.bfloat16,
            num_slots=serve["max_batch_size"])))
    # pages over the attention layer alone, records over the nine mixers
    assert cache[0].kv.shape == (1, 3072, 16, 8, 128)
    assert cache[0].state.shape == (9, 64, 64, 128, 128)
    assert params["runs"][0]["router"].shape == (5, 4096, 72)
    assert params["runs"][0]["w_gate"].shape == (5, 36, 4096, 768)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def step(params, k_side, v_side, tokens, positions, bt, slots, ctx, ss):
        return granite_hybrid.forward_counted(
            params, cfg, tokens, positions, (k_side, v_side), bt, slots, ctx,
            state_slots=ss)

    return jax.jit(step, donate_argnums=(1, 2)).lower(
        params, *cache, i32(rows, tokens), i32(rows, tokens), i32(rows, width),
        i32(rows, tokens), i32(rows), i32(rows)).compile()


def test_granite_decode_step_updates_state_and_pages_in_place(
        one_chip, no_compile_cache, monkeypatch):
    """The whole trunk of a decode step at the benchmark's size: the
    state kernel, the attention decode kernel and the grouped products
    are in it, and neither the recurrent state (2.42 GB at 64 slots)
    nor a run's expert stacks (3.4 GB) are copied."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _granite_step(one_chip, 64, 1, 256)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ssm_decode_step" in text
    state = "f32[9,64,64,128,128]"
    ops = [(re.search(r" ([a-z][a-z\-]*)\(", ln.split(" = ", 1)[1]).group(1), ln)
           for ln in text.splitlines()[1:] if state in ln and " = " in ln]
    assert {op for op, _ in ops} <= {
        "parameter", "get-tuple-element", "tuple", "while", "bitcast",
        "custom-call"}, {op for op, _ in ops}
    mem = compiled.memory_analysis()
    print("decode step: arguments", mem.argument_size_in_bytes,
          "temporaries", mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 12.5e9
    assert mem.temp_size_in_bytes < 2 ** 28


def test_granite_prefill_chunk_fits_beside_the_model(
        one_chip, no_compile_cache, monkeypatch):
    """A 2048-token chunk: the chunked scan's 128 heads x 256 x 256
    decay matrices a chunk and the sorted rows of 20 480 picks are the
    step's temporaries, and they fit in what the model leaves."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _granite_step(one_chip, 1, 2048, 256)
    mem = compiled.memory_analysis()
    print("prefill step: arguments", mem.argument_size_in_bytes,
          "temporaries", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < 2.5 * 2 ** 30


# Kimi-Linear-48B-A3B as one chip serves it (benchmark/configs/
# kimi-linear-48b-a3b-ep16.json): all 27 layers, twenty of them Kimi
# Delta Attention (32 heads of 128, a float32 state of 128 x 128 a head:
# a row's 2 MiB one block of the state kernel), seven latent attention
# over their own stack of pages, 16 of 256 experts of 1024 held behind
# 26 of them, a contraction of 2304
def test_kda_decode_kernel_compiles_at_kimi_linears_state(
        one_chip, no_compile_cache, monkeypatch):
    from dynamo_tpu.ops import kda
    from dynamo_tpu.ops.live_rows import live_row_list

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    layers, slots, h, k = 20, 64, 32, 128
    f32, act = jnp.float32, jnp.bfloat16

    def f(q, kk, v, g, beta, records, li, live):
        return kda.kda_decode_step(q, kk, v, g, beta, records, li,
                                   live_row_list(live))

    compiled = jax.jit(f, donate_argnums=(5,)).lower(
        s((slots, h, k), act), s((slots, h, k), act), s((slots, h, k), act),
        s((slots, h, k), f32), s((slots, h), f32),
        s((layers, slots, h, k, k), f32), s((), jnp.int32),
        s((slots,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "kda_decode_step" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 22


@pytest.mark.parametrize("rows", [512, 8192])
@pytest.mark.parametrize("k,n", [(2304, 1024), (1024, 2304)])
def test_grouped_products_compile_at_kimi_linears_expert_shapes(
        one_chip, no_compile_cache, monkeypatch, rows, k, n):
    """A decode step's 64 x 8 picks and a 1024-token chunk's, over the
    16 experts held of the 26 expert layers."""
    from dynamo_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(gm.grouped_matmul).lower(
        s((rows, k), jnp.bfloat16), s((26, 16, k, n), jnp.bfloat16),
        s((16,), jnp.int32), s((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _kimi_step(one_chip, rows, tokens, width):
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.models import kimi_linear

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-linear-48b-a3b-ep16.json")) as f:
        hf = json.load(f)
    cfg = ModelConfig.from_hf_config(hf)
    serve = hf["serve"]

    def s(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(s, jax.eval_shape(
        lambda: kimi_linear.init_params(cfg, jax.random.PRNGKey(0),
                                        jnp.bfloat16)))
    cache = jax.tree.map(s, jax.eval_shape(
        lambda: kimi_linear.init_kv_cache(
            cfg, serve["num_kv_blocks"], 16, jnp.bfloat16,
            num_slots=serve["max_batch_size"])))
    # latent pages over the seven latent layers, records over the twenty
    assert cache[0].kv.shape == (7, 8192, 1, 16, 512)
    assert cache[0].state.shape == (20, 64, 32, 128, 128)
    assert cache[1].state.shape == (20, 64, 3, 12288)
    assert params["moe"]["router"].shape == (26, 2304, 256)
    assert params["moe"]["w_gate"].shape == (26, 16, 2304, 1024)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def step(params, k_side, v_side, tokens, positions, bt, slots, ctx, ss):
        return kimi_linear.forward_counted(
            params, cfg, tokens, positions, (k_side, v_side), bt, slots, ctx,
            state_slots=ss)

    return jax.jit(step, donate_argnums=(1, 2)).lower(
        params, *cache, i32(rows, tokens), i32(rows, tokens), i32(rows, width),
        i32(rows, tokens), i32(rows), i32(rows)).compile()


def test_kimi_linear_decode_step_updates_state_and_pages_in_place(
        one_chip, no_compile_cache, monkeypatch):
    """The whole trunk of a decode step at the benchmark's size: the KDA
    state kernel, the latent decode kernel and the grouped products are
    in it, and neither the state (2.68 GB at 64 slots), the latent pages
    (0.94 GB) nor the expert stacks (5.9 GB) are copied, through the
    scan over periods and the two loops of traced length inside it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _kimi_step(one_chip, 64, 1, 256)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "kda_decode_step" in text
    for big in ("f32[20,64,32,128,128]", "bf16[7,8192,1,16,512]",
                "bf16[26,16,2304,1024]"):
        ops = [(re.search(r" ([a-z][a-z\-]*)\(", ln.split(" = ", 1)[1]).group(1),
                ln) for ln in text.splitlines()[1:]
               if big in ln and " = " in ln]
        assert ops and {op for op, _ in ops} <= {
            "parameter", "get-tuple-element", "tuple", "while", "bitcast",
            "custom-call", "scatter", "fusion"}, (big, {op for op, _ in ops})
        if big.startswith("f32"):
            assert {op for op, _ in ops} <= {
                "parameter", "get-tuple-element", "tuple", "while", "bitcast",
                "custom-call"}, {op for op, _ in ops}
    mem = compiled.memory_analysis()
    print("decode step: arguments", mem.argument_size_in_bytes,
          "temporaries", mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 13.0e9    # the head is not the trunk's
    assert mem.temp_size_in_bytes < 2 ** 28


def test_kimi_linear_prefill_chunk_fits_beside_the_model(
        one_chip, no_compile_cache, monkeypatch):
    """A 1024-token chunk: the chunked scan's pairwise exponents of a
    chunk of 64 (32 heads x 4 x 16 x 16 x 128) and the sorted rows of
    8192 picks are the step's temporaries, and they fit in what the
    model leaves."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _kimi_step(one_chip, 1, 1024, 256)
    mem = compiled.memory_analysis()
    print("prefill step: arguments", mem.argument_size_in_bytes,
          "temporaries", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < 1.2 * 2 ** 30


# dots3-note-prev as one chip serves it (benchmark/configs/
# dots3-note-prev-ep16.json): nine layers F F S S S F S S S, three full
# layers of 128 heads over a latent of 512 behind an indexer of 64 x 128
# that picks 2048 keys, six window layers of 64 heads over a latent of
# 1024 and a window of 513, 16 of 256 experts of 5120 x 1536 held; 36864
# pages of the full kind, 1216 of the window kind, a table of 1152
def test_latent_decode_kernel_compiles_at_dots3s_window_layers(
        one_chip, no_compile_cache):
    """The latent decode kernel at rank 1024 and 64 heads with a window
    of 513: the walk starts at the window's first page."""
    from dynamo_tpu.ops.pallas_decode import mla_paged_decode_attention

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(ql, qr, c, kr, bt, ctx, li):
        return mla_paged_decode_attention(
            ql, qr, c, kr, bt, ctx, layer_idx=li, scale=256 ** -0.5,
            sliding_window=513)

    compiled = jax.jit(call).lower(
        s((32, 1, 64, 1024)), s((32, 1, 64, 128)),
        s((6, 1216, 1, 16, 1024)), s((6, 1216, 1, 16, 128)),
        s((32, 1152), jnp.int32), s((32,), jnp.int32),
        s((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _dots3_step(one_chip, rows, tokens, width):
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.models import dots3

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "dots3-note-prev-ep16.json")) as f:
        hf = json.load(f)
    cfg = ModelConfig.from_hf_config(hf)
    serve = hf["serve"]
    pool = EngineConfig(
        model=cfg, **{k: serve[k] for k in (
            "max_model_len", "max_batch_size", "num_kv_blocks",
            "prefill_buckets", "max_prefill_tokens_per_step",
            "max_prefill_batch")}).window_pool_pages()
    assert pool == 1 + 32 * 34 + 127

    def s(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(s, jax.eval_shape(
        lambda: dots3.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    k_side, v_side = jax.tree.map(s, jax.eval_shape(
        lambda: dots3.init_kv_cache(
            cfg, serve["num_kv_blocks"], 16, jnp.bfloat16,
            num_slots=serve["max_batch_size"], window_blocks=pool,
            max_len=serve["max_model_len"])))
    # a page shape a kind: a full layer's page a token's row whole (512
    # lanes of latent, 128 of rope key); the indexer's keys by slot
    assert k_side.full.shape == (3, 36864, 1, 16, 640)
    assert k_side.window.shape == (6, 1216, 1, 16, 1024)
    assert v_side.index.shape == (3, 32, 18432, 128)
    assert v_side.window.shape == (6, 1216, 1, 16, 128)
    assert params["moe"]["router"].shape == (8, 5120, 256)
    assert params["moe"]["w_gate"].shape == (8, 16, 5120, 1536)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def step(params, k_side, v_side, toks, positions, bt, slots, ctx):
        return dots3.forward_counted(params, cfg, toks, positions,
                                     (k_side, v_side), bt, slots, ctx)

    return jax.jit(step, donate_argnums=(1, 2)).lower(
        params, k_side, v_side, i32(rows, tokens), i32(rows, tokens),
        i32(rows, 2 * width), i32(rows, tokens), i32(rows)).compile()


# (arguments, temporaries) of the same two steps at PR 54, three page
# stacks of 512 / 128 / 128 lanes a full layer: compiled here the same way
_DOTS3_BEFORE_PR55 = {(32, 1): (11999302656, 179547136),
                      (1, 2048): (11999040000, 997646848)}


@pytest.mark.parametrize("rows,tokens,width", [
    (32, 1, 1152), (1, 2048, 1152)])
def test_dots3_step_keeps_every_page_stack_in_place(
        one_chip, no_compile_cache, monkeypatch, rows, tokens, width):
    """A decode step of 32 rows and a 2048-token prefill chunk at the
    benchmark's size: the window layers' latent kernel (decode) and the
    grouped products are in it, none of the three page stacks (2.27 GB of
    the full kind's rows, 0.24 + 0.03 GB of the window kind) nor the
    0.45 GB of indexer keys by slot is copied, not a layer's 151 MB of
    them either (until PR 63 a decode step gathered that much a full
    layer out of pages; the dense prefix's layer, whose index the
    compiler knows, would still copy it as a static slice were its
    offset not held as a value), and the blocked prefill's temporaries
    stay under a tenth of the chip. A full layer's body looks a picked
    key up once (decode: one gather of ``[32, 2048, 640]`` under
    ``dsa_attend``) and a key block's pages once (prefill: ``[64, 16,
    640]``), and makes no copy of what it gathered; nothing under
    ``dsa_index`` gathers."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _dots3_step(one_chip, rows, tokens, width)
    text = compiled.as_text()
    assert re.search(r"tpu_custom_call[^\n]*moe_experts", text)
    assert bool(re.search(r"tpu_custom_call[^\n]*swa_latent", text)) == (tokens == 1)
    for scope in ("dsa_index", "dsa_select", "dsa_attend"):
        assert scope in text, scope
    # two bodies trace a full layer: layer 0 of the dense prefix, and the
    # period's loop
    gathered = re.findall(r"= bf16\[([0-9,]+)\]\S* gather\([^\n]*dsa_attend",
                          text)
    assert gathered == ["32,2048,640" if tokens == 1 else "64,16,640"] * 2
    copied = [math.prod(map(int, dims.split(","))) for dims in re.findall(
        r"= \w+\[([0-9,]+)\]\S* copy\([^\n]*dsa_attend", text)]
    assert max(copied, default=0) < 32 * 2048 * 512     # the queries, at most
    assert not re.search(r" gather\([^\n]*dsa_index", text)
    mem = compiled.memory_analysis()
    before = _DOTS3_BEFORE_PR55[rows, tokens]
    print(f"dots3 step {rows}x{tokens}: arguments",
          mem.argument_size_in_bytes, "temporaries", mem.temp_size_in_bytes,
          f"(before PR 55: {before[0]}, {before[1]})")
    # weights 9.21 GB without the head's 0.19 (the trunk ends at the
    # hidden state) + pages 2.99
    assert 11.9e9 < mem.argument_size_in_bytes < 12.1e9
    # 55.5 MB and 997.7 MB; a copy of one layer's indexer keys would
    # add 151 MB (the gathered pages did until PR 63: 177.9 MB a decode
    # step), of all three 0.45 GB, of the rows 2.27
    assert mem.temp_size_in_bytes < (0.12 if tokens == 1 else 1.0) * 2 ** 30


# MiMo-V2.5 as one chip serves it (benchmark/configs/mimo-v2.5-ep16.json):
# 64 query heads over 4 kv heads in a full layer and 8 in a window layer,
# keys of 192 in 256 lanes and values of 128, pages of 16, a window of 128
# under a learned sink, 32 rows, a table of 1152
@pytest.mark.parametrize("kvh,sink", [(8, True), (4, False)])
@pytest.mark.parametrize("rows,tokens", [(32, 1), (1, 2048)])
def test_decode_and_flash_kernels_compile_at_mimos_pages(
        one_chip, no_compile_cache, kvh, sink, rows, tokens):
    """The decode kernel at 32 rows and the flash kernel on a 2048-token
    chunk, at both kinds' shapes: K of 256 lanes (two stacks of 128) and V of
    128, the window
    layers' sink (in the flash kernel the running softmax's first term),
    the query block sized from its bytes (64 heads of 256 lanes do not
    fit at 128 rows)."""
    from dynamo_tpu.ops.attention import attention
    from dynamo_tpu.ops.pallas_attention import q_block_rows

    assert q_block_rows(64, 256, 128, 2) == 64
    assert q_block_rows(32, 128, 128, 2) == 128     # Phi-3's, as it was

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    layers, blocks, page, h, width = 5 if sink else 2, 417, 16, 64, 1152

    def f(q, k, v, bt, pos, ctx, li, *sinks):
        return attention(q, k, v, bt, pos, ctx, impl="pallas", layer_idx=li,
                         sliding_window=128 if sink else None,
                         sinks=sinks[0] if sinks else None, v_dim=128)

    args = [s((rows, tokens, h, 192), jnp.bfloat16),
            (s((layers, blocks, page, kvh, 128), jnp.bfloat16),) * 2,
            s((layers, blocks, page, kvh, 128), jnp.bfloat16),
            s((rows, width), jnp.int32), s((rows, tokens), jnp.int32),
            s((rows,), jnp.int32), s((), jnp.int32)]
    if sink:
        args.append(s((h,), jnp.float32))
    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (rows, tokens, h, 128)


@pytest.mark.parametrize("rows", [256, 16384])
@pytest.mark.parametrize("k,n", [(4096, 2048), (2048, 4096)])
def test_grouped_products_compile_at_mimos_expert_shapes(
        one_chip, no_compile_cache, monkeypatch, rows, k, n):
    from dynamo_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(gm.grouped_matmul).lower(
        s((rows, k), jnp.bfloat16), s((6, 16, k, n), jnp.bfloat16),
        s((16,), jnp.int32), s((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _mimo_step(one_chip, rows, tokens, width):
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.models import mimo_v2

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mimo-v2.5-ep16.json")) as f:
        hf = json.load(f)
    cfg = ModelConfig.from_hf_config(hf)
    serve = hf["serve"]
    pool = EngineConfig(
        model=cfg, **{k: serve[k] for k in (
            "max_model_len", "max_batch_size", "num_kv_blocks",
            "prefill_buckets", "max_prefill_tokens_per_step",
            "max_prefill_batch")}).window_pool_pages()
    assert pool == 1 + 32 * 9 + 128

    def s(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(s, jax.eval_shape(
        lambda: mimo_v2.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    k_side, v_side = jax.tree.map(s, jax.eval_shape(
        lambda: mimo_v2.init_kv_cache(cfg, serve["num_kv_blocks"], 16,
                                      jnp.bfloat16, window_blocks=pool)))
    assert [k.shape for k in k_side.full] == [(2, 36864, 16, 4, 128)] * 2
    assert v_side.full.shape == (2, 36864, 16, 4, 128)
    assert [k.shape for k in k_side.window] == [(5, 417, 16, 8, 128)] * 2
    assert v_side.window.shape == (5, 417, 16, 8, 128)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def step(params, k_side, v_side, toks, positions, bt, slots, ctx):
        return mimo_v2.forward_counted(params, cfg, toks, positions,
                                       (k_side, v_side), bt, slots, ctx)

    return jax.jit(step, donate_argnums=(1, 2)).lower(
        params, k_side, v_side, i32(rows, tokens), i32(rows, tokens),
        i32(rows, 2 * width), i32(rows, tokens), i32(rows)).compile()


@pytest.mark.parametrize("rows,tokens,width", [
    (32, 1, 1152), (1, 2048, 1152)])
def test_mimo_step_keeps_both_page_stacks_in_place(
        one_chip, no_compile_cache, monkeypatch, rows, tokens, width):
    """A decode step of 32 rows and a 2048-token prefill chunk at the
    benchmark's size on the routes the chip takes: the paged decode (or
    flash) kernel once a kind of layer, the three grouped products of
    the held experts, neither the full kind's pages (3.62 GB) nor the
    window kind's (0.20 GB) copied, and the prefill chunk's temporaries
    fit beside what is held."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _mimo_step(one_chip, rows, tokens, width)
    text = compiled.as_text()
    for scope in ("kv_window", "kv_full", "moe_experts"):
        assert re.search(rf"tpu_custom_call[^\n]*{scope}", text), scope
    mem = compiled.memory_analysis()
    print(f"mimo step {rows}x{tokens}: arguments",
          mem.argument_size_in_bytes, "temporaries", mem.temp_size_in_bytes)
    # weights 6.86 GB without the head's 0.16 (the trunk ends at the
    # hidden state) + full pages 3.62 + window pages 0.20
    assert 10.4e9 < mem.argument_size_in_bytes < 10.8e9
    # a copy of the window stacks would be 0.2 GB, of the full 3.6
    assert mem.temp_size_in_bytes < (64 if tokens == 1 else 1024) * 2 ** 20


# Nemotron 3 Super as one chip serves it (benchmark/configs/
# nemotron-3-super-ep4.json): the stage's eleven letters MEMEMEM*EME,
# five of them a mixer of 128 heads of 64 over a state of 128 with
# **eight** groups of B and C (sixteen heads = 8 tiles a group: a block
# of the state kernel is 32 tiles, four whole groups), one NoPE attention
# layer of 32 query heads over 2 kv heads, five expert layers of 128 of
# 512 experts held, two matrices each in a latent of 1024 (contractions
# of 1024 and 2688), at 128 decode rows
def test_ssm_decode_kernel_compiles_at_nemotrons_eight_groups(
        one_chip, no_compile_cache, monkeypatch):
    from dynamo_tpu.ops import ssm
    from dynamo_tpu.ops.live_rows import live_row_list

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssm.lane_heads(64, 16) == 2
    # 64 tiles of 64 KiB a row, 8 a group: a block of 32 holds four groups
    assert ssm._tile_block(64, 8, 128 * 128 * 4) == 32

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    layers, slots, h, p, n, g = 5, 128, 128, 64, 128, 8
    f32, act = jnp.float32, jnp.bfloat16
    record = ssm.record_shape(h, p, n, h // g)
    assert record == (64, 128, 128)

    def f(x, dt, a, bm, cm, d, records, li, live):
        return ssm.ssm_decode_step(x, dt, a, bm, cm, d, records, li,
                                   live_row_list(live))

    compiled = jax.jit(f, donate_argnums=(6,)).lower(
        s((slots, h, p), act), s((slots, h), f32), s((h,), f32),
        s((slots, g, n), act), s((slots, g, n), act), s((h,), f32),
        s((layers, slots) + record, f32), s((), jnp.int32),
        s((slots,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20
    traced, = [t for t in ssm.blocks_traced() if {
        "heads": h, "p": p, "n": n, "heads_per_group": 16}.items() <= t.items()]
    assert (traced["lane_heads"], traced["tiles_per_block"],
            traced["groups_per_block"], traced["block_bytes"]) == (
        2, 32, 4, 2 << 20)


@pytest.mark.parametrize("rows", [2816, 22528])
@pytest.mark.parametrize("k,n", [(1024, 2688), (2688, 1024)])
def test_grouped_products_compile_at_nemotrons_latent_experts(
        one_chip, no_compile_cache, monkeypatch, rows, k, n):
    """A decode step's 128 x 22 picks and a 1024-token chunk's, over the
    128 experts held of the five expert layers: 2688 columns are five
    tiles of 512 and one of 128, a contraction of 2688 is whole."""
    from dynamo_tpu.ops import grouped_matmul as gm

    assert gm._tiling(rows, 1024, 2688) == (128, 1024, 512)
    assert gm._tiling(rows, 2688, 1024) == (128, 2688, 512)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(gm.grouped_matmul).lower(
        s((rows, k), jnp.bfloat16), s((5, 128, k, n), jnp.bfloat16),
        s((128,), jnp.int32), s((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _nemotron_step(one_chip, rows, tokens, width):
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.models import nemotron_h

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron-3-super-ep4.json")) as f:
        hf = json.load(f)
    cfg = ModelConfig.from_hf_config(hf)
    serve = hf["serve"]

    def s(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(s, jax.eval_shape(
        lambda: nemotron_h.init_params(cfg, jax.random.PRNGKey(0),
                                       jnp.bfloat16)))
    cache = jax.tree.map(s, jax.eval_shape(
        lambda: nemotron_h.init_kv_cache(
            cfg, serve["num_kv_blocks"], 16, jnp.bfloat16,
            num_slots=serve["max_batch_size"])))
    # pages over the attention layer alone, records over the five mixers
    assert cache[0].kv.shape == (1, 24576, 16, 2, 128)
    assert cache[0].state.shape == (5, 128, 64, 128, 128)
    assert params["moe"]["router"].shape == (5, 4096, 512)
    assert params["moe"]["w_up"].shape == (5, 128, 1024, 2688)
    assert params["moe"]["w_down"].shape == (5, 128, 2688, 1024)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def step(params, k_side, v_side, tokens, positions, bt, slots, ctx, ss):
        return nemotron_h.forward_counted(
            params, cfg, tokens, positions, (k_side, v_side), bt, slots, ctx,
            state_slots=ss)

    return jax.jit(step, donate_argnums=(1, 2)).lower(
        params, *cache, i32(rows, tokens), i32(rows, tokens), i32(rows, width),
        i32(rows, tokens), i32(rows), i32(rows)).compile()


def test_nemotron_decode_step_at_128_rows_updates_state_and_pages_in_place(
        one_chip, no_compile_cache, monkeypatch):
    """The whole trunk of a decode step at the benchmark's size, 128
    rows: the state kernel, the attention decode kernel and the two
    grouped products are in it once each (one body a kind, whatever the
    pattern), and neither the recurrent state (2.68 GB at 128 slots) nor
    the expert stacks (7.05 GB) are copied."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _nemotron_step(one_chip, 128, 1, 192)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ssm_decode_step" in text
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text)) <= 6
    for held in ("f32[5,128,64,128,128]", "bf16[5,128,1024,2688]",
                 "bf16[5,128,2688,1024]", "bf16[640,1024,2688]"):
        ops = [(re.search(r" ([a-z][a-z\-]*)\(", ln.split(" = ", 1)[1]).group(1), ln)
               for ln in text.splitlines()[1:] if held in ln and " = " in ln]
        assert {op for op, _ in ops} <= {
            "parameter", "get-tuple-element", "tuple", "while", "bitcast",
            "custom-call"}, (held, {op for op, _ in ops})
    mem = compiled.memory_analysis()
    print("decode step: arguments", mem.argument_size_in_bytes,
          "temporaries", mem.temp_size_in_bytes)
    # (the head is not the trunk's: 12.42 GB held less its 0.27)
    assert mem.argument_size_in_bytes > 12.1e9
    assert mem.temp_size_in_bytes < 2 ** 28
    # (a 1024-token prefill chunk compiled the same way leaves 0.28 GB of
    # temporaries: _nemotron_step(one_chip, 1, 1024, 192), 45 s, by hand)


def _decode_trunk(ll, topo, config):
    """The decode trunk of a benchmark configuration compiled for the
    described chips (the real tp mesh where ``serve`` asks for one), once
    a run of this file: a quarter of a minute to a minute each."""
    if config not in _DECODE_TRUNKS:
        _DECODE_TRUNKS[config] = ll.lower_trunk(
            os.path.join(ROOT, "benchmark", "configs", config + ".json"),
            topo.devices).compile()
    return _DECODE_TRUNKS[config]


@pytest.mark.parametrize("config", ["phi3-mini-4k", "mistral-7b-v0.3-tp4"])
def test_decode_step_reads_every_projection_weight_where_it_lies(
        topo, no_compile_cache, monkeypatch, config):
    ll = _layer_loop()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _decode_trunk(ll, topo, config)
    text = compiled.as_text()
    bodies = ll.loop_bodies(text)
    assert len(bodies) == 1, list(bodies)          # the one scan over the layers
    ops = ll.operations(next(iter(bodies.values())))
    assert any(op == "custom-call" and "paged_decode_attention" in name
               for name, _, op, _, _ in ops)
    # no `copy` and no stand-alone dynamic-slice fusion yields an array
    # of a projection weight's element count (>= 2**20)
    assert ll.staged_weights(text) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


# PR 41: the decode kernels' grid is the rows that hold a token. Mosaic
# has to accept a grid whose first bound is a value of the step's (under
# shard_map at tp=4 too), and the list has to be made once a step: the
# kernel's custom call takes the bound (a scalar) and the list first, and
# inside the layer loop both are elements of the loop's carried tuple,
# not the result of an operation of the loop. Phi-3 on one chip, the
# real tp=4 program of Mistral-7B, Moonlight's MLA kernel.
@pytest.mark.parametrize("config,kernel", [
    ("phi3-mini-4k", "paged_decode_attention"),
    ("mistral-7b-v0.3-tp4", "paged_decode_attention"),
    ("moonlight-16b-a3b", "mla_paged_decode_attention")])
def test_decode_step_walks_a_row_list_made_outside_the_layer_loop(
        topo, no_compile_cache, monkeypatch, config, kernel):
    ll = _layer_loop()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _decode_trunk(ll, topo, config).as_text()
    with open(os.path.join(ROOT, "benchmark", "configs", config + ".json")) as f:
        rows = json.load(f)["serve"]["max_batch_size"]
    calls = 0
    for lines in ll.loop_bodies(text).values():
        made_here = {name: op for name, _, op, _, _ in ll.operations(lines)}
        for line in lines:
            if "custom-call(" not in line or f"%{kernel}." not in line.split(" = ")[0]:
                continue
            calls += 1
            bound, row_list = re.search(
                r"custom-call\(%([\w.\-]+), %([\w.\-]+),", line).groups()
            assert f"operand_layout_constraints={{s32[], s32[{rows}]{{0}}, " in line
            assert made_here[bound] == "get-tuple-element", (bound, made_here[bound])
            assert made_here[row_list] == "get-tuple-element", (row_list, made_here[row_list])
    assert calls >= 1


# two lines of the Phi-3 decode loop as the parent of PR 39 compiled it,
# and the product that reads the stack in place: what the reader above
# must tell apart, held here where no topology is needed
_LOOP_TEXT = """HloModule jit_step

%fused_computation.7 (param_0.1: bf16[32,3072,3072], param_1.2: s32[]) -> bf16[1,3072,3072] {
  %param_0.1 = bf16[32,3072,3072]{2,1,0:T(8,128)(2,1)} parameter(0)
  %param_1.2 = s32[]{:T(128)} parameter(1)
  %constant.9 = s32[]{:T(128)} constant(0)
  ROOT %dynamic-slice.3 = bf16[1,3072,3072]{2,1,0:T(8,128)(2,1)S(1)} dynamic-slice(%param_0.1, %param_1.2, %constant.9, %constant.9), dynamic_slice_sizes={1,3072,3072}
}

%fused_computation.101 (param_0.5: bf16[32,3072], param_1.6: bf16[32,3072,3072], param_2.7: s32[]) -> bf16[32,3072] {
  %param_0.5 = bf16[32,3072]{1,0:T(8,128)(2,1)S(1)} parameter(0)
  %param_1.6 = bf16[32,3072,3072]{2,1,0:T(8,128)(2,1)} parameter(1)
  %param_2.7 = s32[]{:T(128)} parameter(2)
  %constant.11 = s32[]{:T(128)} constant(0)
  %dynamic-slice.5 = bf16[1,3072,3072]{2,1,0:T(8,128)(2,1)} dynamic-slice(%param_1.6, %param_2.7, %constant.11, %constant.11), dynamic_slice_sizes={1,3072,3072}
  %bitcast.9 = bf16[3072,3072]{1,0:T(8,128)(2,1)} bitcast(%dynamic-slice.5)
  ROOT %convolution.2 = bf16[32,3072]{1,0:T(8,128)(2,1)S(1)} convolution(%param_0.5, %bitcast.9), dim_labels=bf_io->bf
}

%body.1 (arg: (s32[], bf16[32,3072], bf16[32,3072,3072])) -> (s32[], bf16[32,3072], bf16[32,3072,3072]) {
  %arg = (s32[]{:T(128)}, bf16[32,3072]{1,0:T(8,128)(2,1)}, bf16[32,3072,3072]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %get-tuple-element.1 = s32[]{:T(128)} get-tuple-element(%arg), index=0
  %get-tuple-element.2 = bf16[32,3072]{1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %get-tuple-element.3 = bf16[32,3072,3072]{2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=2
  %constant_dynamic-slice_fusion.7 = bf16[1,3072,3072]{2,1,0:T(8,128)(2,1)S(1)} fusion(%get-tuple-element.3, %get-tuple-element.1), kind=kLoop, calls=%fused_computation.7
  %copy.34 = bf16[1,3072,3072]{1,2,0:T(8,128)(2,1)S(1)} copy(%constant_dynamic-slice_fusion.7)
  %copy.36 = bf16[32,32,128]{2,1,0:T(8,128)(2,1)S(1)} copy(%get-tuple-element.2)
  %fusion.101 = bf16[32,3072]{1,0:T(8,128)(2,1)S(1)} fusion(%get-tuple-element.2, %get-tuple-element.3, %get-tuple-element.1), kind=kOutput, calls=%fused_computation.101
  ROOT %tuple.1 = (s32[]{:T(128)}, bf16[32,3072]{1,0:T(8,128)(2,1)}, bf16[32,3072,3072]{2,1,0:T(8,128)(2,1)}) tuple(%get-tuple-element.1, %fusion.101, %get-tuple-element.3)
}

ENTRY %main (p: (s32[], bf16[32,3072], bf16[32,3072,3072])) -> (s32[], bf16[32,3072], bf16[32,3072,3072]) {
  %p = (s32[]{:T(128)}, bf16[32,3072]{1,0:T(8,128)(2,1)}, bf16[32,3072,3072]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %copy.1 = bf16[32,3072,3072]{2,1,0:T(8,128)(2,1)} copy(%p)
  ROOT %while.1 = (s32[]{:T(128)}, bf16[32,3072]{1,0:T(8,128)(2,1)}, bf16[32,3072,3072]{2,1,0:T(8,128)(2,1)}) while(%p), condition=%cond.1, body=%body.1
}
"""


def test_layer_loop_reader_tells_a_staged_weight_from_a_streamed_one():
    ll = _layer_loop()
    bodies = ll.loop_bodies(_LOOP_TEXT)
    assert list(bodies) == ["body.1"]
    ops = {name: (result, op, n) for name, result, op, n, _ in
           ll.operations(bodies["body.1"])}
    assert ops["copy.34"] == (
        "bf16[1,3072,3072]{1,2,0:T(8,128)(2,1)S(1)}", "copy", 3072 * 3072)
    assert ops["tuple.1"][2] == 32 * 3072 * 3072      # its largest member
    # the slice alone and the transposing copy; not the small copy, not
    # the product that slices inside its own fusion, not a copy outside
    # the loop
    assert ll.staged_weights(_LOOP_TEXT) == [
        "constant_dynamic-slice_fusion.7", "copy.34"]
    assert ll.staged_weights(_LOOP_TEXT, least=2 ** 17) == [
        "constant_dynamic-slice_fusion.7", "copy.34", "copy.36"]
