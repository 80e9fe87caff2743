"""Compile the main-path kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a chip that is
described and not attached, and refuses what the chip would refuse
(unaligned slices, too much VMEM, a program that does not fit).  The
shapes are StarCoder2-3B's published widths: 24 query heads, 2 KV
heads, head_dim 128, d_model 3072, 16-token pages; and DeepSeek-V2-Lite's
(9 of its 27 layers as the benchmark runs them): 16 heads over one
576-value latent stored in 640 lanes, 64 experts of width 1408.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and xdist workers each
import every test file.  Keep every compile for the described chip in
this one file, so that one worker loads the library.
"""

import dataclasses
import functools
import math
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import chunked_prefill_attention as cpa
from repro.kernels import mla_decode, mla_prefill, moe_gmm
from repro.kernels import ops
from repro.kernels import paged_decode_attention as pfd
from repro.kernels import ragged_chunked_prefill as rcp
from repro.kernels import rmsnorm as rn
from repro.models import model as model_lib, transformer
from repro.serving import generate

CFG = configs.get_config("starcoder2-3b")
H, KV, D = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
BS, NUM_PAGES, NB = 16, 2049, 35      # page size, pool pages, table width
HBM_BYTES = 16 * 2**30                # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A described-chip compile written to the persistent cache cannot
    be read back without a chip; keep the cache off around each test."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


bf16, i32 = jnp.bfloat16, jnp.int32


def _pool_relayouts(compiled) -> list:
    """Copies and transposes in the compiled program that produce an
    array of one KV pool's size, or the stacked pools': a pool read in
    another layout than its own ``(N, bs, KV, D)``."""
    n = NUM_PAGES * BS * KV * D
    out = []
    for line in compiled.as_text().splitlines():
        m = re.search(r"= bf16\[([\d,]*)\]\S* (copy|transpose)\(", line)
        if m and math.prod(int(d) for d in m.group(1).split(",")) in (
                n, CFG.num_layers * n):
            out.append(line.strip()[:160])
    return out


def _pool_copies(compiled, one_layer: tuple, stacked: str) -> list:
    """Instructions of the compiled program that make one layer's pool
    (shapes ``one_layer``; a bitcast or a parameter makes none) or
    write the ``stacked`` pools by a dynamic-update-slice: the pool of
    a scanned layer sliced out of the stack and written back."""
    out = []
    for line in compiled.as_text().splitlines():
        m = re.search(r"= (bf16\[[\d,]*\])\S* ([\w-]+)\(", line)
        if not m:
            continue
        shape, op = m.groups()
        if ((shape in one_layer and op not in ("bitcast", "parameter"))
                or (shape == stacked and op == "dynamic-update-slice")):
            out.append(line.strip()[:160])
    return out


def _pool_bytes(cache) -> int:
    return sum(a.size * a.dtype.itemsize for key, layer in cache.items()
               if key.startswith(("prefix", "scan"))
               for a in jax.tree.leaves(layer))


def test_paged_decode_attention_compiles(one_chip):
    B = 16
    c = _compile(functools.partial(pfd.paged_flash_decode_attention,
                                   interpret=False), one_chip,
                 ((B, H, D), bf16), ((1, NUM_PAGES, BS, KV, D), bf16),
                 ((1, NUM_PAGES, BS, KV, D), bf16), ((B, NB), i32),
                 ((B,), i32), ((), i32))
    assert _has_kernel(c)


def test_paged_decode_reads_pool_in_place(one_chip):
    """At sc2-chat-steady's shapes (64 slots, 24-entry tables, the 30
    layers' stacked pools) the kernel DMAs one layer's pages from the
    stacked pools as they are: no transposed ``(KV, N, bs, D)`` copy and
    no one-layer slice of a pool feeds it."""
    B, nb, L = 64, 24, CFG.num_layers
    c = _compile(functools.partial(pfd.paged_flash_decode_attention,
                                   interpret=False), one_chip,
                 ((B, H, D), bf16), ((L, NUM_PAGES, BS, KV, D), bf16),
                 ((L, NUM_PAGES, BS, KV, D), bf16), ((B, nb), i32),
                 ((B,), i32), ((), i32))
    assert _has_kernel(c)
    assert f"bf16[{KV},{NUM_PAGES},{BS},{D}]" not in c.as_text()
    assert _pool_relayouts(c) == []
    assert _pool_copies(c, (f"bf16[{NUM_PAGES},{BS},{KV},{D}]",), "") == []


def test_chunked_prefill_attention_compiles(one_chip):
    # the per-chunk kernel holds its whole (T*G, D) query block in VMEM,
    # so its compile time grows with T (about 50 s at T=512); the engine
    # serves through the fused ragged kernel, this one stays at T=128
    T = 128
    c = _compile(functools.partial(cpa.chunked_prefill_attention,
                                   interpret=False), one_chip,
                 ((1, T, H, D), bf16), ((NUM_PAGES, BS, KV, D), bf16),
                 ((NUM_PAGES, BS, KV, D), bf16), ((1, NB), i32), ((1,), i32))
    assert _has_kernel(c)


@pytest.mark.parametrize("chunk_pad,chunks", [(64, 4), (512, 2), (2048, 1)])
def test_ragged_chunked_prefill_compiles(one_chip, chunk_pad, chunks):
    """The fused kernel's VMEM use is bounded independently of the chunk
    pad, so every pad the engine reaches compiles."""
    T, C = chunk_pad, chunks
    c = _compile(functools.partial(rcp.ragged_chunked_prefill,
                                   interpret=False), one_chip,
                 ((C, T, H, D), bf16), ((C, T, KV, D), bf16),
                 ((C, T, KV, D), bf16), ((NUM_PAGES, BS, KV, D), bf16),
                 ((NUM_PAGES, BS, KV, D), bf16), ((C, NB), i32), ((C, 4), i32))
    assert _has_kernel(c)


def test_rms_norm_compiles(one_chip):
    c = _compile(functools.partial(rn.rms_norm, interpret=False), one_chip,
                 ((512, CFG.d_model), bf16), ((CFG.d_model,), bf16))
    assert _has_kernel(c)


@pytest.mark.parametrize("step", ["decode_window", "ragged_prefill"])
def test_full_width_step_compiles_and_fits(one_chip, monkeypatch, step):
    """The engine's whole step programs at published widths: the model's
    kernel sites must pick the compiled kernel (``_default_interpret``
    steered here, since the backend is the CPU), and parameters, the KV
    pool and the output, counted apart, must fit one chip.  The decode
    window updates the stacked pools in place: no scanned layer's pool
    is sliced out and written back, and the donated pools are aliased
    from input to output."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, i32, sharding=one_chip)

    params = sds(jax.eval_shape(functools.partial(
        model_lib.init_params, jax.random.PRNGKey(0), CFG)))
    slots = 16
    cache = sds(jax.eval_shape(functools.partial(
        transformer.init_paged_cache, CFG, slots, NUM_PAGES, BS)))
    if step == "decode_window":
        fn = generate.make_paged_decode_steps_fn(CFG, True).fn
        c = fn.lower(params, cache, arg((slots, 1)), arg((slots, NB)),
                     num_steps=1).compile()
    else:
        fn = generate.make_ragged_prefill_fn(CFG, True).fn
        c = fn.lower(params, cache, {"tokens": arg((1, 512))}, arg((512,)),
                     arg((1, 4)), arg((1, NB)), chunk_pad=512).compile()
    assert _has_kernel(c)
    m = c.memory_analysis()
    if step == "decode_window":
        assert _pool_relayouts(c) == []
        one = (f"bf16[1,{NUM_PAGES},{BS},{KV},{D}]",
               f"bf16[{NUM_PAGES},{BS},{KV},{D}]")
        stacked = f"bf16[{CFG.num_layers},{NUM_PAGES},{BS},{KV},{D}]"
        assert _pool_copies(c, one, stacked) == []
        assert m.alias_size_in_bytes >= _pool_bytes(cache)
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes) < HBM_BYTES


# --- DeepSeek-V2-Lite: latent attention and the dropless expert layer ---

LITE = dataclasses.replace(configs.get_config("deepseek-v2-lite"),
                           num_layers=9)
LANES, LITE_NB, ROWS = LITE.latent_lanes, 25, 64


def test_mla_decode_attention_compiles(one_chip):
    c = _compile(functools.partial(mla_decode.mla_decode_attention,
                                   rank=LITE.kv_lora_rank, interpret=False),
                 one_chip, ((ROWS, LITE.num_heads, LANES), bf16),
                 ((LITE.num_layers - 1, NUM_PAGES, BS, LANES), bf16),
                 ((ROWS, LITE_NB), i32), ((ROWS,), i32), ((), i32))
    assert _has_kernel(c)


@pytest.mark.parametrize("chunks,chunk_pad", [(1, 1), (8, 32), (8, 128)])
def test_mla_prefill_attention_compiles(one_chip, chunks, chunk_pad):
    c = _compile(functools.partial(mla_prefill.mla_prefill_attention,
                                   rank=LITE.kv_lora_rank, interpret=False),
                 one_chip, ((chunks, chunk_pad, LITE.num_heads, LANES), bf16),
                 ((NUM_PAGES, BS, LANES), bf16), ((chunks, LITE_NB), i32),
                 ((chunks, 4), i32))
    assert _has_kernel(c)


@pytest.mark.parametrize("rows", [ROWS * 6, 128 * 6])
@pytest.mark.parametrize("which", ["gate_up", "down"])
def test_moe_gmm_compiles(one_chip, rows, which):
    D, F, E = LITE.d_model, LITE.moe_d_ff, LITE.num_experts
    K, N = (D, F) if which == "gate_up" else (F, D)
    c = _compile(functools.partial(moe_gmm.gmm, interpret=False), one_chip,
                 ((rows, K), bf16), ((E, K, N), bf16), ((E,), i32))
    assert _has_kernel(c)


@pytest.mark.parametrize("step", ["decode_window", "ragged_prefill"])
def test_deepseek_step_compiles_and_fits(one_chip, monkeypatch, step):
    """DeepSeek-V2-Lite's whole step programs, 9 layers at published
    widths and 64 slots: the latent kernel and the expert matmul are in,
    and parameters, the latent pool and the output, counted apart, fit
    one chip.  The decode window updates the scanned layers' stacked
    latent pool in place (no one-layer slice of it, no write-back) and
    aliases the donated pools from input to output."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(functools.partial(
            model_lib.init_params, jax.random.PRNGKey(0), LITE)))
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(functools.partial(
            transformer.init_paged_cache, LITE, ROWS, NUM_PAGES, BS)))

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, i32, sharding=one_chip)

    if step == "decode_window":
        fn = generate.make_paged_decode_steps_fn(LITE, True).fn
        c = fn.lower(params, cache, arg((ROWS, 1)), arg((ROWS, LITE_NB)),
                     num_steps=1).compile()
        kernels = ("mla_decode_kernel", "moe_gmm_kernel")
    else:
        fn = generate.make_ragged_prefill_fn(LITE, True).fn
        c = fn.lower(params, cache, {"tokens": arg((1, 128))}, arg((128,)),
                     arg((8, 4)), arg((8, LITE_NB)), chunk_pad=32).compile()
        kernels = ("mla_prefill_kernel", "moe_gmm_kernel")
    text = c.as_text()
    assert all(k in text for k in kernels)
    # the expert matmul reads its layer's experts from the whole stack:
    # no instruction makes a copy of one layer's (64, D, F) experts
    E, D, F = LITE.num_experts, LITE.d_model, LITE.moe_d_ff
    assert not re.search(rf"= bf16\[{E},({D},{F}|{F},{D})\]", text)
    m = c.memory_analysis()
    if step == "decode_window":
        one = (f"bf16[1,{NUM_PAGES},{BS},{LANES}]",)
        stacked = f"bf16[{LITE.num_layers - 1},{NUM_PAGES},{BS},{LANES}]"
        assert _pool_copies(c, one, stacked) == []
        assert m.alias_size_in_bytes >= _pool_bytes(cache)
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes) < HBM_BYTES
