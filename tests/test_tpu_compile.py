"""Compile the main-path kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a chip that is
described and not attached, and refuses what the chip would refuse
(unaligned slices, too much VMEM, a program that does not fit).  The
shapes are StarCoder2-3B's published widths: 24 query heads, 2 KV
heads, head_dim 128, d_model 3072, 16-token pages.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and xdist workers each
import every test file.  Keep every compile for the described chip in
this one file, so that one worker loads the library.
"""

import functools

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import chunked_prefill_attention as cpa
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


def test_paged_decode_attention_compiles(one_chip):
    B = 16
    c = _compile(functools.partial(pfd.paged_flash_decode_attention,
                                   interpret=False), one_chip,
                 ((B, H, D), bf16), ((NUM_PAGES, BS, KV, D), bf16),
                 ((NUM_PAGES, BS, KV, D), bf16), ((B, NB), i32), ((B,), i32))
    assert _has_kernel(c)


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
    steered here, since the backend is the CPU), and parameters plus the
    KV pool held twice (no donation) must fit one chip."""
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
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes) < HBM_BYTES
