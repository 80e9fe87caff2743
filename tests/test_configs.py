"""Config registry: every assigned arch, exact assignment rows, param
counts near the published sizes, smoke variants within the reduced caps."""

import pytest

from repro import configs

EXPECTED = {
    # arch: (layers, d_model, heads, kv, d_ff-ish, vocab, ~params B, ~active B)
    "kimi-k2-1t-a32b": dict(L=61, d=7168, H=64, KV=8, V=163840,
                            N=(950e9, 1.1e12), A=(30e9, 36e9)),
    "minitron-4b": dict(L=32, d=3072, H=24, KV=8, V=256000,
                        N=(3.5e9, 5e9), A=None),
    "yi-6b": dict(L=32, d=4096, H=32, KV=4, V=64000,
                  N=(5.5e9, 6.5e9), A=None),
    "mixtral-8x22b": dict(L=56, d=6144, H=48, KV=8, V=32768,
                          N=(130e9, 145e9), A=(36e9, 42e9)),
    "h2o-danube-3-4b": dict(L=24, d=3840, H=32, KV=8, V=32000,
                            N=(3.3e9, 4.3e9), A=None),
    "starcoder2-3b": dict(L=30, d=3072, H=24, KV=2, V=49152,
                          N=(2.7e9, 3.4e9), A=None),
    "llava-next-mistral-7b": dict(L=32, d=4096, H=32, KV=8, V=32000,
                                  N=(6.5e9, 7.6e9), A=None),
    "mamba2-1.3b": dict(L=48, d=2048, H=None, KV=None, V=50280,
                        N=(1.2e9, 1.5e9), A=None),
    "seamless-m4t-large-v2": dict(L=24, d=1024, H=16, KV=16, V=256206,
                                  N=(1.2e9, 2.4e9), A=None),
    "recurrentgemma-9b": dict(L=38, d=4096, H=16, KV=1, V=256000,
                              N=(8e9, 10e9), A=None),
    "deepseek-v2-lite": dict(L=27, d=2048, H=16, KV=16, V=102400,
                             N=(15e9, 16.5e9), A=(2.2e9, 2.9e9)),
}


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_assignment_row(arch):
    cfg = configs.get_config(arch)
    e = EXPECTED[arch]
    assert cfg.num_layers == e["L"]
    assert cfg.d_model == e["d"]
    assert cfg.vocab_size == e["V"]
    if e["H"] is not None:
        assert cfg.num_heads == e["H"]
        assert cfg.num_kv_heads == e["KV"]
    lo, hi = e["N"]
    assert lo <= cfg.param_count() <= hi, cfg.param_count()
    if e["A"]:
        lo, hi = e["A"]
        assert lo <= cfg.active_param_count() <= hi
    assert cfg.source, "every config must cite its source"


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_smoke_config_caps(arch):
    s = configs.get_smoke_config(arch)
    assert s.num_layers <= 3
    assert s.d_model <= 512
    assert s.num_experts <= 4
    assert s.family == configs.get_config(arch).family


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_padded_vocab_shardable(arch):
    cfg = configs.get_config(arch)
    assert cfg.padded_vocab % 2048 == 0
    assert cfg.padded_vocab % 32 == 0          # 16-way model x 2 pods
    assert cfg.padded_vocab >= cfg.vocab_size


def test_long_500k_policy():
    """long_500k runs iff decode state is bounded (DESIGN.md §4)."""
    shape = configs.INPUT_SHAPES["long_500k"]
    eligible = {a for a in configs.ARCH_IDS
                if configs.shape_applicable(configs.get_config(a), shape)[0]}
    assert eligible == {"mamba2-1.3b", "recurrentgemma-9b",
                        "mixtral-8x22b", "h2o-danube-3-4b"}


def test_input_shapes_exact():
    s = configs.INPUT_SHAPES
    assert (s["train_4k"].seq_len, s["train_4k"].global_batch) == (4096, 256)
    assert (s["prefill_32k"].seq_len,
            s["prefill_32k"].global_batch) == (32768, 32)
    assert (s["decode_32k"].seq_len,
            s["decode_32k"].global_batch) == (32768, 128)
    assert (s["long_500k"].seq_len,
            s["long_500k"].global_batch) == (524288, 1)
