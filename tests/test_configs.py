"""Architecture configs match the assignment table."""

import pytest

from repro import configs
from repro.common.config import SHAPES


def test_registry_complete():
    assert len(configs.list_archs()) == 10
    for a in configs.list_archs():
        cfg = configs.get(a)
        red = configs.reduced(a)
        assert cfg.family == red.family
        assert cfg.num_layers >= 2


SPEC = {
    # arch: (L, d_model, H, kv, vocab)
    "granite_moe_1b_a400m": (24, 1024, 16, 8, 49155),
    "kimi_k2_1t_a32b": (61, 7168, 64, 8, 163840),
    "mamba2_780m": (48, 1536, 0, 0, 50280),
    "jamba_1_5_large_398b": (72, 8192, 64, 8, 65536),
    "mistral_nemo_12b": (40, 5120, 32, 8, 131072),
    "qwen2_5_32b": (64, 5120, 40, 8, 152064),
    "smollm_360m": (32, 960, 15, 5, 49152),
    "granite_3_2b": (40, 2048, 32, 8, 49155),
    "seamless_m4t_large_v2": (24, 1024, 16, 16, 256206),
    "paligemma_3b": (18, 2048, 8, 1, 257216),
}


@pytest.mark.parametrize("arch", configs.list_archs())
def test_spec_dims(arch):
    L, d, h, kv, v = SPEC[arch]
    cfg = configs.get(arch)
    assert cfg.num_layers == L
    assert cfg.d_model == d
    assert cfg.num_heads == h
    assert cfg.num_kv_heads == kv
    assert cfg.vocab_size == v


# name-implied parameter counts (total, rtol) — sanity that the analytic
# counter and the config agree with the published sizes.
PARAMS = {
    "kimi_k2_1t_a32b": (1.04e12, 0.08),
    "mamba2_780m": (780e6, 0.15),
    "jamba_1_5_large_398b": (398e9, 0.10),
    "mistral_nemo_12b": (12.2e9, 0.10),
    "qwen2_5_32b": (32.5e9, 0.10),
    "smollm_360m": (360e6, 0.15),
    "granite_3_2b": (2.5e9, 0.25),
    "paligemma_3b": (2.5e9, 0.15),   # gemma-2b language tower of the 3B VLM
    "granite_moe_1b_a400m": (1.3e9, 0.25),
}


@pytest.mark.parametrize("arch", sorted(PARAMS))
def test_param_counts(arch):
    target, rtol = PARAMS[arch]
    n = configs.get(arch).param_count()
    assert abs(n - target) / target < rtol, (arch, n, target)


def test_active_params_kimi():
    cfg = configs.get("kimi_k2_1t_a32b")
    a = cfg.active_param_count()
    assert 25e9 < a < 40e9, a  # "a32b"
    assert a < cfg.param_count() / 10


def test_shape_cells():
    cells = configs.all_cells()
    # 10 archs x 4 shapes - 8 long_500k skips (pure-attention archs)
    assert len(cells) == 32
    long_archs = {a for a, s in cells if s == "long_500k"}
    assert long_archs == {"mamba2_780m", "jamba_1_5_large_398b"}


def test_shapes_table():
    assert SHAPES["train_4k"].tokens == 4096 * 256
    assert SHAPES["long_500k"].seq_len == 524288
    assert SHAPES["decode_32k"].kind == "decode"


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_device_kind_table_describes_v5e(kind):
    from repro.common import hw

    assert hw.chip_for(kind) is hw.V5E


def test_unknown_device_kind_is_an_error():
    from repro.common import hw

    with pytest.raises(ValueError, match="not described"):
        hw.chip_for("TPU v9 imaginary")


def test_compile_cache_dir(monkeypatch):
    """The env var wins untouched; without it the cache sits at the fixed
    `<checkout>/.jax_cache`. The config is restored before anything
    compiles, so the test session stays cache-free."""
    import os

    import jax

    from repro.common import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable()
        assert path == os.path.join(compile_cache.CHECKOUT, ".jax_cache")
        assert os.path.isfile(os.path.join(compile_cache.CHECKOUT,
                                           "chip_smoke.py"))
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
