"""Assigned-architecture configs match the published specs exactly."""
import pytest

from repro.configs import ARCH_IDS, LONG_CONTEXT_ARCHS, SHAPES, get_config
from repro.configs.base import arch_shape_cells

EXPECTED = {
    # arch: (L, d_model, H, KV, d_ff, vocab)
    "stablelm-3b": (32, 2560, 32, 32, 6912, 50304),
    "glm4-9b": (40, 4096, 32, 2, 13696, 151552),
    "qwen2-1.5b": (28, 1536, 12, 2, 8960, 151936),
    "gemma2-9b": (42, 3584, 16, 8, 14336, 256000),
    "rwkv6-3b": (32, 2560, 40, 40, 8960, 65536),
    "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
    "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
    "moonshot-v1-16b-a3b": (27, 2048, 16, 16, 1408, 163840),
    "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151936),
    "pixtral-12b": (40, 5120, 32, 8, 14336, 131072),
}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_exact_config(arch):
    cfg = get_config(arch)
    L, D, H, KV, FF, V = EXPECTED[arch]
    assert cfg.num_layers == L
    assert cfg.d_model == D
    assert cfg.num_heads == H
    assert cfg.num_kv_heads == KV
    assert cfg.d_ff == FF
    assert cfg.vocab_size == V


def test_arch_specific_features():
    assert get_config("qwen2-1.5b").qkv_bias
    g = get_config("gemma2-9b")
    assert g.attn_softcap == 50.0 and g.final_softcap == 30.0
    assert g.sliding_window == 4096 and g.local_global_alternating
    assert get_config("zamba2-2.7b").ssm.d_state == 64
    m = get_config("moonshot-v1-16b-a3b").moe
    assert m.num_experts == 64 and m.top_k == 6
    q = get_config("qwen3-moe-235b-a22b").moe
    assert q.num_experts == 128 and q.top_k == 8
    assert get_config("musicgen-large").pos_emb == "sinusoidal"
    assert get_config("rwkv6-3b").rwkv.head_size == 64


def test_param_counts_in_published_range():
    """Sanity: total params land near the advertised sizes."""
    expect = {"stablelm-3b": (2.0e9, 4.5e9), "glm4-9b": (8e9, 11e9),
              "qwen2-1.5b": (1.2e9, 2.1e9), "gemma2-9b": (8e9, 11e9),
              "rwkv6-3b": (2.5e9, 4e9), "zamba2-2.7b": (2.2e9, 3.5e9),
              "moonshot-v1-16b-a3b": (15.5e9, 16.5e9),   # Moonlight-16B
              "qwen3-moe-235b-a22b": (2.1e11, 2.6e11),
              "pixtral-12b": (1.0e10, 1.4e10)}
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, (arch, n)


def test_moe_active_params():
    q = get_config("qwen3-moe-235b-a22b")
    assert 1.5e10 <= q.active_param_count() <= 2.6e10   # ~22B active
    m = get_config("moonshot-v1-16b-a3b")
    assert 2.7e9 <= m.active_param_count() <= 3.1e9  # ~2.9B: the "A3B"


def test_cell_enumeration():
    cells = arch_shape_cells()
    assert len(cells) == 33                               # 10*3 + 3 long_500k
    longs = [a for a, s in cells if s == "long_500k"]
    assert set(longs) == set(LONG_CONTEXT_ARCHS)


def test_smoke_configs_are_small():
    for arch in ARCH_IDS:
        cfg = get_config(arch, smoke=True)
        assert cfg.param_count() < 5e6, arch
        assert cfg.family == get_config(arch).family
