"""The shape functions against hand-worked operations and bytes."""
import pytest

from benchmarks.harness import shapes
from benchmarks.tables import dense_decoder as tables

MISTRAL_2L = {"hidden_size": 4096, "head_dim": 128, "num_attention_heads": 32,
              "num_key_value_heads": 8, "intermediate_size": 14336,
              "num_hidden_layers": 2, "vocab_size": 32768}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_train_flops_per_token():
    # a layer: 4096*6144 + 4096*4096 + 3*4096*14336 = 218,103,808
    assert tables.matmul_params(MISTRAL_2L) == 2 * 218103808 + 4096 * 32768
    # 6 * 570,425,344 + 6*2*32*128*4096
    assert tables.train_flops_per_token(MISTRAL_2L, 4096) == pytest.approx(
        6 * 570425344 + 201326592)


def test_flash_call():
    w = shapes.flash_fwd_bwd(batch=2, seq=4096, heads=32, kv_heads=8,
                             head_dim=128)
    fwd = 2 * 2 * 2 * 32 * 4096 * 4096 * 128 / 2     # 274,877,906,944
    assert w["flops"] == pytest.approx(3.5 * fwd)
    q, kv = 2 * 4096 * 32 * 128 * 2, 2 * 4096 * 8 * 128 * 2
    assert w["bytes"] == 6 * q + 6 * kv
    r = shapes.roofline_seconds(w, PEAKS)
    assert r["bound"] == "compute"
    assert r["seconds"] == pytest.approx(3.5 * fwd / 197e12)


def test_paged_decode_call():
    w = shapes.paged_decode([100, 300], heads=16, kv_heads=8, head_dim=128)
    assert w["flops"] == 2 * 2 * 400 * 16 * 128
    assert w["bytes"] == 2 * 400 * 8 * 128 * 2 + 2 * 2 * 16 * 128 * 2
    assert shapes.roofline_seconds(w, PEAKS)["bound"] == "memory"


def test_paged_prefill_call():
    w = shapes.paged_prefill(chunk=128, context_before=1024, heads=32,
                             kv_heads=8, head_dim=128)
    pairs = 128 * 1024 + 128 * 129 / 2
    assert w["flops"] == 2 * 2 * pairs * 32 * 128
    assert w["bytes"] == 2 * 1152 * 8 * 128 * 2 + 2 * 128 * 32 * 128 * 2
