import json

import flops
from conftest import ROOT


def model(name):
    return json.loads((ROOT / "benchmarks/cr/configs" / f"{name}.json").read_text())["model"]


def test_qwen2_by_hand():
    # per layer: q 896x896, k and v 896x128 each, o 896x896, 3 x 896x4864
    layer = 896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864
    assert layer == 14_909_440
    weights = 24 * layer + 896 * 151_936          # tied head once
    assert weights == 493_961_216
    attn = 12 * 24 * 14 * 64 * 128                # QK^T and PV, fwd + bwd
    assert flops.flops_per_token(model("qwen2-0.5b"), 128) == 6 * weights + attn
    assert 6 * weights + attn == 2_996_797_440


def test_zamba2_by_hand():
    D, E, N, H, P, W, S = 2048, 4096, 64, 64, 64, 4, 128
    mamba = D * (2 * E + 2 * N + H) + E * D + W * (E + 2 * N)
    assert mamba == 25_575_936
    attn = 4 * D * D + 3 * D * 8192
    group = 6 * mamba + 2 * D * D + attn
    weights = 2 * group + D * 32_000
    ssd = 6 * H * S * (N + P)
    extra = 2 * (6 * ssd + 12 * 32 * 64 * S)
    got = flops.flops_per_token(model("zamba2-1.2b"), S)
    assert got == 6 * weights + extra
    assert got == 3_222_441_984
