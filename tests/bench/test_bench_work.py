"""Work counts against hand-worked numbers for both configurations."""
import json

import pytest
from tiny import ROOT

from bench.lib import spec, work


def config(name):
    """The published configuration: the file with any cut undone."""
    c = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    return dict(c, **c.get("published", {}))


def test_qwen3_1_7b_flops_per_token():
    c = config("qwen3-1.7b")
    # per layer: q,k,v 2048 x (16+8+8) x 128, o 2048 x 2048, MLP 3 x 2048 x 6144
    layer = 2048 * 32 * 128 + 2048 * 2048 + 3 * 2048 * 6144
    assert spec.family(c).layer_matmul_params(c) == layer == 50_331_648
    # 28 layers and the tied head over the published 151,936 ids
    assert work.matmul_params(c) == 28 * layer + 151_936 * 2048 == 1_720_451_072
    per_token = work.train_flops(c, [1]) - 3 * work.attn_flops(c, 1)
    assert per_token == pytest.approx(10.32e9, rel=1e-3)


def test_qwen3_8b_flops_per_token():
    c = config("qwen3-8b")
    layer = 4096 * 48 * 128 + 4096 * 4096 + 3 * 4096 * 12288
    assert work.matmul_params(c) == 36 * layer + 151_936 * 4096 == 7_568_097_280
    assert 6 * work.matmul_params(c) == pytest.approx(45.4e9, rel=1e-3)


def test_kv_bytes_per_token():
    # 28 layers x (K, V) x 8 heads x 128 x 2 bytes = 112 KiB
    assert work.kv_bytes_per_token(config("qwen3-1.7b")) == 112 * 1024
    assert work.kv_bytes_per_token(config("qwen3-8b")) == 36 * 2 * 8 * 128 * 2


def test_causal_attention_counts_each_position():
    c = config("qwen3-1.7b")
    # a 3-token sequence attends 1 + 2 + 3 keys
    attn = work.train_flops(c, [3]) - 6 * work.matmul_params(c) * 3
    assert attn == pytest.approx(3 * 4 * 28 * 16 * 128 * 6)


def test_decode_least_seconds_by_hand():
    c = config("qwen3-1.7b")
    peak_f, bw = 197e12, 819e9
    # two requests: prompt 10 with 2 tokens, prompt 20 with 1 token
    got = work.decode_least_seconds(c, [10, 20], [2, 1], peak_f, bw)
    kv, n = work.kv_bytes_per_token(c), work.matmul_params(c)
    step0 = max((work.weight_bytes(c, 2) + kv * (11 + 21)) / bw,
                (2 * n * 2 + work.attn_flops(c, 32)) / peak_f)
    step1 = max((work.weight_bytes(c, 1) + kv * 12) / bw,
                (2 * n + work.attn_flops(c, 12)) / peak_f)
    assert got == pytest.approx(step0 + step1)
    # weights dominate: about 3.44 GB a step at 819 GB/s
    assert step1 == pytest.approx(3.44e9 / bw, rel=0.01)


def test_logprob_kernel_bytes():
    b = work.logprob_kernel_bytes(8, 256)
    assert b["fwd"] == 8 * 256 * 4 + 8 * 16
    assert b["bwd"] == 2 * 8 * 256 * 4 + 8 * 24
