"""bench/work.py against counts made by hand at qwen2-0.5b's shapes."""
import json

from tiny_cell import ROOT
from bench import work

QWEN = json.loads((ROOT / "bench/configs/qwen2-0.5b.json").read_text())


def test_matmul_parameters_per_layer():
    # qwen2-0.5b: q and o 896 x 896, k and v 896 x 128, gate and up
    # 896 x 4864, down 4864 x 896 (494 M parameters in all with the QKV
    # biases, norms and the 151936 x 896 embedding)
    fc = 2 * 896 * 896 + 2 * 896 * 128 + 3 * 896 * 4864
    assert work.layer_matmul_params(QWEN) == fc == 14_909_440
    assert [(k, n) for _, k, n in work.fc_shapes(QWEN)] == [
        (896, 896), (896, 128), (896, 128), (896, 896),
        (896, 4864), (896, 4864), (4864, 896)]


def test_fc_call():
    f, b = work.fc_call(32, 896, 4864)
    assert f == 2 * 32 * 896 * 4864
    assert b == 2 * (896 * 4864 + 32 * 896 + 32 * 4864)


def test_attention_work():
    # one qwen decode row over 1000 positions: 4 * 1000 * 14 * 64 FLOPs and
    # 1000 * 2 * 2 * 64 * 2 B of K and V per layer (12,288 B per position
    # over 24 layers), plus q in and out
    f, b = work.attention_work(QWEN, 1000)
    assert f == 24 * 4 * 1000 * 14 * 64
    assert b == 24 * (1000 * 2 * 2 * 64 + 2 * 14 * 64) * 2
    # a prompt chunk of 64 rows over 100 positions reads K and V once
    f, b = work.attention_work(QWEN, 100, q_rows=64)
    assert f == 24 * 4 * 64 * 100 * 14 * 64
    assert b == 24 * (100 * 2 * 2 * 64 + 2 * 64 * 14 * 64) * 2


def test_token_and_prompt_flops():
    n = 2 * 24 * 14_909_440
    assert work.token_flops(QWEN, 10, logits=False) == n + 4 * 24 * 14 * 64 * 10
    assert work.token_flops(QWEN, 10, logits=True) == (
        n + 4 * 24 * 14 * 64 * 10 + 2 * 896 * 151936)
    assert work.prompt_flops(QWEN, 3) == (
        3 * n + 4 * 24 * 14 * 64 * (1 + 2 + 3) + 2 * 896 * 151936)
