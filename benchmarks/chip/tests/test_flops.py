"""The work counts against hand arithmetic."""
import common
import flops


def test_smollm_param_count():
    spec = common.config("smollm-135m")["model"]
    # embedding 49152*576; per layer q,o 576*576, k,v 576*192, mlp 3*576*1536,
    # two norms of 576; final norm 576; tied head
    per_layer = 2 * 576 * 576 + 2 * 576 * 192 + 3 * 576 * 1536 + 2 * 576
    assert flops.param_count(spec) == 49152 * 576 + 30 * per_layer + 576
    assert flops.param_count(spec) == 134_515_008


def test_stablelm_8l_param_count():
    spec = common.config("stablelm-3b-8l")["model"]
    per_layer = 4 * 2560 * 2560 + 3 * 2560 * 6912 + 2 * 2560
    n = 2 * 50304 * 2560 + 8 * per_layer + 2560
    assert flops.param_count(spec) == n == 891_988_480


def test_kv_bytes_per_token():
    assert flops.kv_bytes_per_token(
        common.config("smollm-135m")["model"]) == 23_040
    assert flops.kv_bytes_per_token(
        common.config("stablelm-3b-8l")["model"]) == 81_920


def test_decode_and_prefill_counts():
    spec = common.config("smollm-135m")["model"]
    mm = flops.matmul_params(spec)
    assert mm == 30 * (2 * 576 * 576 + 2 * 576 * 192 + 3 * 576 * 1536) \
        + 49152 * 576
    attn1 = 4 * 30 * 9 * 64
    assert flops.decode_flops(spec, [9, 19]) == 2 * mm * 2 + attn1 * 30
    assert flops.decode_bytes(spec, [9, 19]) == 2 * mm + 23_040 * 30
    # positions 4..6 of a prompt: 3 tokens through the layers, keys 5+6+7,
    # the head once
    body = 2 * (mm - 49152 * 576)
    assert flops.prefill_flops(spec, 4, 3) == body * 3 + attn1 * 18 \
        + 2 * 49152 * 576
    peak = common.peaks("TPU v5 lite")
    least = flops.decode_least_s(spec, [100] * 64, peak)
    assert least == flops.decode_bytes(spec, [100] * 64) / 819e9


def test_train_flops_per_token():
    spec = common.config("smollm-135m")["model"]
    per = flops.train_flops_per_token(spec, 2048)
    assert per == 6 * flops.matmul_params(spec) + 3 * 4 * 30 * 9 * 64 * 1024.5
    assert 0.9e9 < per < 1.2e9
