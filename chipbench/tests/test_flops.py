"""flops.py against counts made by hand from the published sizes."""

import json

import pytest

from chipbench import flops, harness

CONFIGS = harness.PACKAGE / "configs"


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_train_count_is_3_61_gflop_a_token():
    cfg = config("mistral-7b-v0.1-d2")
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024            # q, o + k, v
    mlp = 3 * 4096 * 14336
    matmul = 2 * (attn + mlp) + 4096 * 32000            # two layers + the head, no embedding
    assert matmul == 567_279_616
    by_hand = 6 * matmul + 6 * 2 * 4096 * 4096          # + causal attention 6*L*h*S
    assert flops.train_flops_per_token(cfg, 4096) == by_hand
    assert round(by_hand / 1e9, 2) == 3.61


def test_flash_work_is_the_attention_term_of_the_step():
    cfg = config("mistral-7b-v0.1-d2")
    tokens = 2 * 4096
    assert flops.flash_train_flops(cfg, 2, 4096) == 6 * 2 * 4096 * 4096 * tokens
    least, bound = flops.roofline_seconds(flops.flash_train_flops(cfg, 2, 4096),
                                          flops.flash_train_bytes(cfg, 2, 4096),
                                          flops.load_peaks("TPU v5 lite"))
    assert bound == "compute" and 8.0e-3 < least < 8.8e-3


def test_serve_count_at_one_prompt_and_one_decode_token():
    cfg = config("mixtral-8x7b-v0.1-d3")
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    active = attn + 4096 * 8 + 2 * 3 * 4096 * 14336     # router + top-2 experts
    head = 4096 * 32000
    # a prompt of 100 tokens whose first token fell in the window: every prompt
    # token through three layers at a mean context of 50.5 keys, the head once
    prompt = 100 * 3 * (2 * active + 4 * 4096 * 50.5) + 2 * head
    assert flops.serve_request_flops(cfg, 100, True, []) == pytest.approx(prompt)
    # one decode step at context 101: three layers and the head
    decode = 3 * (2 * active + 4 * 4096 * 101) + 2 * head
    assert flops.serve_request_flops(cfg, 100, False, [101]) == pytest.approx(decode)
    assert round(decode / 1e9, 2) == 2.63


def test_decode_tick_bytes_count_the_experts_expected_to_be_hit():
    cfg = config("mixtral-8x7b-v0.1-d3")
    assert flops.expected_experts_read(cfg, 1) == pytest.approx(2.0)
    assert flops.expected_experts_read(cfg, 8) == pytest.approx(8 * (1 - 0.75 ** 8))
    every = flops.decode_tick_bytes(cfg, 1e9, 0.0)       # so many slots that all are hit
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert every == pytest.approx(
        2 * (3 * (attn + 4096 * 8 + 8 * 3 * 4096 * 14336) + 4096 * 32000))
    kv_row = 3 * 2 * 1024 * 2                            # layers x (k, v) x width x bytes
    assert flops.decode_tick_bytes(cfg, 8, 500) - flops.decode_tick_bytes(cfg, 8, 0) == \
        pytest.approx(8 * 500 * kv_row)


def test_unknown_device_kind_is_an_error():
    assert flops.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.load_peaks("TPU v9 imaginary")
