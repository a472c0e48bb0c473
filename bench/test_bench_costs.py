"""FLOP and byte counts at the cells' shapes (CPU)."""
import json
import pathlib


from bench import weights
from bench.costs import lm as C
from bench.costs import relayout as RL

CONFIGS = pathlib.Path(__file__).resolve().parent / "configs"


def dims(name):
    return weights.dims(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_relayout_bytes_per_request():
    # 28 layers, K and V of (1, S, 8, 128) bf16, four passes
    assert RL.request_bytes(28, 4096, 8, 128, 2) == 1_879_048_192
    assert RL.request_bytes(28, 1, 8, 128, 2) == 458_752
    assert RL.request_min_hbm_bytes(28, 4096, 8, 128, 2) == 1_879_048_192 // 2


def test_qwen3_parameter_count_matches_the_published_model():
    m = dims("qwen3-1.7b")
    assert (m["L"], m["d"], m["H"], m["KV"], m["hd"], m["F"], m["V"]) == (
        28, 2048, 16, 8, 128, 6144, 151936)
    # the program's own count of qwen3-1.7b at this width (tied embeddings)
    assert C.param_count(m) == 1_720_574_976


def test_prefill_and_decode_flops():
    m = dims("qwen3-1.7b")
    per_token = 2 * m["L"] * C.layer_matmul_params(m)
    head = 2 * m["d"] * m["V"]
    assert C.decode_flops(m, 0) == per_token + C.attention_flops(m, 1) + head
    assert C.attention_flops(m, 10) == 4 * 28 * 16 * 128 * 10
    # causal prefill: position p attends p + 1 keys
    S = 512
    assert C.prefill_flops(m, S) == (per_token * S + head
                                     + 4 * 28 * 16 * 128 * S * (S + 1) // 2)
    # decode flops grow with the cache by the attention term only
    assert C.decode_flops(m, 99) - C.decode_flops(m, 0) == C.attention_flops(m, 99)


def test_kv_bytes_per_position():
    m = dims("qwen3-1.7b")
    assert C.kv_bytes_per_position(m, 2) == 2 * 28 * 8 * 128 * 2
