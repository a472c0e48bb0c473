"""The traffic generator: fixed work per seed, deterministic order (CPU)."""
import json
import pathlib

import numpy as np
import pytest

from bench import generate

TRAFFIC = pathlib.Path(__file__).resolve().parent / "traffic"


def mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def test_quota_by_largest_remainder():
    assert generate.quota({"512": 0.5, "1024": 0.3, "2048": 0.2}, 8) == [
        512] * 4 + [1024] * 2 + [2048] * 2
    assert generate.quota({"512": 0.5, "1024": 0.3, "2048": 0.2}, 4) == [
        512, 512, 1024, 2048]
    assert generate.quota({"16": 0.4, "32": 0.3, "64": 0.2, "128": 0.1}, 16) == (
        [16] * 6 + [32] * 5 + [64] * 3 + [128] * 2)
    assert generate.quota({"1024": 0.5, "2048": 0.3, "4096": 0.2}, 10) == (
        [1024] * 5 + [2048] * 3 + [4096] * 2)


@pytest.mark.parametrize("name", ["longprompt", "chat"])
def test_serve_round_is_deterministic_and_seed_keeps_the_work(name):
    m = mix(name)
    seed = 2**33 + 7
    a = generate.serve_round(m, 151936, seed)
    b = generate.serve_round(m, 151936, seed)
    assert [(t.tolist(), n) for t, n in a] == [(t.tolist(), n) for t, n in b]
    c = generate.serve_round(m, 151936, seed + 1)
    assert [t.tolist() for t, _ in a] != [t.tolist() for t, _ in c]
    shape = lambda r: [(len(t), n) for t, n in r]
    assert shape(a) == shape(c)                # the same sizes in one order
    assert len(a) == m["requests_per_round"]
    assert all(t.dtype == np.int32 and 0 <= t.min() and t.max() < 151936
               for t, _ in a)


def test_relayout_requests_are_deterministic():
    m = mix("kv")
    a = generate.relayout_requests(m, 12345678901)
    assert a == generate.relayout_requests(m, 12345678901)
    assert sorted(a) == sorted(generate.relayout_requests(m, 5))
    assert sorted(a) == [1024] * 5 + [2048] * 3 + [4096] * 2
