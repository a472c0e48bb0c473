"""Small sizes at which the tests run whole cells on the CPU.

Tests pass these to ``harness.execute``/``harness.prepare`` with
``require_tpu=False``; the benchmark's own runs never use them.  Widths
are cut to what the CPU and the Pallas interpreter run in seconds; the
KV row stays 128 wide, the store's tile width.
"""
from bench import harness

SERVE_CELL = "serve-qwen3-1.7b-longprompt"
RELAYOUT_CELL = "relayout-qwen3-1.7b-kv"


def config(name="qwen3-1.7b"):
    cfg = dict(harness.read_json(harness.BENCH / "configs" / f"{name}.json"))
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=64, intermediate_size=128, vocab_size=512,
               num_hidden_layers=2)
    return cfg


def mix(cell):
    spec = harness.find_cell(harness.benchmark_spec(), cell)
    m = dict(harness.read_json(harness.BENCH / "traffic" / f"{spec['traffic']}.json"))
    if m["driver"] == "kv_relayout":
        m.update(requests=3, seq_len={"64": 0.5, "128": 0.5}, trace_seconds=0.3)
    else:
        m.update(requests_per_round=4, max_batch=4,
                 prompt_len={"16": 0.5, "32": 0.5},
                 answer_len={"4": 0.5, "8": 0.5}, trace_seconds=0.3)
    return m


def kw(cell):
    """Keyword arguments of ``harness.execute`` for a small CPU run."""
    return dict(require_tpu=False, config=config(), mix=mix(cell))
