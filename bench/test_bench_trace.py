"""The trace reduction on hand-built traces (CPU)."""
import pytest

from bench.trace import reduce as R

KERNEL = ('%run.1 = bf16[256,8,16,128]{3,2,1,0} custom-call(bf16[4096,1024] '
          '%x), custom_call_target="tpu_custom_call"')
FUSION = ('%fusion.3 = (bf16[1,4096], bf16[1,4096]) fusion(bf16[28,4096] %ks),'
          ' kind=kLoop, calls=%fused_computation.59')
COLLECTIVE = '%all-reduce.2 = f32[1024]{0} all-reduce(f32[1024] %g), to_apply=%add'


def test_merge_clip_union_and_gaps():
    iv = [(5, 10), (0, 3), (8, 12), (12, 14), (20, 20)]
    assert R.merge(iv) == [(0, 3), (5, 14)]
    assert R.union_ns(iv, 2, 13) == 1 + 8
    assert R.gaps(iv, 0, 20) == [(3, 5), (14, 20)]
    assert R.gaps([], 0, 4) == [(0, 4)]
    assert R.clip([(0, 5)], 5, 9) == []


def _trace():
    ops = {"/device:TPU:0": [(100, 200, FUSION), (200, 300, KERNEL),
                             (400, 450, KERNEL), (450, 460, COLLECTIVE),
                             (900, 1100, FUSION)],
           "/device:TPU:1": [(100, 500, FUSION)]}
    modules = {"/device:TPU:0": [(100, 460, "jit_roundtrip(123)"),
                                 (900, 1100, "jit_decode_step(9)")],
               "/device:TPU:1": [(100, 500, "jit_roundtrip(123)")]}
    host = [(0, 1000, R.WINDOW_SPAN), (290, 420, "PjitFunction(roundtrip)"),
            (250, 800, "bench.round"), (500, 900, "compose")]
    return R.from_events(ops, host, (0, 1000), modules)


def test_busy_idle_and_window_clip():
    tr = _trace()
    assert tr.window_s == pytest.approx(1e-6)
    # chip 0: 100-300, 400-460, 900-1000 (clipped) = 360 ns; chip 1: 400 ns
    assert tr.busy_s() == pytest.approx((360 + 400) / 2 / 1e9)
    assert tr.idle_share() == pytest.approx(1 - 380 / 1000)


def test_module_times():
    tr = _trace()
    assert tr.module_seconds(lambda n: n == "jit_roundtrip") == pytest.approx(
        (360 + 400) / 2 / 1e9)
    assert tr.module_seconds(lambda n: "decode_step" in n) == pytest.approx(
        100 / 2 / 1e9)


def test_short_names_and_top_ops():
    assert R.short_name(KERNEL) == "run.1 custom-call:tpu_custom_call"
    assert R.short_name(FUSION) == "fusion.3 fusion:kLoop"
    assert R.short_name(COLLECTIVE) == "all-reduce.2 all-reduce"
    top = dict(_trace().top_ops(3))
    assert top["jit_roundtrip/fusion.3 fusion:kLoop"] == pytest.approx(
        (100 + 400) / 2 / 1e9)
    assert top["jit_roundtrip/run.1 custom-call:tpu_custom_call"] == pytest.approx(
        150 / 2 / 1e9)


def test_idle_gaps_named_by_the_innermost_host_event():
    gaps = dict(_trace().idle_gaps())
    # chip 0 idles 0-100 (no host event), 300-400 (mid 350: PjitFunction is
    # the shortest event covering it), 460-900 (mid 680: compose)
    assert gaps == {"(no host event)": pytest.approx(100e-9),
                    "PjitFunction(roundtrip)": pytest.approx(100e-9),
                    "compose": pytest.approx(440e-9)}


def test_a_trace_needs_its_window_span():
    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    class Profile:
        planes = [Plane("/host:CPU", [Line("python3", [])])]

    with pytest.raises(ValueError):
        R.Trace.from_profile(Profile())


def test_busy_time_inside_named_host_spans():
    tr = R.from_events({"/device:TPU:0": [(100, 200, FUSION), (250, 300, KERNEL),
                                          (600, 700, FUSION)]},
                       [(0, 1000, R.WINDOW_SPAN), (90, 260, "bench.decode"),
                        (550, 650, "bench.decode")], (0, 1000))
    assert tr.span_busy_s("bench.decode") == pytest.approx((100 + 10 + 50) / 1e9)
    assert tr.span_busy_s("nothing") == 0.0
