"""The trace reduction on a small synthetic trace: busy union, idle share,
idle gaps named by the innermost host span, self time of nested ops."""
import random

import pytest

from benchlib.trace import (
    TraceData,
    covered,
    idle_gaps,
    innermost,
    innermost_each,
    merge,
    module_seconds,
    reduce,
    self_times,
    short_op,
    span_idle_seconds,
)

MS = 1_000_000  # ns


def trace(devices=1):
    ops = [("%fusion.1 = f32[2] fusion(f32[2] %a), kind=kLoop", 0, 10 * MS),
           ("%copy.2 = f32[2] copy(f32[2] %b)", 5 * MS, 20 * MS),
           ("%while.3 = (s32[]) while((s32[]) %t), body=%b", 30 * MS, 40 * MS),
           ("%fusion.4 = f32[2] fusion(f32[2] %c), kind=kOutput", 32 * MS, 36 * MS)]
    spans = [("bench.window", 0, 50 * MS), ("engine.step", 15 * MS, 35 * MS),
             ("np.asarray(jax.Array)", 22 * MS, 28 * MS), ("engine.step", 40 * MS, 50 * MS)]
    mods = [("jit_step(1)", 0, 20 * MS), ("jit_chunk(2)", 30 * MS, 40 * MS)]
    return TraceData([list(ops)] * devices, [list(mods)] * devices, spans)


def test_busy_is_the_union_and_idle_share_its_complement():
    red = reduce(trace())
    assert red.window_s == pytest.approx(0.050)
    assert red.busy_s == pytest.approx(0.030)  # [0, 20] and [30, 40]
    assert red.idle_share == pytest.approx(0.4)


def test_busy_is_averaged_over_devices():
    td = trace(devices=2)
    td.device_ops[1] = [("%x = f32[] add()", 0, 50 * MS)]
    assert reduce(td).busy_s == pytest.approx((0.030 + 0.050) / 2)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    red = reduce(trace())
    assert dict(red.idle_by_span) == pytest.approx(
        {"np.asarray(jax.Array)": 0.010, "engine.step": 0.010})


def test_gaps_merge_and_clip_to_the_window():
    busy = merge([("a", -5, 3), ("b", 2, 4), ("c", 8, 12)], 0, 10)
    assert busy == [(0, 4), (8, 10)]
    assert idle_gaps(busy, 0, 10) == [(4, 8)]
    assert innermost([("w", 0, 10), ("s", 4, 8)], 5) == "s"
    assert innermost([("w", 0, 10)], 11) is None


def test_nested_ops_count_their_own_time_only():
    own = {n: t for n, _, _, t in self_times(trace().device_ops[0])}
    assert own["%while.3 = (s32[]) while((s32[]) %t), body=%b"] == 6 * MS
    top = dict(reduce(trace()).top_ops)
    assert top["while.3 (while)"] == pytest.approx(0.006)
    assert top["fusion.4 (fusion, kOutput)"] == pytest.approx(0.004)
    assert top["copy.2 (copy)"] == pytest.approx(0.015)


def test_program_time_and_idle_inside_spans():
    td = trace()
    red = reduce(td)
    assert module_seconds(td, "jit_step(", red.lo, red.hi) == pytest.approx(0.020)
    assert module_seconds(td, "jit_chunk(", red.lo, red.hi) == pytest.approx(0.010)
    idle, n = span_idle_seconds(td, red, "engine.step")
    assert n == 2 and idle == pytest.approx(0.010 + 0.010)


def test_short_op_names():
    assert short_op("%fusion.12 = bf16[2]{0} fusion(bf16[2]{0} %p), kind=kOutput, calls=%f") \
        == "fusion.12 (fusion, kOutput)"
    assert short_op("%copy-start.1 = (bf16[2]{0}, u32[]) copy-start(bf16[2]{0} %g)") \
        == "copy-start.1 (copy-start)"


def test_a_trace_without_device_ops_is_refused():
    td = trace()
    td.device_ops = [[]]
    with pytest.raises(ValueError):
        reduce(td)


def test_the_sweeps_read_what_the_plain_loops_read():
    """The innermost span of many times in one sweep, and the busy time of a
    span found by bisection, against the plain loops over every span and
    every interval."""
    def innermost_loop(spans, t):
        best = None
        for name, s, e in spans:
            if s <= t < e and (best is None or e - s < best[1]):
                best = (name, e - s)
        return None if best is None else best[0]

    rng = random.Random(5)
    spans = [(f"s{i}", float(s), float(s + rng.choice([1, 2, 5, 5, 30]))) for i, s in
             enumerate(rng.randrange(0, 200) for _ in range(300))]
    times = [rng.uniform(-5, 240) for _ in range(500)] + [s for _, s, _ in spans[:50]]
    assert innermost_each(spans, times) == [innermost_loop(spans, t) for t in times]
    busy = merge(spans, 0.0, 230.0)
    starts = [s for s, _ in busy]
    for _ in range(200):
        lo = rng.uniform(-5, 240)
        hi = lo + rng.uniform(0, 40)
        assert covered(busy, lo, hi, starts) == covered(busy, lo, hi)
