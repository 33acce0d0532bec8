"""Percentiles, open-loop timing, traffic, and the trace reduction, on
hand-made samples."""

import math

import pytest

from benchmark import readers, stats, trace, traffic

MIX = {"shape_seed": 5, "arrivals": {"rate_per_s": 50.0},
       "prompt_tokens": {"median": 64, "sigma": 1.0, "min": 8, "max": 512},
       "output_tokens": {"median": 16, "sigma": 0.7, "min": 2, "max": 64},
       "pool": 300}


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (95, 4.8),
                                    (100, 5.0)])
def test_percentile_interpolates(q, want):
    assert stats.percentile([5, 1, 4, 2, 3], q) == pytest.approx(want)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_open_loop_times_run_from_due_not_from_submit():
    recs = [
        {"due": 10.0, "submitted": 10.2, "token_times": [10.5, 10.6, 10.9]},
        {"due": 11.0, "submitted": 11.0, "token_times": []},     # never began
    ]
    ttft = stats.ttft_ms(recs)
    assert ttft[0] == pytest.approx(500.0)          # not 300: due, not submit
    assert math.isinf(ttft[1])                      # missing counts as a miss
    assert stats.percentile(ttft, 95) == math.inf
    assert stats.token_gaps_ms(recs) == pytest.approx([100.0, 300.0])
    assert stats.lateness_ms(recs) == pytest.approx([200.0, 0.0])
    assert stats.tokens_in_window(recs, 10.55, 10.9) == 1


def test_schedule_repeats_for_a_seed_and_differs_across_seeds():
    a = traffic.open_loop_schedule(MIX, 7, 4.0)
    b = traffic.open_loop_schedule(MIX, 7, 4.0)
    c = traffic.open_loop_schedule(MIX, 8, 4.0)
    assert a == b
    assert [r["due_s"] for r in a] == [r["due_s"] for r in c]
    pairs = lambda s: [(r["prompt_len"], r["max_new"]) for r in s]
    assert pairs(a) != pairs(c)
    # every seed offers the same work: the same multiset, another order,
    # and that already inside every block of 16 requests
    assert sorted(pairs(a)) == sorted(pairs(c))
    assert sorted(pairs(a)[16:32]) == sorted(pairs(c)[16:32])
    assert pairs(a)[16:32] != pairs(c)[16:32]
    assert 120 < len(a) < 280                        # 50/s for 4 s
    assert all(8 <= p <= 512 and 2 <= m <= 64 for p, m in pairs(a))
    longer = traffic.open_loop_schedule(MIX, 7, 6.0)
    assert [r["due_s"] for r in longer[:len(a)]] == [r["due_s"] for r in a]


def test_bursts_raise_the_rate_inside_their_windows():
    mix = {**MIX, "arrivals": {"rate_per_s": 50.0, "burst_amp": 2.0,
                               "burst_every_s": 2.0, "burst_width_s": 1.0}}
    times = traffic.arrival_times(mix, 20.0)
    inside = sum(1 for t in times if t % 2.0 < 1.0)
    assert inside > 2 * (len(times) - inside)


def test_closed_loop_pool_and_tokens():
    a, b = traffic.closed_loop_pool(MIX, 1), traffic.closed_loop_pool(MIX, 2)
    assert len(a) == 300 and a != b and sorted(a) == sorted(b)
    t = traffic.prompt_tokens(2**31 + 5, 3, 40, 100)
    assert t.shape == (40,) and t.min() >= 0 and t.max() < 100
    assert (t == traffic.prompt_tokens(2**31 + 5, 3, 40, 100)).all()
    assert (t != traffic.prompt_tokens(2**31 + 5, -3, 40, 100)).any()


# -- the trace reduction on a hand-built event list ---------------------------

OPS = [("fusion.1", 0.0, 1.0), ("all-reduce.3", 1.0, 2.0),
       ("fusion.2", 1.5, 2.5), ("custom-call.9", 4.0, 5.0)]
SPANS = [("input", 2.5, 3.0), ("train_step", 3.0, 5.0),
         ("engine.step:decode", 0.0, 2.0)]


def test_union_and_gaps():
    assert trace.union([(0, 1), (0.5, 2), (3, 4), (4, 4)]) == [(0, 2), (3, 4)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert trace.gaps([(1, 2)], 0, 3) == [(0, 1), (2, 3)]


def test_busy_idle_and_top_ops():
    busy, window = trace.busy_and_window({"d0": OPS, "d1": OPS[:1]}, 0.0, 5.0)
    assert window == 5.0
    assert busy == pytest.approx((3.5 + 1.0) / 2)   # averaged over devices
    top = trace.top_ops({"d0": OPS}, 0.0, 5.0, n=2)
    assert [t[0] for t in top] == ["fusion.1", "fusion.2"]


def test_self_time_takes_nested_events_out_of_their_parent():
    ops = [("%while.1 = (s32[]) while(...)", 0.0, 10.0),
           ("%fusion.7 = f32[8] fusion(f32[8] %p), kind=kLoop", 1.0, 4.0),
           ('%closed_call.2 = bf16[2] custom-call(), '
            'custom_call_target="tpu_custom_call", x=1', 4.0, 9.0)]
    assert dict(trace.self_times(ops))[ops[0][0]] == pytest.approx(2.0)
    top = trace.top_ops({"d0": ops}, 0.0, 10.0)
    assert top == [["closed_call.2 [tpu_custom_call]", pytest.approx(5.0)],
                   ["fusion.7 [kLoop]", pytest.approx(3.0)],
                   ["while.1", pytest.approx(2.0)]]


def test_gaps_are_named_by_the_host_span_that_covers_them():
    rows = dict(trace.gaps_by_span(OPS, SPANS, 0.0, 5.0))
    # idle 2.5-4.0: 0.5 s under "input", 1.0 s under "train_step" -> one gap,
    # named by the span that covers most of it
    assert rows == {"train_step": pytest.approx(1.5)}
    rows = dict(trace.gaps_by_span(OPS, [], 0.0, 5.0))
    assert rows == {"(none)": pytest.approx(1.5)}


def test_exposed_collective_time_and_spans():
    # the all-reduce runs 1.0-2.0; fusion.2 overlaps it from 1.5
    assert trace.exposed_collective_s(OPS, 0.0, 5.0) == pytest.approx(0.5)
    assert trace.busy_inside(OPS, 3.0, 5.0) == pytest.approx(1.0)
    assert trace.share_matching({"d0": OPS}, "custom-call", 0.0, 5.0) == \
        pytest.approx(1.0 / 3.5)
    kernel = [('%c.1 = f32[] custom-call(), custom_call_target="tpu_custom_call"',
               0.0, 1.0), ("%fusion.3 = f32[] fusion()", 1.0, 4.0)]
    assert readers.pallas_share_pct(
        {"trace": {"device_ops": {"d0": kernel}, "lo": 0.0, "hi": 4.0}}) == \
        pytest.approx(25.0)


def test_readers_over_a_hand_built_summary():
    facts = {"trace": {"device_ops": {"d0": OPS, "d1": OPS}, "spans": SPANS,
                       "lo": 0.0, "hi": 5.0, "busy_s": 3.5, "window_s": 5.0}}
    assert readers.device_idle_pct(facts) == pytest.approx(30.0)
    assert readers.span_device_ms(facts, "engine.step:decode") == \
        pytest.approx(2000.0)
    assert readers.span_device_share_pct(facts, "train_step") == \
        pytest.approx(100.0 / 3.5)
    assert readers.exposed_collective_ms_per_step(
        facts, "engine.step:decode") == pytest.approx(500.0)
    assert readers.device_idle_pct({}) is None


def test_reduction_of_a_recorded_chip_trace(tmp_path):
    """Four profiled steps of ``mistral7b-train-1chip`` on one TPU v5e (PR
    23's first chip run), as the profiler wrote them."""
    import gzip
    import os
    import shutil

    src = os.path.join(os.path.dirname(__file__), "data",
                       "train_4steps.xplane.pb.gz")
    path = str(tmp_path / "t.xplane.pb")
    with gzip.open(src, "rb") as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    t = trace.summarize(path)
    assert list(t["device_ops"]) == ["/device:TPU:0"]
    assert [n for n, _, _ in t["spans"]] == ["input", "train_step"] * 4
    assert t["window_s"] == pytest.approx(1.0711, abs=1e-3)
    assert t["busy_s"] == pytest.approx(1.0557, abs=1e-3)
    facts = {"trace": t}
    assert readers.device_idle_pct(facts) == pytest.approx(1.43, abs=0.02)
    assert readers.span_device_ms(facts, "train_step") == \
        pytest.approx(265.0, abs=3.0)
    assert readers.pallas_share_pct(facts) == pytest.approx(10.2, abs=0.1)
    assert readers.exposed_collective_ms_per_step(facts) is None  # one chip
    names = [n for n, _ in t["breakdown"]["device_ops"]]
    assert len(names) == 10 and all(len(n) < 80 for n in names)
    assert t["breakdown"]["idle_gaps"][0][0] in ("input", "train_step")
