"""CPU tests of the ``serve.*`` span reduction (:mod:`spans`): innermost
idle labels, eager dispatch counts, and the arithmetic of each number,
on a hand-written trace; and on the recorded chip trace, which holds no
``serve.*`` span, agreement with :mod:`xtrace`.

    PYTHONPATH=src python -m pytest -q bench/tests
"""
import gzip
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH]

import spans  # noqa: E402
import xtrace  # noqa: E402

US = 1_000_000  # ps

# host: (start us, end us, name, stats) on the python thread
HOST = [
    (0, 100, "window", {}),
    (0, 50, "tick", {}),
    (1, 49, "serve.tick", {"slots": 2}),
    (2, 20, "serve.plan", {"batches": 1}),
    (3, 10, "serve.make_slot", {"retx": 0}),
    (4, 5, "PjitFunction(add)", {}),
    (4.1, 4.9, "PjitFunction(add)", {}),  # the same dispatch, nested
    (6, 7, "PjitFunction(multiply)", {}),
    (11, 19, "serve.make_slot", {"retx": 1}),
    (12, 13, "PjitFunction(sin)", {}),
    (21, 25, "serve.stage", {"lanes": 2, "bucket": 4}),
    (26, 40, "serve.dispatch", {"lanes": 2, "bucket": 4, "mcs": 0}),
    (30, 38, "serve.wait", {}),
    (41, 47, "serve.feedback", {"lanes": 2}),
    (50, 100, "run", {}),
    (52, 70, "serve.batch", {"slots": 3}),
    (53, 56, "serve.stack", {}),
    (57, 65, "serve.dispatch", {}),
    (58, 64, "serve.wait", {}),
    (60.5, 61, "PjitFunction(add)", {}),  # outside every make_slot
    (66, 69, "serve.slot_metrics", {}),
    (72, 90, "serve.batch", {"slots": 5}),
    (73, 77, "serve.stack", {}),
    (78, 84, "serve.dispatch", {}),
    (79, 83, "serve.wait", {}),
    (85, 89, "serve.slot_metrics", {}),
]
DEVICE = [(31, 37), (59, 63), (80, 82)]  # us


def text_proto(host, device) -> str:
    """An XSpace with one TPU plane and the host plane, as text."""
    names = sorted({h[2] for h in host})
    stats = sorted({k for h in host for k in h[3]})
    ev_md = "".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                    f'name: "{n}" }} }}\n' for i, n in enumerate(names))
    st_md = "".join(f'stat_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                    f'name: "{n}" }} }}\n' for i, n in enumerate(stats))

    def event(md, s, e, st=None):
        body = "".join(f"stats {{ metadata_id: {stats.index(k) + 1} "
                       f"int64_value: {v} }} " for k, v in (st or {}).items())
        return (f"events {{ metadata_id: {md} offset_ps: {int(s * US)} "
                f"duration_ps: {int((e - s) * US)} {body}}}\n")

    host_events = "".join(event(names.index(n) + 1, s, e, st)
                          for s, e, n, st in host)
    dev_events = "".join(event(1, s, e) for s, e in device)
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{dev_events}  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0
{host_events}  }}
{ev_md}{st_md}}}
"""


def profile(host=HOST, device=DEVICE):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(text_proto(host, device))


@pytest.fixture(scope="module")
def sp():
    return spans.reduce(profile())


def test_spans_in_the_window_with_their_counts(sp):
    assert [s.name for s in sp.spans][:3] == [
        "serve.tick", "serve.plan", "serve.make_slot"]
    assert len(sp.spans) == 18
    assert sp.count("serve.batch", "slots") == 8
    stage = sp.named("serve.stage")[0]
    assert stage.stats == {"lanes": 2, "bucket": 4}
    assert stage.dur == pytest.approx(4000.0)  # ns
    assert sp.harness["tick"] == [1, pytest.approx(50e-6)]


def test_eager_dispatches_count_once_inside_slot_builds(sp):
    # add (with its nested twin), multiply, sin; not the add in a wait
    assert sp.eager_ops == 3


def test_idle_gaps_take_the_innermost_span(sp):
    # gaps [0,31) [37,59) [63,80) [82,100) us; their midpoints 15.5 in
    # the second make_slot, 48 in serve.tick only, 71.5 and 91 in run
    assert sp.idle_by_span == {
        "serve.make_slot": pytest.approx(31e-6),
        "serve.tick": pytest.approx(22e-6),
        "run": pytest.approx(35e-6),
    }


def test_numbers_arithmetic(sp):
    got = spans.numbers(sp)
    assert got == {
        # one tick of 48 us less its 8 us wait
        "tti_host_ms": pytest.approx(0.040),
        "slot_build_ms_per_tti": pytest.approx(0.015),
        "stage_ms_per_tti": pytest.approx(0.004),
        "feedback_ms_per_tti": pytest.approx(0.006),
        "eager_ops_per_slot": pytest.approx(1.5),
        # (18 - 6) + (18 - 4) us over 3 + 5 slots
        "batch_host_us_per_slot": pytest.approx(26 / 8),
        "stack_us_per_slot": pytest.approx(7 / 8),
        "slot_metrics_us_per_slot": pytest.approx(7 / 8),
    }


def test_coverage_arithmetic(sp):
    got = spans.coverage(sp)
    # tick: 48 us, children cover 18 + 4 + 14 + 6; batches: 4 + 4 of 36
    assert got["tick_self_share"] == pytest.approx(100 * 6 / 48)
    assert got["batch_self_share"] == pytest.approx(100 * 8 / 36)
    assert got["serve_idle_share"] == pytest.approx(100 * 53 / 88)


def test_a_trace_without_program_spans_reads_nothing():
    host = [h for h in HOST if not h[2].startswith("serve.")]
    sp = spans.reduce(profile(host))
    assert sp.spans == [] and sp.eager_ops == 0
    assert all(v is None for v in spans.numbers(sp).values())
    assert all(v is None for v in spans.coverage(sp).values())
    assert sp.idle_by_span == pytest.approx(
        xtrace.reduce(profile(host)).idle_by_span)


@pytest.mark.parametrize("spans_in", [
    [(0, 10, "a"), (2, 8, "b"), (3, 4, "c")],
    [(0, 10, "a"), (1, 3, "b"), (4, 9, "c"), (5, 6, "d")],
    [(5, 6, "c"), (0, 10, "a"), (2, 8, "b")],
])
def test_sweep_matches_the_shortest_containing_span(spans_in):
    gaps = [(0, 1), (2.5, 3.2), (3.1, 3.9), (4.5, 5.5), (8.5, 9), (9, 12)]
    want = {}
    for a, b in gaps:
        mid = (a + b) / 2
        inside = [s for s in spans_in if s[0] <= mid < s[1]]
        label = min(inside, key=lambda s: s[1] - s[0])[2] if inside \
            else "window"
        want[label] = want.get(label, 0.0) + (b - a) * 1e-9
    assert spans.label_idle(gaps, spans_in) == pytest.approx(want)


def test_recorded_chip_trace_matches_xtrace():
    """The recorded `siso-backlog` window predates the program's spans:
    the reduction finds none, and labels idle time as xtrace does."""
    from jax.profiler import ProfileData

    path = os.path.join(BENCH, "tests", "data", "siso-backlog.xplane.pb.gz")
    with gzip.open(path) as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    sp = spans.reduce(pd)
    s = xtrace.reduce(pd)
    assert sp.spans == [] and sp.eager_ops == 0
    assert sp.window_s == pytest.approx(s.window_s)
    assert sp.idle_by_span.keys() == s.idle_by_span.keys()
    for k, v in s.idle_by_span.items():
        assert sp.idle_by_span[k] == pytest.approx(v, rel=1e-12)
    assert all(v is None for v in spans.numbers(sp).values())
