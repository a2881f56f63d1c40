"""The program's ``serve.*`` spans in a profiler trace, and the per-layer
numbers that read them.

The serving loop (``repro.serve.trace``) marks its phases with
``jax.profiler.TraceAnnotation`` spans named ``serve.*`` that carry
counts as stats.  They sit on the ``/host:CPU`` plane beside the
harness's spans (``window``, ``tick``, ``run``, ``submit``), on the same
clock as the device's operations.  Inside the harness's ``window`` span
:func:`reduce` keeps:

* ``spans``: every ``serve.*`` span that starts in the window, as
  (start, end, name, stats), sorted by start;
* ``eager_ops``: the ``PjitFunction(`` host events inside
  ``serve.make_slot`` spans, one per eager dispatch (JAX records a
  second such event inside the first, so only the outermost counts);
* ``idle_by_span``: the idle gaps of the first device, as in
  :mod:`xtrace`, labelled by the innermost harness or ``serve.*`` span
  around each gap's midpoint (a sort-and-stack sweep: the spans of one
  thread nest);
* ``harness``: count and summed seconds of each harness span.

On a trace without ``serve.*`` spans ``spans`` is empty, ``eager_ops``
0, ``idle_by_span`` equals :mod:`xtrace`'s, and every number of
:func:`numbers` is None.
"""
from __future__ import annotations

import bisect
import dataclasses

import xtrace

PREFIX = "serve."
EAGER = "PjitFunction("


@dataclasses.dataclass
class Span:
    start: float  # ns
    end: float
    name: str
    stats: dict

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Spans:
    window_s: float
    spans: list  # [Span], serve.* spans starting in the window
    eager_ops: int  # outermost PjitFunction( events inside make_slot
    idle_by_span: dict  # innermost span name -> idle seconds, device 0
    harness: dict  # harness span name -> [count, seconds] in the window

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def total_s(self, name: str) -> float:
        return sum(s.dur for s in self.named(name)) * 1e-9

    def count(self, name: str, stat: str) -> int:
        return sum(int(s.stats.get(stat, 0)) for s in self.named(name))

    def self_s(self, outer: str, inner: str) -> float:
        """Summed seconds of the ``outer`` spans less the ``inner`` spans
        that start inside them."""
        inner_spans = self.named(inner)
        starts = [s.start for s in inner_spans]
        tot = 0.0
        for o in self.named(outer):
            lo = bisect.bisect_left(starts, o.start)
            hi = bisect.bisect_left(starts, o.end)
            tot += o.dur - sum(s.dur for s in inner_spans[lo:hi])
        return tot * 1e-9

    def uncovered_s(self, name: str) -> float:
        """Summed seconds of the ``name`` spans that no span inside them
        covers (their self time)."""
        starts = [s.start for s in self.spans]
        tot = 0.0
        for o in self.named(name):
            lo = bisect.bisect_left(starts, o.start)
            hi = bisect.bisect_left(starts, o.end)
            kids = xtrace.union([(s.start, s.end) for s in self.spans[lo:hi]
                                 if s is not o and s.end <= o.end])
            tot += o.dur - sum(e - s for s, e in kids)
        return tot * 1e-9


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats if isinstance(v, (int, float))}


def _outermost(events: list) -> list:
    """The (start, end) events of one thread that no other contains."""
    out = []
    for s, e in sorted(events):
        if out and s < out[-1][1]:
            continue
        out.append((s, e))
    return out


def label_idle(gaps: list, spans: list) -> dict:
    """Idle seconds by the innermost span around each gap's midpoint.

    ``gaps``: [(start, end)] ns; ``spans``: [(start, end, name)] of the
    host thread, nested.  A midpoint that no span covers is ``window``.
    """
    order = sorted(spans, key=lambda s: (s[0], -s[1]))
    idle = {}
    stack = []
    i = 0
    for a, b in sorted(gaps, key=lambda g: g[0] + g[1]):
        if b <= a:
            continue
        mid = (a + b) / 2
        while i < len(order) and order[i][0] <= mid:
            while stack and stack[-1][1] <= order[i][0]:
                stack.pop()
            stack.append(order[i])
            i += 1
        while stack and stack[-1][1] <= mid:
            stack.pop()
        label = stack[-1][2] if stack else "window"
        idle[label] = idle.get(label, 0.0) + (b - a) * 1e-9
    return idle


def _busy(profile, w0: float, w1: float) -> list:
    """Merged operation intervals of the first device that ran anything
    in [w0, w1), as :mod:`xtrace` takes them; [] without one."""
    for plane in profile.planes:
        if not plane.name.startswith(xtrace.DEVICE_PREFIX):
            continue
        ops = [(max(ev.start_ns, w0), min(ev.end_ns, w1))
               for line in plane.lines if line.name == xtrace.OPS_LINE
               for ev in line.events]
        ops = [(s, e) for s, e in ops if e > s]
        if ops:
            return xtrace.union(ops)
    return []


def reduce(profile) -> Spans:
    """``profile``: a ``jax.profiler.ProfileData``."""
    harness, serve, eager = [], [], []
    for plane in profile.planes:
        if plane.name != xtrace.HOST_PLANE:
            continue
        for line in plane.lines:
            calls = []
            for ev in line.events:
                name = ev.name
                if name.startswith(PREFIX):
                    serve.append(Span(ev.start_ns, ev.end_ns, name,
                                      _stats(ev)))
                elif name.startswith(EAGER):
                    calls.append((ev.start_ns, ev.end_ns))
                elif name in xtrace.SPANS:
                    harness.append((ev.start_ns, ev.end_ns, name))
            eager += _outermost(calls)
    windows = [s for s in harness if s[2] == "window"]
    if not windows:
        raise ValueError("trace holds no 'window' span")
    w0, w1 = windows[0][0], windows[0][1]
    spans = sorted((s for s in serve if w0 <= s.start < w1),
                   key=lambda s: (s.start, -s.end))
    builds = [s for s in spans if s.name == "serve.make_slot"]
    starts = [s.start for s in builds]
    n_eager = 0
    for s, e in eager:
        k = bisect.bisect_right(starts, s) - 1
        if k >= 0 and s < builds[k].end and e <= builds[k].end:
            n_eager += 1
    counts = {}
    for s, e, name in harness:
        if w0 <= s < w1:
            c = counts.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += (e - s) * 1e-9
    merged = _busy(profile, w0, w1)
    idle = {}
    if merged:
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        labelled = [s for s in harness if s[2] != "window"]
        labelled += [(s.start, s.end, s.name) for s in serve]
        idle = label_idle(list(zip(edges[0::2], edges[1::2])), labelled)
    return Spans((w1 - w0) * 1e-9, spans, n_eager, idle, counts)


def load(log_dir: str) -> Spans:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(xtrace.find(log_dir)))


def _ratio(num: float, den: float, scale: float = 1.0):
    return num / den * scale if den else None


def numbers(sp: Spans) -> dict:
    """The per-layer numbers the ``serve.*`` spans give, name -> value
    (None where the trace holds no span to read):

    * ``tti_host_ms``: mean over ``serve.tick`` of its duration less its
      ``serve.wait`` spans (prefetch staging counts as host time);
    * ``slot_build_ms_per_tti``, ``stage_ms_per_tti``,
      ``feedback_ms_per_tti``: summed ``serve.make_slot``,
      ``serve.stage``, ``serve.feedback`` over the ticks;
    * ``eager_ops_per_slot``: outermost eager dispatches inside
      ``serve.make_slot`` over the ``serve.make_slot`` spans;
    * ``batch_host_us_per_slot``: summed ``serve.batch`` less its
      ``serve.wait`` over the real slots (``slots`` stats) of the
      batches; ``stack_us_per_slot``, ``slot_metrics_us_per_slot``:
      summed ``serve.stack``, ``serve.slot_metrics`` over those slots.
    """
    ticks = len(sp.named("serve.tick"))
    builds = len(sp.named("serve.make_slot"))
    slots = sp.count("serve.batch", "slots")
    return {
        "tti_host_ms": _ratio(sp.self_s("serve.tick", "serve.wait"),
                              ticks, 1e3),
        "slot_build_ms_per_tti": _ratio(sp.total_s("serve.make_slot"),
                                        ticks, 1e3),
        "stage_ms_per_tti": _ratio(sp.total_s("serve.stage"), ticks, 1e3),
        "feedback_ms_per_tti": _ratio(sp.total_s("serve.feedback"),
                                      ticks, 1e3),
        "eager_ops_per_slot": _ratio(sp.eager_ops, builds),
        "batch_host_us_per_slot": _ratio(
            sp.self_s("serve.batch", "serve.wait"), slots, 1e6),
        "stack_us_per_slot": _ratio(sp.total_s("serve.stack"), slots, 1e6),
        "slot_metrics_us_per_slot": _ratio(
            sp.total_s("serve.slot_metrics"), slots, 1e6),
    }


def coverage(sp: Spans) -> dict:
    """How much the ``serve.*`` spans explain, in %: the self time of
    ``serve.tick`` and of ``serve.batch`` over their length, and the
    share of the device's idle time that a ``serve.*`` span labels."""
    idle = sum(sp.idle_by_span.values())
    served = sum(v for k, v in sp.idle_by_span.items()
                 if k.startswith(PREFIX))
    return {
        "tick_self_share": _ratio(sp.uncovered_s("serve.tick"),
                                  sp.total_s("serve.tick"), 100.0),
        "batch_self_share": _ratio(sp.uncovered_s("serve.batch"),
                                   sp.total_s("serve.batch"), 100.0),
        "serve_idle_share": _ratio(served, idle, 100.0) if sp.spans
        else None,
    }
