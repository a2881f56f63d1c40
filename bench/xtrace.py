"""Reduction of a profiler trace (``.xplane.pb``) to the device metrics.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed operation.  The harness's own host spans (``window``,
``tick``, ``run``, ``submit``) sit on the ``/host:CPU`` plane, on the same
clock.  Inside the ``window`` span:

* busy time of a device is the union of its operation intervals, and
  ``busy_s`` the mean over the devices that ran anything;
* a kernel's time is the summed duration of the operations whose name or
  string stats name it;
* idle gaps are the stretches no operation covers on the first device,
  labelled by the innermost harness span around each gap's midpoint.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPANS = ("window", "tick", "run", "submit")


@dataclasses.dataclass
class Op:
    start: float  # ns
    end: float
    name: str
    text: str  # name and every string stat, for kernel matching


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    n_devices: int
    op_s: dict  # op kind -> summed device seconds (all devices)
    idle_by_span: dict  # host span -> idle seconds on the first device
    ops: list  # [Op] inside the window, all devices

    def kernel_s(self, kernel: str) -> float:
        """Summed device seconds of the operations that name ``kernel``,
        averaged over the devices."""
        tot = sum(o.end - o.start for o in self.ops if kernel in o.text)
        return tot * 1e-9 / max(self.n_devices, 1)

    def top_ops(self, n: int = 10) -> list:
        return sorted(([k, v] for k, v in self.op_s.items()),
                      key=lambda kv: -kv[1])[:n]

    def top_idle(self, n: int = 10) -> list:
        return sorted(([k, v] for k, v in self.idle_by_span.items()),
                      key=lambda kv: -kv[1])[:n]


def union(intervals: list) -> list:
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def find(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def short_name(name: str) -> str:
    """A device op's HLO text -> its kind: the custom-call target, or the
    instruction name without its number (``fusion``, ``ldpc_decode``)."""
    target = re.search(r'custom_call_target="([^"]+)"', name)
    if target and target.group(1) != "tpu_custom_call":
        return target.group(1)
    head = re.match(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?: =|$)", name)
    return head.group(1) if head else name[:64]


def _text(ev) -> str:
    parts = [ev.name]
    for _, v in ev.stats:
        if isinstance(v, str):
            parts.append(v)
    return "|".join(parts)


def reduce(profile) -> Summary:
    """``profile``: a ``jax.profiler.ProfileData``."""
    spans = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in SPANS:
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    windows = [s for s in spans if s[2] == "window"]
    if not windows:
        raise ValueError("trace holds no 'window' span")
    w0, w1 = windows[0][0], windows[0][1]
    per_device = []
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e > s:
                    ops.append(Op(s, e, ev.name, _text(ev)))
        if ops:
            per_device.append(ops)
    window_s = (w1 - w0) * 1e-9
    if not per_device:
        return Summary(window_s, 0.0, 0, {}, {}, [])
    busy = [sum(e - s for s, e in union([(o.start, o.end) for o in ops]))
            for ops in per_device]
    op_s = {}
    for ops in per_device:
        for o in ops:
            k = short_name(o.name)
            op_s[k] = op_s.get(k, 0.0) + (o.end - o.start) * 1e-9
    merged = union([(o.start, o.end) for o in per_device[0]])
    inner = sorted((s for s in spans if s[2] != "window"),
                   key=lambda s: s[0])
    starts = [s[0] for s in inner]
    idle = {}
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        label = "window"
        best = None
        for s in inner[: bisect.bisect_right(starts, mid)]:
            if s[0] <= mid < s[1] and (best is None
                                       or s[1] - s[0] < best[1] - best[0]):
                best = s
        if best is not None:
            label = best[2]
        idle[label] = idle.get(label, 0.0) + (b - a) * 1e-9
    return Summary(
        window_s=window_s,
        busy_s=sum(busy) / len(busy) * 1e-9,
        n_devices=len(per_device),
        op_s=op_s, idle_by_span=idle,
        ops=[o for ops in per_device for o in ops],
    )


def load(log_dir: str) -> Summary:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(find(log_dir)))
