"""One traced run of one cell, with the program's ``serve.*`` spans read.

    python3 bench/trace_spans.py --workload <cell> --seed <n> --seconds <s>

Runs ``bench/run.py`` with ``--trace 1`` in this process, and reduces
the same trace a second time with :mod:`spans`.  Standard output ends
with the run's result line, then one JSON object: the numbers of
:func:`spans.numbers` and :func:`spans.coverage`, the device's idle
seconds by innermost span, the harness spans' counts and seconds, the
``serve.*`` spans' counts and seconds, and the seconds the reduction
took.  On a program without ``serve.*`` spans the numbers are None.
"""
import time

T_PROCESS = time.perf_counter()

import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402
import spans  # noqa: E402
import xtrace  # noqa: E402

run.T_PROCESS = T_PROCESS
LOAD = xtrace.load
REDUCED = {}


def _load(log_dir: str):
    """The harness's reduction, then the spans' of the same trace."""
    t0 = time.perf_counter()
    summary = LOAD(log_dir)
    t1 = time.perf_counter()
    REDUCED["spans"] = spans.load(log_dir)
    REDUCED["xtrace_s"] = t1 - t0
    REDUCED["spans_s"] = time.perf_counter() - t1
    return summary


xtrace.load = _load


def main() -> int:
    sys.argv += ["--trace", "1"]
    rc = run.main()
    sp = REDUCED["spans"]
    by_name = {}
    for s in sp.spans:
        c = by_name.setdefault(s.name, [0, 0.0])
        c[0] += 1
        c[1] += s.dur * 1e-9
    print(json.dumps({
        "serve_numbers": spans.numbers(sp),
        "coverage": spans.coverage(sp),
        "idle_by_span": sorted(([k, v] for k, v in sp.idle_by_span.items()),
                               key=lambda kv: -kv[1]),
        "harness_spans": sp.harness,
        "serve_spans": by_name,
        "eager_ops": sp.eager_ops,
        "window_s": sp.window_s,
        "xtrace_reduce_s": REDUCED["xtrace_s"],
        "spans_reduce_s": REDUCED["spans_s"],
    }), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
