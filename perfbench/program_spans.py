#!/usr/bin/env python3
"""The program's own host spans in a profiler trace: how the host's time
inside ``ex.run`` splits by step, and which step the host was in while
the device sat idle.

``tracing.py`` reads the benchmark's own ``bench.*`` spans. The program
opens ``n3h.*`` spans at the steps of its executor chain
(``repro.obs.spans``: ``n3h.run``, ``n3h.layer``, ``n3h.layer.run``,
``n3h.layer.launch``, ``n3h.layer.glue``, ``n3h.layer.tail``) on the
same ``/host:CPU`` plane and clock. This module reads both:

    python3 perfbench/program_spans.py <file.xplane.pb[.gz]>

prints one JSON object: per image of the traced window, the host
milliseconds of each step and of ``bench.dispatch``, every ``n3h.*``
label's total and self time, and the device's idle time by the
innermost span open at each gap's midpoint (over ``bench.*`` and
``n3h.*``). On a trace without ``n3h.*`` spans the idle split is
``tracing.summarize``'s. Needs only JAX, no chip.
"""
from __future__ import annotations

import collections
import gzip
import json
import sys

import tracing

PREFIX = "n3h."
DISPATCH = "bench.dispatch"
REQUEST = "bench.request"
#: host step -> (labels, total or self time): the four add up to the
#: time inside ``n3h.run``
STEPS = {
    "launch": (("n3h.layer.launch",), 0),
    "bookkeeping": (("n3h.run", "n3h.layer", "n3h.layer.run"), 1),
    "glue": (("n3h.layer.glue",), 0),
    "tail": (("n3h.layer.tail",), 0),
}


def host_spans(pd) -> list:
    """The ``bench.*`` and ``n3h.*`` spans of the host plane."""
    return [tracing.Interval(ev.start_ns, ev.end_ns, ev.name)
            for plane in pd.planes if plane.name == tracing.HOST_PLANE
            for line in plane.lines for ev in line.events
            if ev.name.startswith((tracing.SPAN_PREFIX, PREFIX))]


def span_times(spans) -> dict:
    """label -> [total ns, self ns, count] of nested spans. A span's
    self time is its duration minus the union of the spans nested in it
    (the union of its direct children's)."""
    ordered = sorted(spans, key=lambda sp: (sp.start, -sp.end))
    children = collections.defaultdict(list)
    enclosing = []   # indices of the spans open around the current one
    for i, sp in enumerate(ordered):
        while enclosing and not (ordered[enclosing[-1]].start <= sp.start
                                 and sp.end <= ordered[enclosing[-1]].end):
            enclosing.pop()
        if enclosing:
            children[enclosing[-1]].append((sp.start, sp.end))
        enclosing.append(i)
    out = collections.defaultdict(lambda: [0.0, 0.0, 0])
    for i, sp in enumerate(ordered):
        total = sp.end - sp.start
        rec = out[sp.label]
        rec[0] += total
        rec[1] += total - tracing.union_ns(children[i])
        rec[2] += 1
    return dict(out)


def split(pd) -> dict:
    """Reduce a ``jax.profiler.ProfileData`` (see the module's text).
    Times are seconds, over the whole window; ``images`` is the count
    of ``bench.request`` spans in it."""
    spans = host_spans(pd)
    win = [sp for sp in spans if sp.label == tracing.WINDOW_SPAN]
    if len(win) != 1:
        raise ValueError(f"trace holds {len(win)} {tracing.WINDOW_SPAN} "
                         f"spans, not 1")
    lo, hi = win[0].start, win[0].end
    inside = [sp for sp in spans if lo <= sp.start and sp.end <= hi]
    times = span_times([sp for sp in inside if sp.label.startswith(PREFIX)])
    steps = {step: sum(times[k][col] for k in labels if k in times) * 1e-9
             for step, (labels, col) in STEPS.items()}
    planes = [p for p in pd.planes if tracing.DEVICE_PLANE.match(p.name)]
    idle = collections.defaultdict(lambda: [0.0, 0])
    for plane in planes:
        ops, _ = tracing._device_ops(plane, lo, hi)
        busy = [(s, e) for s, e, _ in ops]
        for label, (ns, cnt) in tracing.attribute(
                tracing.gaps(busy, lo, hi), spans).items():
            idle[label][0] += ns
            idle[label][1] += cnt
    n = max(len(planes), 1)
    return {
        "window_s": (hi - lo) * 1e-9,
        "images": sum(sp.label == REQUEST for sp in inside),
        "dispatch_s": sum(sp.end - sp.start for sp in inside
                          if sp.label == DISPATCH) * 1e-9,
        "steps_s": steps,
        "span_seconds": {k: [v[0] * 1e-9, v[1] * 1e-9, v[2]]
                         for k, v in times.items()},
        "idle_seconds": {k: [v[0] / n * 1e-9, v[1]] for k, v in idle.items()},
    }


def read_file(path: str):
    import jax
    with open(path, "rb") as f:
        data = f.read()
    if path.endswith(".gz"):
        data = gzip.decompress(data)
    return jax.profiler.ProfileData.from_serialized_xspace(data)


def per_image_ms(res: dict) -> dict:
    """The host split of :func:`split` in milliseconds per image."""
    k = 1e3 / max(res["images"], 1)
    out = {f"{step}_ms": v * k for step, v in res["steps_s"].items()}
    out["n3h_ms"] = sum(res["steps_s"].values()) * k
    out["dispatch_ms"] = res["dispatch_s"] * k
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    res = split(read_file(argv[0]))
    top = sorted(res["idle_seconds"].items(), key=lambda kv: -kv[1][0])
    print(json.dumps({"images": res["images"], "window_s": res["window_s"],
                      "per_image": per_image_ms(res),
                      "span_seconds": res["span_seconds"],
                      "idle_gaps": top[:10]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
