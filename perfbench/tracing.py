"""Reduction of a JAX profiler trace to device busy time, kernel time and
idle gaps.

The trace (``.xplane.pb``) holds one plane per TPU, ``/device:TPU:<n>``,
whose line ``XLA Ops`` has one event per HLO operation run on the
device, named by its HLO text, and the ``XLA Modules`` line, one event
per executable run. The host plane ``/host:CPU`` holds the benchmark's
own ``jax.profiler.TraceAnnotation`` spans (``bench.*``). Both share one
clock: nanoseconds from the start of the trace.

A Pallas kernel is an operation whose HLO is a ``custom-call`` to the
target ``tpu_custom_call`` (how Mosaic kernels reach XLA); every other
operation is XLA's own work.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
#: label of idle time in which no benchmark span was open
NO_SPAN = "(no bench span)"

_INSTR = re.compile(r"^%([A-Za-z_][\w\-]*?)(?:\.\d+)? = (.*)$", re.S)
_LAYOUT = re.compile(r"\{[^{}]*\}")
_KERNEL = re.compile(r'custom-call\(.*custom_call_target="tpu_custom_call"')
_MODULE = re.compile(r"^(.*?)(?:\(\d+\))?$")


def is_kernel(hlo: str) -> bool:
    """Is this device operation a Pallas (Mosaic) kernel launch?"""
    return _KERNEL.search(hlo) is not None


def op_label(hlo: str) -> str:
    """A short stable name of a device operation: the HLO instruction
    name without its numeric suffix and the result type without its
    layout, e.g. ``fused_conv_gemm f32[3136,256]``."""
    m = _INSTR.match(hlo)
    if m is None:
        return hlo[:80]
    rest = m.group(2)
    if rest.startswith("("):  # a tuple: up to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape = rest[:i + 1]
    else:
        shape = rest.split(" ", 1)[0]
    while True:
        stripped = _LAYOUT.sub("", shape)
        if stripped == shape:
            return f"{m.group(1)} {shape}"
        shape = stripped


def module_label(name: str) -> str:
    """``jit_f(1881638226656373441)`` -> ``jit_f``."""
    return _MODULE.match(name).group(1)


@dataclasses.dataclass(frozen=True)
class Interval:
    start: float
    end: float
    label: str


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, start: float, end: float) -> list:
    """The stretches of ``[start, end]`` that no interval covers."""
    out, t = [], start
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, end)))
        t = max(t, e)
        if t >= end:
            break
    if t < end:
        out.append((t, end))
    return [(s, e) for s, e in out if e > s]


def attribute(gap_list, spans) -> dict:
    """label -> [idle ns, gap count]: each gap goes to the innermost
    (shortest) host span open at its midpoint. Spans of one label must
    not overlap each other, as the benchmark's own do not."""
    groups = collections.defaultdict(list)
    for sp in spans:
        groups[sp.label].append(sp)
    index = []
    for group in groups.values():
        group.sort(key=lambda sp: sp.start)
        index.append(([sp.start for sp in group], group))
    out = collections.defaultdict(lambda: [0.0, 0])
    for s, e in gap_list:
        mid = (s + e) / 2
        best = None
        for starts, group in index:
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and group[i].end >= mid and (
                    best is None or
                    group[i].end - group[i].start < best.end - best.start):
                best = group[i]
        label = best.label if best is not None else NO_SPAN
        out[label][0] += e - s
        out[label][1] += 1
    return dict(out)


@dataclasses.dataclass
class TraceSummary:
    """Device time inside the benchmark's window, averaged over chips."""
    window_s: float
    busy_s: float
    kernel_s: float
    other_s: float
    kernel_ops: int
    op_seconds: dict      # "module/op label" -> device seconds
    gap_seconds: dict     # host span label -> [idle seconds, gaps]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int) -> list:
        top = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in top]

    def top_gaps(self, n: int) -> list:
        top = sorted(self.gap_seconds.items(), key=lambda kv: -kv[1][0])[:n]
        return [[f"{k} ({v[1]} gaps)", v[0]] for k, v in top]


def _spans(pd) -> list:
    out = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    out.append(Interval(ev.start_ns, ev.end_ns, ev.name))
    return out


def _device_ops(plane, lo: float, hi: float):
    """(ops, modules) of one device plane, clipped to ``[lo, hi]``."""
    ops, modules = [], []
    for line in plane.lines:
        if line.name not in (OPS_LINE, MODULES_LINE):
            continue
        for ev in line.events:
            s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
            if e <= s:
                continue
            (ops if line.name == OPS_LINE else modules).append((s, e, ev.name))
    return ops, modules


def summarize(pd) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`TraceSummary`."""
    spans = _spans(pd)
    win = [sp for sp in spans if sp.label == WINDOW_SPAN]
    if len(win) != 1:
        raise ValueError(f"trace holds {len(win)} {WINDOW_SPAN} spans, not 1")
    lo, hi = win[0].start, win[0].end
    planes = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    if not planes:
        raise ValueError("trace holds no TPU plane")
    kind_cache: dict = {}
    busy = kern = other = 0.0
    n_kernel = 0
    op_ns = collections.Counter()
    gap_ns: dict = collections.defaultdict(lambda: [0.0, 0])
    for plane in planes:
        ops, modules = _device_ops(plane, lo, hi)
        modules.sort()
        mod_starts = [m[0] for m in modules]
        k_iv, o_iv = [], []
        for s, e, name in ops:
            info = kind_cache.get(name)
            if info is None:
                info = kind_cache[name] = (is_kernel(name), op_label(name))
            kernel, label = info
            (k_iv if kernel else o_iv).append((s, e))
            n_kernel += kernel
            i = bisect.bisect_right(mod_starts, s) - 1
            mod = module_label(modules[i][2]) if i >= 0 and modules[i][1] >= s \
                else "?"
            op_ns[f"{mod}/{label}"] += e - s
        busy += union_ns(k_iv + o_iv)
        kern += union_ns(k_iv)
        other += union_ns(o_iv)
        for label, (ns, cnt) in attribute(gaps(k_iv + o_iv, lo, hi),
                                          spans).items():
            gap_ns[label][0] += ns
            gap_ns[label][1] += cnt
    n = len(planes)
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy / n * 1e-9,
        kernel_s=kern / n * 1e-9, other_s=other / n * 1e-9,
        kernel_ops=n_kernel,
        op_seconds={k: v / n * 1e-9 for k, v in op_ns.items()},
        gap_seconds={k: [v[0] / n * 1e-9, v[1]] for k, v in gap_ns.items()})


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_file(path: str) -> TraceSummary:
    import jax
    return summarize(jax.profiler.ProfileData.from_file(path))


def reduce_dir(trace_dir: str) -> TraceSummary:
    return reduce_file(find_xplane(trace_dir))
