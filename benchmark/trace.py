"""Trace reduction: from one profiler capture (``.xplane.pb``) to the
numbers the per-layer metrics read.

Read with ``jax.profiler.ProfileData`` and nothing else.  A device plane
is one whose name starts with ``/device:`` (``/device:TPU:0``); its
``XLA Ops`` line holds the operations that ran, its ``XLA Modules``
line the executables they belong to.  Host spans are the events of the
host plane, among them the program's ``lp.<stage>`` annotations.
"""
import glob
import os

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return files[-1] if files else None


def _union(intervals):
    """Total length and the merged list of [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _events(line):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events if ev.duration_ns > 0]


def reduce_planes(planes, window_ns, device_prefix=DEVICE_PREFIX,
                  ops_line=OPS_LINE, modules_line=MODULES_LINE,
                  host_plane="/host:CPU", span_prefix="lp."):
    """The reduction, over planes as ``ProfileData`` gives them.

    ``window_ns``: (start, end) of the traced window on the trace's
    clock.  Returns None when no device plane holds an operation, else
    a dict: ``busy_s`` (mean over devices of the union of operation
    intervals inside the window), ``window_s``, ``devices``,
    ``module_s`` ({executable: device seconds, summed over devices}),
    ``op_s`` ({operation: seconds}), and ``idle_gaps``: the longest
    gaps between operations, each named by the host span that overlaps
    it most (``idle`` where none does).
    """
    w0, w1 = window_ns
    busy, merged_all, module_s, op_s = [], [], {}, {}
    for plane in planes:
        if not plane.name.startswith(device_prefix):
            continue
        ops = []
        for line in plane.lines:
            if line.name.startswith(ops_line):
                for name, s, e in _events(line):
                    s, e = max(s, w0), min(e, w1)
                    if e > s:
                        ops.append((s, e))
                        op_s[name] = op_s.get(name, 0.0) + (e - s) / 1e9
            elif line.name.startswith(modules_line):
                for name, s, e in _events(line):
                    s, e = max(s, w0), min(e, w1)
                    if e > s:
                        module_s[name] = module_s.get(name, 0.0) + (e - s) / 1e9
        if ops:
            total, merged = _union(ops)
            busy.append(total)
            merged_all.append(merged)
    if not busy:
        return None
    spans = []
    for plane in planes:
        if plane.name == host_plane:
            for line in plane.lines:
                spans += [ev for ev in _events(line)
                          if ev[0].startswith(span_prefix)]
    gaps = []
    for merged in merged_all[:1]:  # gaps of the first device
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
    gaps.sort(reverse=True)
    named = []
    for length, s, e in gaps[:10]:
        best, best_overlap = "idle", 0
        for name, hs, he in spans:
            ov = min(e, he) - max(s, hs)
            if ov > best_overlap:
                best, best_overlap = name, ov
        named.append([best, length / 1e9])
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "devices": len(busy),
        "module_s": module_s,
        "op_s": op_s,
        "device_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": named,
    }


def trace_window_ns(planes, host_plane="/host:CPU", marker="bench.window"):
    """(start, end) of the ``bench.window`` annotation the harness puts
    around the traced window; None when the capture lacks it."""
    for plane in planes:
        if plane.name != host_plane:
            continue
        for line in plane.lines:
            for name, s, e in _events(line):
                if name == marker:
                    return (s, e)
    return None


def reduce_trace(trace_dir, **kw):
    from jax.profiler import ProfileData

    path = find_xplane(trace_dir)
    if path is None:
        return None
    planes = list(ProfileData.from_file(path).planes)
    window = trace_window_ns(planes)
    if window is None:
        return None
    return reduce_planes(planes, window, **kw)
