"""The device trace of a run: capture with JAX's profiler, then reduce the
``.xplane.pb`` to plain events and the events to numbers.

A reduced trace is a dict that JSON can hold, so a test can check the
reduction on a small recorded one::

    {"devices": {"<plane>": [[op name, opcode, start_ns, duration_ns], ...]},
     "host": [[annotation, start_ns, duration_ns], ...]}

``devices`` holds the ops that XLA ran on each chip (the ``XLA Ops`` line
of each ``/device:TPU:<i>`` plane); ``host`` holds the harness's own
``TraceAnnotation`` spans (``input``, ``round``, ``block``), on the same
clock.
"""

from __future__ import annotations

import contextlib
import glob
import shutil
import tempfile

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PHASES = ("input", "round", "block")
# opcode classes, by the HLO opcode that the trace gives each op
SELECT_OPCODES = ("sort", "topk")
COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                      "all-to-all", "collective-permute")
# ops that hold others (a loop body's ops are events of their own, inside
# the loop's event): counted in busy time, not in op time, so no time is
# counted twice
CONTAINER_OPCODES = ("while", "conditional", "call")


def opcode(text: str) -> str:
    """The HLO opcode of an op from its HLO text, as the TPU trace names
    each op (``%sort.3 = (f32[8]{0}, s32[8]{0}) sort(...)``); a bare name
    (``sort.3``) gives its prefix."""
    if "=" not in text:
        return text.lstrip("%").split(".", 1)[0].lower()
    rhs = text.split("=", 1)[1].strip()
    depth, i = 0, 0   # skip the result shape, which may hold spaces
    while i < len(rhs):
        c = rhs[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == " " and depth == 0:
            break
        i += 1
    return rhs[i:].strip().split("(", 1)[0].strip().lower()


def op_name(text: str) -> str:
    """``%sort.3 = ...`` -> ``sort.3``."""
    return text.split("=", 1)[0].strip().lstrip("%")


def load(path: str) -> dict:
    """Reduce one ``.xplane.pb`` to the plain form above."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices[plane.name] = [
                [op_name(e.name), opcode(e.name), int(e.start_ns), int(e.duration_ns)]
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            host += [[e.name, int(e.start_ns), int(e.duration_ns)]
                     for line in plane.lines for e in line.events
                     if e.name in HOST_PHASES]
    return {"devices": devices, "host": host}


@contextlib.contextmanager
def captured(out: dict):
    """Trace the block into a temporary directory (under ``TMPDIR``), then
    put the reduced trace in ``out["trace"]`` and delete the files."""
    import jax
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one xplane file, found {paths}")
        out["trace"] = load(paths[0])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _overlap(a0, a1, b0, b1):
    return max(0, min(a1, b1) - max(a0, b0))


def reduce(tr: dict, rounds: int) -> dict | None:
    """Numbers of a traced window of ``rounds`` rounds.

    The window runs from the start of the first host ``input`` span to
    the end of the last ``block`` span. Per device: busy time is the
    union of its op intervals inside the window; op time by opcode class
    is summed over its ops (clipped to the window). The fullest device is
    the one with the most busy time. Idle gaps are the stretches of the
    window in which the fullest device ran no op, each named by the host
    span that overlaps it most (``host`` where none does).
    Returns None where the trace holds no window or no device op.
    """
    starts = [s for n, s, _ in tr["host"] if n == "input"]
    ends = [s + d for n, s, d in tr["host"] if n == "block"]
    if not starts or not ends or not tr["devices"]:
        return None
    lo, hi = min(starts), max(ends)
    if hi <= lo:
        return None
    per_dev = {}
    for plane, ops in tr["devices"].items():
        ivs = [(max(s, lo), min(s + d, hi)) for _, _, s, d in ops
               if s < hi and s + d > lo]
        merged = _union(ivs)
        busy = sum(e - s for s, e in merged)
        by_class = {"select": 0, "collective": 0}
        by_name = {}
        for name, op, s, d in ops:
            t = _overlap(s, s + d, lo, hi)
            if not t or op in CONTAINER_OPCODES:
                continue
            if op in SELECT_OPCODES:
                by_class["select"] += t
            elif op in COLLECTIVE_OPCODES:
                by_class["collective"] += t
            by_name[name] = by_name.get(name, 0) + t
        per_dev[plane] = (busy, merged, by_class, by_name)
    if not any(v[0] for v in per_dev.values()):
        return None
    plane = max(per_dev, key=lambda p: per_dev[p][0])
    busy, merged, by_class, by_name = per_dev[plane]
    gaps, t = [], lo
    for s, e in merged + [[hi, hi]]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    named = []
    for g0, g1 in gaps:
        best, what = 0, "host"
        for n, s, d in tr["host"]:
            o = _overlap(g0, g1, s, s + d)
            if o > best:
                best, what = o, n
        named.append([what, (g1 - g0) * 1e-9])
    named.sort(key=lambda x: -x[1])
    top_ops = sorted(by_name.items(), key=lambda x: -x[1])[:10]
    n_dev = len(per_dev)
    return {
        "device": plane,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(v[0] for v in per_dev.values()) / n_dev * 1e-9,
        "fullest_busy_s": busy * 1e-9,
        "rounds": rounds,
        "select_s": by_class["select"] * 1e-9,
        "collective_s": by_class["collective"] * 1e-9,
        "device_ops": [[n, t * 1e-9] for n, t in top_ops],
        "idle_gaps": named[:10],
    }
