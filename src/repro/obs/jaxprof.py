"""Profiling hooks for jit entries: compile-vs-execute wall-clock split,
compile-cache hit counting and a donation audit (DESIGN.md §15). A
device trace is captured with ``jax.profiler.trace``.

``JaxProfiler.wrap`` turns a jitted callable into a counted one:

* every call is wall-clock timed on the host;
* calls that grew the function's compile cache (``fn._cache_size()``,
  feature-detected; falls back to an abstract-signature set when the
  attribute is absent) are classified as *compile* calls, the rest as
  *execute* (cache hits) — the split that tells you whether a sweep is
  spending its time in XLA or in the round math;
* donation warnings raised during the call ("donated buffer was not
  usable" et al.) are counted per entry — a silent donation regression
  (e.g. a new consumer of a donated buffer forcing a copy) shows up as a
  non-zero ``donation_warnings`` without anyone watching stderr.

The wrapper calls the wrapped function unchanged — same arguments, same
outputs, no blocking added — so wrapping is value-transparent; only the
host-side bookkeeping differs.  ``NullProbe.wrap_jit`` skips even that.
"""

from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, field

__all__ = ["JitEntry", "JaxProfiler"]

_DONATION_MARKERS = ("donat",)   # matches jax's donation warning family


@dataclass
class JitEntry:
    """Per-wrapped-function counters."""

    name: str
    calls: int = 0
    compiles: int = 0
    compile_wall_s: float = 0.0    # wall time of calls that compiled
    execute_wall_s: float = 0.0    # wall time of cache-hit calls
    donation_warnings: int = 0
    _sig_cache: set = field(default_factory=set, repr=False)

    @property
    def cache_hits(self) -> int:
        return self.calls - self.compiles

    def to_dict(self) -> dict:
        return {"name": self.name, "calls": self.calls,
                "compiles": self.compiles, "cache_hits": self.cache_hits,
                "compile_wall_s": round(self.compile_wall_s, 6),
                "execute_wall_s": round(self.execute_wall_s, 6),
                "donation_warnings": self.donation_warnings}


def _abstract_sig(args, kwargs):
    """Fallback compile detector: the (shape, dtype) signature of the
    call, for jit wrappers without ``_cache_size``."""
    def leaf(x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is None and dtype is None:
            return repr(x)
        return (tuple(shape), str(dtype))
    import jax
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (str(treedef),) + tuple(leaf(x) for x in leaves)


class JaxProfiler:
    """Collects :class:`JitEntry` stats for every wrapped jit entry."""

    def __init__(self):
        self.entries: dict = {}

    def entry(self, name: str) -> JitEntry:
        e = self.entries.get(name)
        if e is None:
            e = self.entries[name] = JitEntry(name)
        return e

    def wrap(self, fn, name: str):
        """Wrap a (usually jitted) callable with compile/execute counting.

        Safe to call on non-jitted callables too — they count as compiling
        once per new abstract signature via the fallback detector.
        """
        e = self.entry(name)
        cache_size = getattr(fn, "_cache_size", None)

        def wrapped(*args, **kwargs):
            if cache_size is not None:
                before = cache_size()
            else:
                sig = _abstract_sig(args, kwargs)
                before = None
            t0 = time.perf_counter()
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                out = fn(*args, **kwargs)
            dt = time.perf_counter() - t0
            e.calls += 1
            e.donation_warnings += sum(
                1 for w in caught
                if any(m in str(w.message).lower()
                       for m in _DONATION_MARKERS))
            for w in caught:           # re-emit: the audit only observes
                if not any(m in str(w.message).lower()
                           for m in _DONATION_MARKERS):
                    warnings.warn_explicit(w.message, w.category,
                                           w.filename, w.lineno)
            if cache_size is not None:
                compiled = cache_size() > before
            else:
                compiled = sig not in e._sig_cache
                e._sig_cache.add(sig)
            if compiled:
                e.compiles += 1
                e.compile_wall_s += dt
            else:
                e.execute_wall_s += dt
            return out

        wrapped.__name__ = f"profiled[{name}]"
        wrapped.__wrapped__ = fn
        return wrapped

    def snapshot(self) -> dict:
        """JSON-serializable {entry name: counters}."""
        return {n: e.to_dict() for n, e in sorted(self.entries.items())}

    def report_rows(self) -> list:
        """(name, calls, compiles, compile_s, execute_s, donation_warnings)
        rows for the terminal report."""
        return [(e.name, e.calls, e.compiles, e.compile_wall_s,
                 e.execute_wall_s, e.donation_warnings)
                for e in sorted(self.entries.values(), key=lambda x: x.name)]
