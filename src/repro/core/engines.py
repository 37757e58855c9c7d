"""The engine registry: one dispatch surface for the stacked FediAC round.

Engine selection used to be smeared across ``FediACConfig(engine=,
stream_chunk=, use_pallas=)``, ``FLConfig(engine=, use_pallas=)``,
``ScenarioSpec(engine=)`` and per-call kwargs; a fourth engine could not
land cleanly on that surface.  This module is the redesign (DESIGN.md
§16): a frozen :class:`EngineSpec` names an engine *and* carries its
tuning knobs (stream chunking, mesh geometry, Pallas fusion), a registry
maps names to runners, and :func:`repro.core.fediac.aggregate_round` —
plus the packet dataplane and the sweep/fleet layers — dispatch through
:func:`resolve`/:func:`run` only.

Everywhere a config used to take an engine *name* it now takes a name
**or** an ``EngineSpec``; names stay first-class (``engines.get("stream")``
returns that engine's default spec).  The legacy per-field knobs
(``FediACConfig.stream_chunk``, ``FediACConfig.use_pallas``,
``FLConfig.use_pallas``) keep working as thin shims: :func:`resolve`
folds them into the spec and emits a one-shot ``DeprecationWarning``
(pinned by ``tests/test_engines_api.py``).

``EngineSpec`` is a frozen dataclass of primitives — hashable and
``__eq__``-stable — so it can sit inside ``FediACConfig`` /
``ScenarioSpec`` wherever those are used as static jit arguments or
sweep-cache keys.
"""

from __future__ import annotations

import dataclasses
import warnings

__all__ = ["EngineSpec", "get", "names", "register", "resolve", "run",
           "with_pallas"]


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """One engine choice plus its knobs, as a single hashable value.

    Only the fields an engine reads matter to it: ``chunk`` tunes the
    stream engine, ``devices``/``axis`` size the sharded engine's 1-D
    coordinate mesh, ``use_pallas`` routes the monolithic/stream engines
    through the fused kernels (DESIGN.md §3).  Zero means "engine
    default" (``stream_engine.DEFAULT_CHUNK`` chunks, every visible
    device).
    """

    name: str = "monolithic"
    chunk: int = 0          # stream: coords per chunk (0 = engine default)
    devices: int = 0        # sharded: mesh size (0 = all visible devices)
    axis: str = "d"         # sharded: coordinate mesh axis name
    use_pallas: bool = False


def _run_monolithic(spec, u_stack, cfg, key, a):
    from .fediac import aggregate_stack
    return aggregate_stack(u_stack, with_pallas(cfg, spec), key, a=a)


def _run_stream(spec, u_stack, cfg, key, a):
    from .stream_engine import aggregate_stream
    return aggregate_stream(u_stack, with_pallas(cfg, spec), key, a=a,
                            chunk=spec.chunk or None)


def _run_sharded(spec, u_stack, cfg, key, a):
    from .shard_engine import aggregate_shard
    return aggregate_shard(u_stack, with_pallas(cfg, spec), key, a=a,
                           devices=spec.devices or None, axis=spec.axis)


def _run_async(spec, u_stack, cfg, key, a):
    from repro.netsim.async_engine import aggregate_async_stack
    return aggregate_async_stack(u_stack, with_pallas(cfg, spec), key, a=a)


_RUNNERS = {
    "monolithic": _run_monolithic,
    "stream": _run_stream,
    "sharded": _run_sharded,
    "async": _run_async,
}


def names() -> tuple[str, ...]:
    """Registered engine names, registration order."""
    return tuple(_RUNNERS)


def register(name: str, runner) -> None:
    """Add an engine: ``runner(spec, u_stack, cfg, key, a)`` with the
    ``aggregate_stack`` return contract.  Future engines plug in here and
    inherit the bit-identity oracle from ``tests/test_engine_matrix.py``.
    """
    _RUNNERS[str(name)] = runner


def _unknown(name) -> ValueError:
    return ValueError(f"unknown FediAC engine {name!r} "
                      f"(expected one of {', '.join(map(repr, _RUNNERS))})")


def get(engine: str | EngineSpec) -> EngineSpec:
    """Normalize a name or spec to a validated :class:`EngineSpec`."""
    if isinstance(engine, EngineSpec):
        if engine.name not in _RUNNERS:
            raise _unknown(engine.name)
        return engine
    if isinstance(engine, str):
        if engine not in _RUNNERS:
            raise _unknown(engine)
        return EngineSpec(name=engine)
    raise TypeError("engine must be an EngineSpec or a registered name, "
                    f"got {type(engine).__name__}")


_warned: set[str] = set()


def _warn_once(field: str, replacement: str) -> None:
    if field in _warned:
        return
    _warned.add(field)
    warnings.warn(f"{field} is deprecated; {replacement}",
                  DeprecationWarning, stacklevel=4)


def _reset_deprecation_warnings() -> None:
    """Test hook: make the next legacy-knob use warn again."""
    _warned.clear()


def resolve(cfg) -> EngineSpec:
    """The engine spec a config selects, legacy knobs folded in.

    ``cfg.engine`` may be a name or an ``EngineSpec``.  The deprecated
    ``cfg.stream_chunk`` / ``cfg.use_pallas`` fields still forward into
    the spec (warning once per process) so old call sites keep their
    exact behavior; new code sets the fields on the spec itself.
    """
    spec = get(getattr(cfg, "engine", "monolithic"))
    chunk = int(getattr(cfg, "stream_chunk", 0) or 0)
    if chunk and not spec.chunk:
        _warn_once("FediACConfig.stream_chunk",
                   "pass engine=EngineSpec(name='stream', chunk=...)")
        spec = dataclasses.replace(spec, chunk=chunk)
    if getattr(cfg, "use_pallas", False) and not spec.use_pallas:
        _warn_once("FediACConfig.use_pallas as an engine selector",
                   "pass engine=EngineSpec(name=..., use_pallas=True)")
        spec = dataclasses.replace(spec, use_pallas=True)
    return spec


def with_pallas(cfg, spec: EngineSpec):
    """A cfg whose low-level ``use_pallas`` mechanism matches the spec
    (``aggregate_stack``/``aggregate_stream`` and the packet cores read
    the cfg field)."""
    if getattr(cfg, "use_pallas", False) == spec.use_pallas:
        return cfg
    return dataclasses.replace(cfg, use_pallas=spec.use_pallas)


def run(spec: EngineSpec, u_stack, cfg, key, *, a=None):
    """Run one stacked round on ``spec``'s engine.  Same signature and
    ``(delta, residuals, counts, TrafficStats)`` contract as
    ``aggregate_stack``; every registered engine is bit-identical to it.
    """
    try:
        runner = _RUNNERS[spec.name]
    except KeyError:
        raise _unknown(spec.name) from None
    return runner(spec, u_stack, cfg, key, a)
