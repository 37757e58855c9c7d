"""What every path of the benchmark shares: finding files by name, the
device and its peaks, the seed, the compile cache and compile counting.

Nothing here belongs to one configuration, traffic mix or metric; those
are files of their own, found by the name that ``BENCHMARK.json`` gives.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
# JAX's persistent compile cache: a fixed path inside the checkout, so
# that every run of a cell after the first finds its programs there.
CACHE_DIR = ROOT / ".bench_cache" / "jax"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> dict:
    """The ``workloads`` entry named ``name``, with its configuration and
    traffic files read in."""
    spec = benchmark()
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return make_cell(w["config"], w["traffic"], name=name, chips=w["chips"],
                     config_file=ROOT / conf["file"])


def make_cell(config: str, traffic: str, *, name: str | None = None,
              chips: int = 1, config_file: Path | None = None) -> dict:
    """A cell from a configuration and a traffic mix, by their names."""
    return {"name": name or f"{config}.{traffic}", "config": config,
            "traffic": traffic, "chips": chips,
            "config_data": load_json(config_file or BENCH_DIR / "configs" / f"{config}.json"),
            "traffic_data": load_json(BENCH_DIR / "workloads" / f"{traffic}.json")}


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold ``-``/``.``)."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def seed_key(seed: int):
    """A threefry key from a seed of up to 64 bits: every bit counts (a
    plain ``jax.random.key`` keeps only the low 32 with x64 off)."""
    import jax
    import numpy as np
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    data = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(data, impl="threefry2x32")


def enable_compile_cache() -> str:
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no eviction: an evicting cache stats every entry's access-time file
    # on each write, and one entry without that file fails every later write
    jax.config.update("jax_compilation_cache_max_size", -1)
    return str(CACHE_DIR)


class CompileCounter:
    """Counts XLA compilations (persistent-cache loads included) inside a
    ``with`` block."""

    def __init__(self):
        self.count = 0

    def _on_event(self, event, duration, **kwargs):
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        return False


def require_chips(chips: int):
    """The devices of the run: ``chips`` TPU chips, or SystemExit(2)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(f"bench: needs {chips} TPU chip(s), found "
                         f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def device_info(devs, memory_peak_bytes: int) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs),
            "memory_peak_bytes": memory_peak_bytes}


def peak_bytes(devs) -> int:
    """``peak_bytes_in_use`` of the fullest device."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
