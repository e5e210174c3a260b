"""What both runners share: the run's context, the notes printed before the
last line, compile counting, the profiler window and the metric readers'
loader.  Nothing here knows a cell, a configuration or a metric by name."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Any, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_data" / "trace"


@dataclass
class Context:
    cell: dict          # BENCHMARK.json entry + its config/traffic files read in
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t0: float           # perf_counter at process start (set-up counts from it)
    devices: List[Any]  # the chips this cell may use


def open_devices(chips: int, rehearse: bool):
    """Turn the compile cache on (before JAX compiles anything) and return
    (JAX's devices, the cache directory); the devices are ``None`` when
    there is no TPU with ``chips`` chips and this is not a rehearsal."""
    if rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from gymfx_tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    on_chip = devices[0].platform == "tpu" and len(devices) >= chips
    if not (on_chip or rehearse):
        print(f"benchmarks: {chips} TPU chip(s) needed; JAX found {len(devices)} x "
              f"{devices[0].platform}", file=sys.stderr)
        return None, cache_dir
    return devices, cache_dir


def note(**fields) -> None:
    """One JSON line of side information (never the last line)."""
    print(json.dumps({"note": fields}, default=str), flush=True)


def seed31(seed: int, stream: int = 0) -> int:
    """A 31-bit seed drawn from ``--seed`` (any whole number, also above
    2**31) and a stream number: what PRNGKey and default_rng are given."""
    import numpy as np

    return int(np.random.SeedSequence([int(seed), stream]).generate_state(1)[0] >> 1)


def program_config(cell: dict, **extra) -> dict:
    """The program's defaults with the configuration file's ``program``
    overrides, then the traffic file's, then ``extra``."""
    from gymfx_tpu.config import DEFAULT_VALUES

    config = dict(DEFAULT_VALUES)
    config.update(cell["config"].get("program", {}))
    config.update(cell["traffic"].get("program", {}))
    config.update(extra)
    return config


def load_module(folder: str, name: str):
    """``benchmarks/<folder>/<name>.py`` by file path (a metric's name may
    hold dots, which an import statement could not take)."""
    path = BENCH / folder / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"{folder}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class CompileCounter:
    """Counts what JAX's monitoring reports of compilation: ``count`` is
    every request to build a program (compiled or fetched from the
    persistent cache: either one inside a window is a fault), ``hits`` and
    ``misses`` are the persistent cache's."""

    EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
              "/jax/compilation_cache/cache_misses": "misses"}
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == self.COMPILE:
            self.count += 1

    def _on_event(self, event, **_):
        if event in self.EVENTS:
            setattr(self, self.EVENTS[event], getattr(self, self.EVENTS[event]) + 1)


def device_report(devices, chips: int) -> dict:
    """The device as JAX reports it, and the peak on the fullest chip.  The
    TPU allocator keeps two books: ``peak_bytes_in_use`` counts live arrays
    only, and the scratch a running program holds is ``peak_bytes_reserved``
    (PR 24: 0.20 GB and 7.26 GB beside a step whose temporaries the compiler
    gives as 7.26 GB).  The peak reported is their sum."""
    peaks = []
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        note(memory_stats={"device": d.id, **stats})
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


@contextlib.contextmanager
def traced_window(cell_name: str, result: dict, root: Path = TRACE_DIR):
    """Profile what runs inside: writes the ``.xplane.pb`` under
    ``<root>/<cell>/`` (emptied first; ``.bench_data/trace`` in the checkout) and leaves its reduction
    (reduce_trace.reduce) in ``result``.  The caller synchronises the
    device before entering and before leaving."""
    import jax

    import reduce_trace

    out = Path(root) / cell_name
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # every Python call as an event: slows the host
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(out), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(reduce_trace.WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()
    files = sorted(out.rglob("*.xplane.pb"))
    result.update(reduce_trace.reduce(files[-1]))
    result["xplane"] = str(files[-1])
