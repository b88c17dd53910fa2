"""What every runner needs around the system under test: the device check,
compile counting, memory, the profiler session and the program-side checks
(``correct``) that ``chip_smoke.py`` asserts."""
from __future__ import annotations

import os
import re
import shutil
import time
from typing import Dict, List, Optional

from . import manifest as _manifest

T_PROCESS_START = time.perf_counter()     # reset by run.py as its first act
OUT_DIR = os.path.join(_manifest.ROOT, "benchmark_out")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def say(msg: str):
    print(f"benchmark: {msg}", flush=True)


def require_tpu(chips: int) -> List:
    import jax

    devices = jax.devices()
    found = f"platform '{devices[0].platform}' ({devices[0].device_kind}) x {len(devices)}"
    if devices[0].platform != "tpu":
        raise NoChip(f"this benchmark needs a TPU and JAX found {found}; nothing was run")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips and JAX found {found}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """The program's compile cache at its fixed path, keeping EVERY program.
    By default JAX keeps only what took a second to compile, so the hundreds
    of small programs of set-up (the eager operations of the reference check,
    the initialisers) would compile again in every run: seconds of set-up
    that also vary with the host's load."""
    import jax

    import paddle_tpu.sysconfig as sysconfig

    path = sysconfig.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts XLA compilations (cache loads included) through ``jax.monitoring``."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw):
        if event.endswith("backend_compile_duration"):
            self.count += 1


def memory_stats(devices) -> Dict[str, int]:
    """The fullest chip's allocator stats (by peak)."""
    return dict(max(((d.memory_stats() or {}) for d in devices),
                    key=lambda s: s.get("peak_bytes_in_use", 0)))


def mosaic_kernels(lowered_texts) -> set:
    names = set()
    for text in lowered_texts:
        for line in text.splitlines():
            if "@tpu_custom_call" in line:
                names.update(re.findall(r'kernel_name = "([^"]+)"', line))
    return names


def fallbacks_noted() -> List[str]:
    from paddle_tpu.analysis import codes

    return sorted(str(f) for f in codes._SEEN_FALLBACKS)


class TraceSession:
    """One short profile inside the window, reduced in this process."""

    def __init__(self, cell_name: str):
        self.dir = os.path.join(OUT_DIR, cell_name, "trace")
        self.cell_dir = os.path.join(OUT_DIR, cell_name)
        self.reduced: Optional[Dict] = None
        self._window = None

    def start(self):
        import jax

        from paddle_tpu.telemetry import trace as ttrace

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        self.tracer = ttrace.enable(annotate=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # spans come as annotations; no per-call tracing
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._window = jax.profiler.TraceAnnotation("bench.trace_window")
        self._window.__enter__()

    def end_window(self):
        """Close the traced window; the profiler itself is stopped later
        (``finish``), where its cost disturbs no request."""
        from paddle_tpu.telemetry import trace as ttrace

        self._window.__exit__(None, None, None)
        ttrace.disable()

    def finish(self):
        import jax

        from ..trace_reduce import events, reduce

        jax.profiler.stop_trace()
        path = events.find_xplane(self.dir)
        if path is None:
            raise RuntimeError(f"the profiler wrote no .xplane.pb under {self.dir}")
        trace = events.extract(path)
        events.save(trace, os.path.join(self.cell_dir, "trace_events.json.gz"))
        shutil.rmtree(self.dir, ignore_errors=True)    # the raw trace is large
        self.trace = trace
        self.reduced = reduce.reduce_trace(trace)
        return self.reduced
