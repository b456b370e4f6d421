"""What every cell shares: finding a cell's files by name, the device
check, the set-up clock, host spans, the trace and the result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its files are
found by the names there, so a later cell, configuration, traffic mix
or per-layer metric is added as files and entries, never by an edit:

- ``bench/configs/<config>.json``: the deployment;
- ``bench/traffic/<traffic>.json``: the mix's parameters, with the
  ``kind`` of generator that reads them;
- ``bench/traffic/<kind>.py``: that generator and the window's driver;
- ``bench/metrics/<metric>.py``: the reader of one per-layer metric.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoDevice(SystemExit):
    """No TPU, or fewer chips than the cell asks for."""


def spec_file() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_cell(name: str, spec: dict | None = None) -> dict:
    """The cell ``name`` with its configuration, traffic mix and the
    per-layer metrics that it reports."""
    spec = spec or spec_file()
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = dict(cells[name])
    cell["config_data"] = json.loads(
        (BENCH / "configs" / f"{cell['config']}.json").read_text())
    cell["traffic_data"] = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    cell["end_to_end"] = [m for m in spec["end_to_end"] if applies(m, name)]
    reported = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [m for m in spec["per_layer"]
                         if name in m.get("workloads", ())
                         or ("workloads" not in m and m["moves"] in reported)]
    return cell


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _module(path: pathlib.Path, name: str):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def kind_module(cell: dict):
    """``bench/traffic/<kind>.py`` of the cell's traffic mix."""
    kind = cell["traffic_data"]["kind"]
    return _module(BENCH / "traffic" / f"{kind}.py", f"bench_kind_{kind}")


def reader(metric: str):
    """The ``read(run)`` of ``bench/metrics/<metric>.py``."""
    return _module(BENCH / "metrics" / f"{metric}.py",
                   f"bench_metric_{metric.replace('.', '_')}").read


def peaks(kind: str) -> dict:
    """The published peaks of one chip of ``device_kind`` ``kind``."""
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json; known: {sorted(table)}")
    return table[kind]


def require_devices(chips: int) -> dict:
    """The accelerator JAX sees; exits non-zero unless it is a TPU with at
    least ``chips`` devices."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"bench: no TPU found, jax.devices()[0].platform is "
                       f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"bench: the cell needs {chips} chips, JAX sees "
                       f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class SetupClock:
    """JAX's own lowering and compile seconds, compile count and
    persistent-cache hits, read from ``jax.monitoring`` (listeners live
    as long as the process: make one per run)."""
    COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                      "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event in self.COMPILE_EVENTS:
            self.compile_s += secs
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def span(name: str):
    """A host span around one call into the program; it lands in the
    profiler's trace when one is being taken, and costs next to nothing
    otherwise."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """Takes the profiler trace of one stretch of the window (``--trace
    1``); a no-op otherwise.  The stretch is marked in the trace by the
    host span ``WINDOW_SPAN``, opened once the profiler runs and closed
    before it stops: the traced window is that span, on the trace's own
    clock."""

    WINDOW_SPAN = "bench.window"

    def __init__(self, on: bool, out_dir: pathlib.Path):
        self.on = on
        self.out_dir = out_dir
        self.done = False
        self._span = None

    def start(self):
        if not self.on or self._span is not None or self.done:
            return
        import jax
        import shutil
        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
        self._span = span(self.WINDOW_SPAN)
        self._span.__enter__()

    def stop(self):
        if self._span is None:
            return
        import jax
        self._span.__exit__(None, None, None)
        self._span = None
        self.done = True
        jax.profiler.stop_trace()          # collects: not in the window

    def xplane(self) -> pathlib.Path | None:
        found = sorted(self.out_dir.glob("**/*.xplane.pb"))
        return found[-1] if found else None
