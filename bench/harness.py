"""What the benchmark reads from its files, and the run it hands a driver.

Everything that belongs to one cell, configuration or per-layer metric is
a file of its own, found by name:

  BENCHMARK.json               cells, metrics and bounds
  bench/workloads/<cell>.json  driver, chips and every traffic parameter
  bench/configs/<config>.json  the configuration as it is run
  bench/configs/<config>.py    its plain reference
  bench/drivers/<driver>.py    ``run(run: Run) -> Outcome``
  bench/metrics/<metric>.py    ``read(ctx: Context) -> float | None``
  bench/peaks.json             the chip's peaks, keyed by ``device_kind``

A workload file names its driver and holds every traffic parameter, and
``"limits"``: the limit of each number its driver compares with the
reference (``Check``), one for each name in the driver's ``CHECKS``.

The reference of a configuration that a serving or training cell runs
provides, with ``cfg`` the configuration file's contents:

  init_weights(key, cfg, dtype)       the weights, in the system's tree
  logits(w, tokens, cfg, low=False)   (S, vocab) float32, one sequence
  loss(w, tokens, labels, cfg, low=False)
                                      mean next-token cross-entropy
  model_config(cfg)                   the system's ``ModelConfig``
  decode_flops(cfg, rows, ctx_sum)    model operations of one decode
                                      call (``decode_step.mfu``)
  train_flops_per_token(cfg, seq)     forward and backward operations
                                      per token (``train_step.mfu``)

``low=True`` is the control: the same computation in the precision below
the configuration's.  The training driver also takes ``cosine_lr`` and
``adamw_step`` from it.  So a configuration of another architecture is
added as these two files, with no edit to the drivers or readers.

Nothing here imports JAX at import time.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return read_json(root / "BENCHMARK.json")


def load_module(path: pathlib.Path, name: str | None = None):
    """Import a file by path (names such as ``qwen2-0.5b.py`` are not
    identifiers)."""
    name = name or "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell(name: str, bench: dict, bench_dir: pathlib.Path = BENCH) -> dict:
    """The cell's workload file, checked against its ``BENCHMARK.json``
    entry: ``{"name", "config", "traffic", "chips", "driver", ...}``."""
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    entry = entries[name]
    spec = read_json(bench_dir / "workloads" / f"{name}.json")
    for key in ("config", "traffic", "chips"):
        if spec.get(key) != entry[key]:
            raise ValueError(f"{name}: workload file has {key}="
                             f"{spec.get(key)!r}, BENCHMARK.json "
                             f"{entry[key]!r}")
    return {"name": name, **spec}


def config(name: str, bench_dir: pathlib.Path = BENCH) -> dict:
    return read_json(bench_dir / "configs" / f"{name}.json")


def reference(name: str, bench_dir: pathlib.Path = BENCH):
    return load_module(bench_dir / "configs" / f"{name}.py")


def driver(name: str, bench_dir: pathlib.Path = BENCH):
    return load_module(bench_dir / "drivers" / f"{name}.py",
                       f"bench_driver_{name}")


def metric_reader(name: str, bench_dir: pathlib.Path = BENCH):
    return load_module(bench_dir / "metrics" / f"{name}.py")


def trace_module():
    """``bench/trace.py`` (by path: the standard library has a ``trace``)."""
    return sys.modules.get("bench_trace") or load_module(
        BENCH / "trace.py", "bench_trace")


def peaks(kind: str, bench_dir: pathlib.Path = BENCH) -> dict:
    table = read_json(bench_dir / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


def seed_key(seed: int):
    """A JAX key for any whole-number seed (``PRNGKey`` alone keeps only
    the low 32 bits)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _applies(metric: dict, name: str) -> bool:
    return "workloads" not in metric or name in metric["workloads"]


def end_to_end_metrics(bench: dict, name: str) -> list[dict]:
    return [m for m in bench["end_to_end"] if _applies(m, name)]


def per_layer_metrics(bench: dict, name: str) -> list[dict]:
    """Per-layer metrics of the cell: those that list it, and those with
    no list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_metrics(bench, name)}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if name in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e:
            out.append(m)
    return out


# ---- what a driver gets and gives back ------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct while ``value <=
    limit`` (every compared number is a gap or an error)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def checks(cell: dict, values: dict) -> list[Check]:
    """Each number compared, beside its limit from the cell's workload
    file (``"limits"``)."""
    return [Check(k, v, cell["limits"][k]) for k, v in values.items()]


@dataclasses.dataclass
class Outcome:
    metrics: dict            # end-to-end name -> value
    checks: list             # [Check]
    attempted: int
    failed: int
    info: dict = dataclasses.field(default_factory=dict)


class Window:
    """The measured window: its edges on the host clock, the profiler
    trace of its first ``trace_s`` seconds when tracing, and the peak
    device memory read after it."""

    def __init__(self, *, trace_dir: str | None, trace_s: float):
        self.trace_dir = trace_dir
        self.trace_s = trace_s
        self.opened = self.closed = None
        self._span = None
        self.compiles = 0           # backend compilations inside the window
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, _secs: float, **_kw) -> None:
        if (name.endswith("backend_compile_duration")
                and self.opened is not None and self.closed is None):
            self.compiles += 1

    def open(self) -> None:
        self.opened = time.perf_counter()
        if self.trace_dir:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation(
                trace_module().WINDOW_SPAN)
            self._span.__enter__()

    @property
    def tracing(self) -> bool:
        return self._span is not None

    def poll(self) -> None:
        """Stop the trace once ``trace_s`` seconds of the window are in
        it; drivers call this between calls into the system."""
        if self.tracing and time.perf_counter() - self.opened >= self.trace_s:
            self.stop_trace()

    def stop_trace(self) -> None:
        if self.tracing:
            import jax

            self._span.__exit__(None, None, None)
            self._span = None
            jax.profiler.stop_trace()

    def close(self) -> None:
        self.closed = time.perf_counter()
        self.stop_trace()

    @property
    def seconds(self) -> float:
        return self.closed - self.opened


@dataclasses.dataclass
class Run:
    """One run of one cell, as the driver sees it."""
    cell: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    window: Window
    started: float                       # process start, host clock
    memory_peak_bytes: int | None = None
    info: dict = dataclasses.field(default_factory=dict)

    def note_memory(self) -> None:
        """Read the peak device memory: after the window, before the
        reference runs and before the program's state is freed."""
        peaks_ = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks_.append(int(stats["peak_bytes_in_use"]))
        self.memory_peak_bytes = max(peaks_) if peaks_ else None

    @property
    def setup_s(self) -> float:
        return self.window.opened - self.started


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""
    trace: object             # trace.Reduction of the traced window
    run: Run
    peak: dict
    info: dict
