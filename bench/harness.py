"""One run of one cell: resolve it by name, set it up, measure, check,
and print the result line.

Resolution is by name alone, so that a later change adds a cell by adding
files: the cell's entry in ``BENCHMARK.json`` names its configuration
(whose ``file`` is given there) and its traffic (``traffic/<name>.json``),
the traffic names its driver kind (``drivers/<kind>.py``), the
configuration its reference (``references/<semantics>.py``), and each
per-layer metric of ``BENCHMARK.json`` is read by ``metrics/<name>.py``.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

BENCH = pathlib.Path(__file__).resolve().parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {path.relative_to(BENCH.parent)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: object
    reference: object
    end_to_end: list[dict]
    per_layer: list[tuple[dict, object]]


def resolve(root: pathlib.Path, workload: str) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    bench = root / "bench"
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    driver = load_module(bench / "drivers" / f"{traffic['driver']}.py",
                         f"bench_driver_{traffic['driver']}")
    semantics = config["deployment"]["semantics"]
    reference = load_module(bench / "references" / f"{semantics}.py",
                            f"bench_reference_{semantics}")

    def applies(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = []
    for m in spec["per_layer"]:
        if "workloads" in m:
            if workload not in m["workloads"]:
                continue
        elif m["moves"] not in e2e_names:
            continue
        per_layer.append((m, load_module(bench / "metrics" / f"{m['name']}.py",
                                         f"bench_metric_{m['name']}")))
    return Cell(workload, int(w["chips"]), config, traffic, driver,
                reference, e2e, per_layer)


class CompileClock:
    """Backend compiles (persistent-cache retrievals included) and cache
    hits, as JAX reports them."""

    def __init__(self):
        import jax
        self.seconds, self.count, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.count += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def mark(self) -> tuple[float, int, int]:
        return self.seconds, self.count, self.hits

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)
        jax.monitoring.unregister_event_listener(self._on_event)


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_chip and (d.platform != "tpu" or len(devs) < chips):
        raise SystemExit(f"bench: the cell needs {chips} TPU chip(s); JAX "
                         f"finds {len(devs)} {d.platform!r} device(s)")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


@dataclass
class Run:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    kernel_impl: str
    device_kind: str = ""
    dep: object = None
    hooks: object = None
    summary: object = None
    attempted: int = 0
    window_s: float = 0.0
    notes: dict = field(default_factory=dict)
    metrics: dict = field(default_factory=dict)
    device: dict = field(default_factory=dict)

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic


def measure(cell: Cell, seed: int, seconds: float, trace: bool, *,
            t_start: float, require_chip: bool = True,
            kernel_impl: str = "pallas") -> Run:
    """Set up, measure the window, and take what the check needs; the
    system's state is freed on return.  ``run.metrics`` holds the
    cell's metrics and ``run.device`` the device line."""
    device = device_info(cell.chips, require_chip)
    t_device = time.monotonic() - t_start
    from repro.kernels.device import use_compile_cache
    import jax
    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()
    from repro.state import lsm
    lsm.set_kernel_impl(kernel_impl)

    run = Run(cell, seed, seconds, trace, kernel_impl, device["kind"])
    n_programs = 0
    if kernel_impl == "pallas":     # the cell's kernel shapes, compiled ahead
        from bench import warmup
        n_programs = warmup.compile_shapes(cell.traffic.get("compile", {}))
    t_compiled = time.monotonic() - t_start
    cell.driver.setup(run)
    setup_s = time.monotonic() - t_start
    c_setup = clock.mark()
    log(f"setup: {setup_s:.3f} s (device ready at {t_device:.3f} s, shapes "
        f"compiled at {t_compiled:.3f} s); compile cache {cache}; {n_programs} "
        f"programs compiled ahead; {c_setup[1]} backend compiles in "
        f"{c_setup[0]:.3f} s ({c_setup[2]} from the persistent cache)")

    tmp = None
    if trace:
        from bench.hooks import Hooks
        run.hooks = Hooks()
        run.hooks.install()
        if run.dep is not None:
            src = run.dep.source_op
            src.generator = run.hooks.wrap_source(src.generator)
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        values = cell.driver.window(run)
    run.window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
        run.hooks.uninstall()
    c_win = clock.mark()
    clock.close()
    log(f"window: {run.window_s:.3f} s, {run.notes}; backend compiles in "
        f"the window: {c_win[1] - c_setup[1]} in "
        f"{c_win[0] - c_setup[0]:.3f} s")

    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    cell.driver.collect(run)
    if run.dep is not None:
        run.dep.free()

    run.device, run.metrics = device, {}
    if trace:
        from bench.trace import Summary, read_profile
        run.summary = Summary(read_profile(tmp))
        shutil.rmtree(tmp, ignore_errors=True)
        device["busy_s"] = run.summary.busy_s
        device["window_s"] = run.summary.window_s
        for m, reader in cell.per_layer:
            v = reader.read(run)
            if v is not None:
                run.metrics[m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}
    else:
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            if m["name"] in values:
                run.metrics[m["name"]] = {"value": float(values[m["name"]]),
                                          "unit": m["unit"]}
    return run


def judge(run: Run, weights: str = "exact") -> dict:
    """The result line: the check's counts against their limits (the
    reference keeps ``weights``; anything but "exact" is a control)."""
    t0 = time.perf_counter()
    counts = run.cell.driver.check(run, weights).counts
    from bench.check import report
    checks = report(counts)
    log(f"check: {time.perf_counter() - t0:.3f} s")
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": run.attempted,
              "failed": min(run.attempted, sum(counts.values())),
              "metrics": run.metrics, "device": run.device}
    if run.trace:
        from bench.hooks import SITES, SOURCE
        names = [s[0] for s in SITES] + [SOURCE]
        breakdown = {"device_ops": run.summary.top_ops(10)}
        gaps = run.summary.idle_gaps(names, 10)
        if gaps is not None:
            breakdown["idle_gaps"] = gaps
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, **kw) -> dict:
    """Set up, measure and check one run; returns the result line."""
    return judge(measure(cell, seed, seconds, trace, t_start=t_start, **kw))


def emit(result: dict) -> None:
    """Compared numbers as the last lines of standard error, and the
    result as the last line of standard output."""
    for name, c in result["checks"].items():
        log(f"compared {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
