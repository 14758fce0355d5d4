"""Run one benchmark cell once on the chip and print its result line.

    python3 -m chipbench.run --workload t40.mine --seed 7 --seconds 10 --trace 0

Everything the cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in ``chipbench/configs/``, its traffic
mix in ``chipbench/traffic/<traffic>.json``, the code that runs the mix's
``mode`` in ``chipbench/modes/<mode>.py``, and each per-layer metric's
reader in ``chipbench/metrics/<name>.py``.  Adding a cell, a configuration,
a mix or a metric adds files and entries; it edits none.

A run generates its data from ``--seed``, builds the system under test,
warms every shape the window uses (set-up, reported as ``setup_s``), then
drives the mix for ``--seconds``.  With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` the window runs under the JAX
profiler and the program's spans, and the result carries the per-layer
metrics, the device's busy and window seconds and a breakdown.  Once the
window has closed, what it produced is compared with the plain reference
(``chipbench/reference.py``); each number compared is printed with its
limit as the last lines on standard error and under ``check`` in the result.

The first JAX device must be a TPU and there must be as many as the cell
asks for; otherwise the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
WINDOW_ANNOTATION = "chipbench.window"


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(bench_root: Path, workload: str) -> dict:
    """The cell ``workload`` with its configuration, traffic mix and
    metric entries, all read from files found by name."""
    bench = json.loads((bench_root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r} in "
                         f"BENCHMARK.json; have {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((bench_root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (bench_root / "chipbench" / "traffic" / f"{cell['traffic']}.json")
        .read_text())

    def in_cell(m):
        return workload in m.get("workloads", [workload])

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if in_cell(m)],
            "per_layer": [m for m in bench["per_layer"] if in_cell(m)],
            "root": bench_root}


def cache_env(bench_root: Path) -> Path:
    """Compile cache, autotune plans and cost-model fits under the
    checkout's ``chipbench/.cache``, whatever the environment says, so that
    two checkouts share nothing; the TPU runtime's logs too, unless
    ``TPU_LOG_DIR`` names a place for them.  The compile cache is kept
    between runs, so only a checkout's first run compiles; the cost model
    starts empty in every run, because its fits carry over and move with
    every run that adds to them."""
    cache = bench_root / "chipbench" / ".cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache / "jax")
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(cache / "autotune.json")
    os.environ["REPRO_COSTMODEL_CACHE"] = str(cache / "costmodel.json")
    os.environ.setdefault("TPU_LOG_DIR", str(cache / "tpu_logs"))
    (cache / "costmodel.json").unlink(missing_ok=True)
    return cache


def chip_devices(chips: int, require_tpu: bool = True) -> list:
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"the first JAX device is {devices[0].platform!r}, "
                     "not a TPU; nothing here runs on the CPU")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices[:chips]


class CompileCounter:
    """XLA compiles and persistent-cache hits, from JAX's monitoring
    events (as the program's ``chip_smoke.py`` counts them)."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.n, self.seconds, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self._COMPILE:
            self.n += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == self._HIT:
            self.hits += 1


class Context:
    """What a run hands its per-layer metric readers.

    ``window`` holds the mode's own records of the window (mines, updates);
    ``spans`` the program's spans inside it and ``trace`` the reduced device
    trace, both only in a traced run."""

    def __init__(self, loaded: dict, devices: list):
        self.cell = loaded["cell"]
        self.config = loaded["config"]
        self.traffic = loaded["traffic"]
        self.devices = devices
        self.chips = len(devices)
        self.window: dict = {}
        self.compiles_in_window = 0
        self.spans: list = []
        self.trace = None
        self.trace_offset_ns = 0.0

    @property
    def peaks(self) -> dict:
        from chipbench.peaks import peaks
        return peaks(self.devices[0].device_kind)

    def span_ns(self, span) -> tuple[float, float]:
        """A program span's interval on the trace's clock."""
        return (span.t0 * 1e9 + self.trace_offset_ns,
                span.t1 * 1e9 + self.trace_offset_ns)


def read_metrics(ctx: Context, entries: list, bench_root: Path) -> dict:
    out = {}
    for m in entries:
        reader = load_module(bench_root / "chipbench" / "metrics"
                             / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        bench_root: Path = ROOT, require_tpu: bool = True,
        t_start: float = T0, log=None) -> dict:
    """One run of one cell; returns the result object (the last line)."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    loaded = load_cell(bench_root, workload)
    cell = loaded["cell"]
    cache = cache_env(bench_root)
    sys.path.insert(0, str(bench_root / "src"))
    import jax
    from repro.launch.cache import enable_compile_cache
    from repro.obs.trace import Tracer, use_tracer

    devices = chip_devices(cell["chips"], require_tpu)
    if require_tpu:
        enable_compile_cache()
    dev = devices[0]
    print(f"device platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)}", flush=True)
    ctx = Context(loaded, devices)
    mode = load_module(bench_root / "chipbench" / "modes"
                       / f"{loaded['traffic']['mode']}.py")
    compiles = CompileCounter()
    state = mode.setup(ctx, seed)
    setup_s = time.perf_counter() - t_start
    print(f"setup_s={setup_s:.6f} compiles={compiles.n} "
          f"compile_s={compiles.seconds:.6f} cache_hits={compiles.hits}",
          flush=True)

    n0, hits0 = compiles.n, compiles.hits
    trace_dir = cache / "trace" / workload
    tracer = Tracer() if trace else None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # device ops and annotations only
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with use_tracer(tracer), \
                jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
            t_win = time.perf_counter()
            e2e = mode.window(ctx, state, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    ctx.compiles_in_window = compiles.n - n0
    print(f"window compiles={ctx.compiles_in_window} "
          f"cache_hits={compiles.hits - hits0}", flush=True)
    mode.describe(ctx, state)
    peak = memory_peak(devices)

    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(devices), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        from chipbench import xtrace
        events = xtrace.load(str(trace_dir))
        win = xtrace.window_of(events, WINDOW_ANNOTATION)
        ctx.trace = xtrace.reduce(events, win)
        ctx.trace_offset_ns = win[0] - t_win * 1e9
        ctx.spans = [s for s in tracer.spans if s.t1 is not None]
        result_device["busy_s"] = ctx.trace.mean_busy_s
        result_device["window_s"] = ctx.trace.window_s
        named = [(s.name, *ctx.span_ns(s)) for s in ctx.spans]
        breakdown = {"device_ops": xtrace.top_ops(ctx.trace),
                     "idle_gaps": xtrace.idle_gaps(ctx.trace, named)}
        metrics = read_metrics(ctx, loaded["per_layer"], bench_root)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in loaded["end_to_end"]:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}

    attempted, failed, checks = mode.check(ctx, state, log)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": result_device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
