"""One run of one cell: set-up, the measured window, the check, the
result line.

The window drives the users' entry, ``repro.fleet.run_sweep``, with a
``SweepConfig`` built from the cell's files and a fresh ``base_seed``
per call.  The benchmark wraps the names ``run_sweep`` calls
(``make_workload``, ``make_fleet``, ``fleet_run``, ``jax_to_np``,
``summarize``, ``merge_cell_moments``) with host timers that are also
``jax.profiler.TraceAnnotation`` spans, and keeps each ``fleet_run``'s
outputs until the window has closed, for the check.  It copies nothing
of the path it measures.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from fleetbench import compare
from fleetbench import reference as R
from fleetbench import trace as T
from fleetbench.peaks import peaks
from fleetbench.spec import BENCH_DIR, ROOT, Benchmark, Cell

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = BENCH_DIR / ".jax_cache"
#: replicas of the window that the reference re-runs.
SAMPLE = 64
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "traces",
    "/jax/core/compile/backend_compile_duration": "compiles",
}
#: what ``run_sweep`` calls, wrapped as phases.
PHASES = {
    "make_workload": "host_gen", "make_fleet": "make_fleet",
    "fleet_run": "dispatch", "jax_to_np": "transfer",
    "summarize": "reduce", "merge_cell_moments": "reduce",
}


class NoChip(RuntimeError):
    pass


def call_seed(seed: int, i: int) -> int:
    """The ``base_seed`` of call ``i`` of a run (call 0 is the warm-up)."""
    ss = np.random.SeedSequence([seed % 2**64, i])
    return int(ss.generate_state(1, np.uint32)[0])


@dataclasses.dataclass
class Call:
    base_seed: int
    out: dict | None = None
    batches: list = dataclasses.field(default_factory=list)
    error: str | None = None


class Probe:
    """Host seconds by phase, trace spans, and the outputs of each
    ``fleet_run`` of the current call."""

    def __init__(self, sweep_module):
        import jax

        self.jax = jax
        self.mod = sweep_module
        self.seconds = collections.Counter()
        self.call: Call | None = None
        self.counts = collections.Counter()
        self.counting = False

    def _wrap(self, name, fn):
        phase = PHASES[name]
        ann = self.jax.profiler.TraceAnnotation

        def wrapped(*a, **kw):
            t = time.perf_counter()
            with ann(f"bench/{phase}"):
                out = fn(*a, **kw)
            self.seconds[phase] += time.perf_counter() - t
            if name == "fleet_run" and self.call is not None:
                self.call.batches.append((out[0], out[1]))
            return out

        return wrapped

    def _on_span(self, event, start, end, **_):
        if self.counting and event in COMPILE_EVENTS:
            self.counts[COMPILE_EVENTS[event]] += 1

    @contextlib.contextmanager
    def installed(self):
        saved = {n: getattr(self.mod, n) for n in PHASES}
        for n, fn in saved.items():
            setattr(self.mod, n, self._wrap(n, fn))
        self.jax.monitoring.register_event_time_span_listener(self._on_span)
        try:
            yield self
        finally:
            for n, fn in saved.items():
                setattr(self.mod, n, fn)
            self.jax.monitoring.unregister_event_time_span_listener(
                self._on_span)


@dataclasses.dataclass
class Context:
    """What a metric reader reads."""
    cell: Cell
    chips: int
    device_kind: str
    setup_s: float
    elapsed_s: float
    calls: int
    replicas_per_call: int
    real_site_ticks: int
    host_seconds: dict
    trace: T.Reduced | None

    @property
    def config(self):
        return self.cell.config

    @property
    def traffic(self):
        return self.cell.traffic

    def peaks(self) -> dict:
        return peaks(self.device_kind)


def use_compile_cache(jax) -> str:
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(CACHE_DIR)


def sweep_config(cell: Cell, base_seed: int):
    from repro.fleet import FleetParams, SweepConfig

    site, tr = cell.config["site"], cell.traffic
    params = FleetParams(
        n_devices=site["n_devices"],
        nominal_bw_bps=site["nominal_bw_bps"],
        transfer_bytes=site["transfer_bytes"],
        hp_deadline=site["hp_deadline_s"],
        lp_deadline_factor=site["lp_deadline_factor"],
        stagger=site["stagger"],
        requeue_slots=site["requeue_slots"],
        compact_every=cell.config["engine"]["compact_every"],
        mesh_shards=tr["mesh_shards"],
    )
    return SweepConfig(
        scenarios=tuple(tr["scenarios"]),
        congestion_levels=tuple(tr["congestion_levels"]),
        n_seeds=tr["n_seeds"], n_frames=site["n_frames"],
        n_devices=site["n_devices"], batch_size=tr["batch_size"],
        base_seed=base_seed, mesh_shards=tr["mesh_shards"], params=params,
    )


def program_constants() -> dict:
    """The constants the program keeps for itself rather than taking from
    a ``SweepConfig``, by the configuration's key for each."""
    import inspect

    from repro.core import jax_state, tasks
    from repro.fleet import engine, state

    out = {"site.frame_period_s": tasks.FRAME_PERIOD,
           "site.device_cores": tasks.DEVICE_CORES,
           "tasks.lp_pad_fraction": tasks.LP_PAD_FRACTION,
           "tasks.max_lp_per_frame": engine.MAX_LP,
           "engine.big": jax_state.BIG,
           "engine.max_windows": inspect.signature(
               state.make_fleet).parameters["max_windows"].default}
    for c in tasks.ALL_CONFIGS:
        out[f"tasks.{c.name}.cores"] = c.cores
        out[f"tasks.{c.name}.seconds"] = c.proc_time
    return out


def check_program_constants(config: dict) -> None:
    """Raise where the program's own constant differs from what the
    configuration states: the run would then measure and check two
    different deployments."""
    bad = []
    for key, prog in program_constants().items():
        want = config
        for part in key.split("."):
            want = want[part]
        if prog != want:
            bad.append(f"{key}: program {prog!r}, configuration {want!r}")
    if bad:
        raise RuntimeError("the program differs from the configuration: "
                           + "; ".join(bad))


def grid(cell: Cell):
    return [(s, float(c)) for s in cell.traffic["scenarios"]
            for c in cell.traffic["congestion_levels"]]


def _pick(tree, path):
    for p in path:
        tree = getattr(tree, p)
    return tree


@dataclasses.dataclass
class Window:
    """One measured window: its calls and what was measured around them."""
    calls: list
    elapsed_s: float
    host_seconds: dict
    counts: dict
    trace: T.Reduced | None = None


@dataclasses.dataclass
class HostCall:
    """A call's answers on the host: per-replica counters and re-queue
    depth, the final state of the sampled replicas, its summaries."""
    base_seed: int
    counters: dict
    rows: list
    states: dict
    out: dict


def sample(seed: int, calls: list, total: int) -> list:
    """(call, replica) pairs drawn from the seed among the replicas of
    the calls that returned."""
    good = [i for i, c in enumerate(calls) if c.error is None]
    rng = np.random.default_rng([seed % 2**64, 0x5EED])
    n = len(good) * total
    picks = rng.choice(n, size=min(SAMPLE, n), replace=False) if n else []
    return sorted((good[int(p) // total], int(p) % total) for p in picks)


def collect(cell: Cell, calls: list, seed: int) -> list:
    """Move each returned call's answers to the host and free its device
    arrays."""
    total = len(grid(cell)) * cell.traffic["n_seeds"]
    picks = sample(seed, calls, total)
    out = []
    for i, call in enumerate(calls):
        if call.error is not None:
            continue
        rows = [g for c, g in picks if c == i]
        counters = collections.defaultdict(list)
        states = {}
        b0 = 0
        for state, stats in call.batches:
            B = np.asarray(stats.frames).shape[0]
            for k in R.INT_COUNTERS + R.FLOAT_COUNTERS:
                counters[k].append(np.asarray(getattr(stats, k)))
            counters["rq_pending"].append(
                np.asarray(state.rq_valid).sum(axis=1).astype(np.int64))
            mine = [g for g in rows if b0 <= g < b0 + B]
            if mine:
                full = {k: np.asarray(_pick(state, path))
                        for k, path in compare.STATE_FIELDS.items()}
                for g in mine:
                    states[g] = {k: v[g - b0] for k, v in full.items()}
            b0 += B
        call.batches = []                  # free the device arrays
        out.append(HostCall(
            call.base_seed,
            {k: np.concatenate(v)[:total] for k, v in counters.items()},
            rows, states, call.out))
    return out


def check(cell: Cell, host: list, failed_calls: int, *,
          control: bool = False):
    """The numbers compared, from the window's answers on the host.
    ``control`` puts the reference in the program's place, computed a
    precision lower (bfloat16 for the float32 the configuration states):
    its run for the sampled replicas, its reduction for the summaries.
    Returns (numbers, failed replicas, notes)."""
    import ml_dtypes

    cells = grid(cell)
    n_seeds = cell.traffic["n_seeds"]
    total = len(cells) * n_seeds
    site = cell.config["site"]
    F, period = site["n_frames"], site["frame_period_s"]
    failed = failed_calls * total
    s_gap = 0.0
    prog_rows = collections.defaultdict(list)
    inputs_v, inputs_bw = [], []
    for hc in host:
        c = hc.counters
        resid = R.residual({k: c[k].astype(np.int64) for k in
                            ("lp_spawned", "lp_completed", "lp_failed",
                             "missed_by_preemption", "rq_pending")})
        failed += int((resid != 0).sum())
        for ci, (scen, cong) in enumerate(cells):
            sl = slice(ci * n_seeds, (ci + 1) * n_seeds)
            part = {k: v[sl] for k, v in c.items()}
            ref = R.summarize(part, F, period)
            prog = (R.summarize(part, F, period, dtype=ml_dtypes.bfloat16)
                    if control else hc.out.get(f"{scen}@{cong:g}"))
            s_gap = max(s_gap, float("inf") if prog is None
                        else compare.summary_gap(prog, ref))
        for g in hc.rows:
            for k in R.INT_COUNTERS + R.FLOAT_COUNTERS + ("rq_pending",):
                prog_rows[k].append(c[k][g])
            for k, v in hc.states[g].items():
                prog_rows[k].append(v)
        by_cell = collections.defaultdict(list)
        for g in hc.rows:
            by_cell[g // n_seeds].append(g % n_seeds)
        for ci in sorted(by_cell):
            scen, cong = cells[ci]
            v, bw = R.make_inputs(scen, n_seeds, F, site["n_devices"],
                                  hc.base_seed + ci, cong,
                                  site["congestion_residual"])
            inputs_v.append(v[:, by_cell[ci]])
            inputs_bw.append(bw[:, by_cell[ci]])
    numbers = {"summary_gap": s_gap, "residual_failures": failed,
               "replica_mismatch": float("inf")}
    notes = {"sampled": len(prog_rows["frames"])}
    if inputs_v:
        v = np.concatenate(inputs_v, axis=1)
        bw = np.concatenate(inputs_bw, axis=1)
        t = time.perf_counter()
        ref = R.SiteModel(cell.config).run(v, bw)
        notes["reference_s"] = time.perf_counter() - t
        prog = ({k: np.stack(x) for k, x in prog_rows.items()} if not control
                else R.SiteModel(cell.config, ml_dtypes.bfloat16).run(v, bw))
        share, agree_gap = compare.replica_mismatch(prog, ref)
        numbers["replica_mismatch"] = share
        notes["time_gap_of_agreeing"] = agree_gap
    return numbers, failed, notes


def _memory_peak(devices) -> int | None:
    peaks_ = []
    for d in devices:
        try:
            peaks_.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except (AttributeError, KeyError, TypeError, RuntimeError):
            pass
    return max(peaks_) if peaks_ else None


class Session:
    """One process's hold on the cell's chips: the program, the probe and
    the warmed-up shapes."""

    def __init__(self, cell_name: str, *, bench: Benchmark | None = None,
                 require_tpu: bool = True, log=print):
        self.bench = bench or Benchmark()
        self.cell = self.bench.cell(cell_name)
        self.log = log
        import jax

        self.jax = jax
        devices = jax.devices()
        self.dev = devices[0]
        self.n_devices = len(devices)
        if require_tpu and self.dev.platform != "tpu":
            raise NoChip(f"JAX's first device is {self.dev.platform!r}, "
                         f"not a TPU")
        if len(devices) < self.cell.chips:
            raise NoChip(f"cell {self.cell.name} needs {self.cell.chips} "
                         f"chips; JAX sees {len(devices)}")
        self.used = devices[:self.cell.chips]
        # a test on the CPU leaves the process's compile cache alone
        cache = use_compile_cache(jax) if require_tpu else "not set"
        log(f"device {self.dev.platform} {self.dev.device_kind!r} "
            f"x{len(devices)}; compile cache {cache}")
        if str(ROOT / "src") not in sys.path:
            sys.path.insert(0, str(ROOT / "src"))
        import repro.fleet.sweep as sweep_mod

        check_program_constants(self.cell.config)
        self.sweep = sweep_mod
        self.probe = Probe(sweep_mod)
        self.total = len(grid(self.cell)) * self.cell.traffic["n_seeds"]
        self.batch = min(self.cell.traffic["batch_size"], self.total)
        self._stack = contextlib.ExitStack()
        self._stack.enter_context(self.probe.installed())

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._stack.close()

    def warm_up(self, seed: int) -> None:
        """One call with the cell's shapes (call 0 of the seed)."""
        out = self.sweep.run_sweep(sweep_config(self.cell, call_seed(seed, 0)))
        if out["_sweep"]["total_replicas"] != self.total:
            raise RuntimeError(f"sweep made {out['_sweep']} replicas, the "
                               f"cell asks for {self.total}")

    def window(self, seed: int, seconds: float, traced: bool) -> Window:
        """Whole calls until ``seconds`` have passed."""
        jax, probe = self.jax, self.probe
        probe.seconds.clear()
        probe.counts.clear()
        log_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
        tracer = (jax.profiler.trace(log_dir) if traced
                  else contextlib.nullcontext())
        calls: list[Call] = []
        probe.counting = True
        try:
            with tracer, jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
                t0 = time.perf_counter()
                while True:
                    call = Call(call_seed(seed, len(calls) + 1))
                    probe.call = call
                    with jax.profiler.TraceAnnotation("bench/sweep"):
                        try:
                            call.out = self.sweep.run_sweep(
                                sweep_config(self.cell, call.base_seed))
                        except Exception as e:  # noqa: BLE001 — counted as failed replicas
                            call.error = f"{type(e).__name__}: {e}"
                            self.log(f"call {len(calls)} raised {call.error}")
                    calls.append(call)
                    if time.perf_counter() - t0 >= seconds:
                        break
                elapsed = time.perf_counter() - t0
        finally:
            probe.counting = False
            probe.call = None
        w = Window(calls, elapsed, dict(probe.seconds), dict(probe.counts))
        if traced:
            t = time.perf_counter()
            w.trace = T.Reduced(T.load(log_dir))
            shutil.rmtree(log_dir, ignore_errors=True)
            self.log(f"trace read in {time.perf_counter() - t:.3f} s")
        return w

    def describe(self, w: Window) -> None:
        cell, F = self.cell, self.cell.config["site"]["n_frames"]
        n_ok = sum(c.error is None for c in w.calls)
        seg = getattr(sweep_config(cell, 0).fleet_params(), "segment_frames",
                      0)
        steps = F if not seg else -(-F // min(seg, F)) * min(seg, F)
        self.log(f"window: {len(w.calls)} calls ({n_ok} ok) of {self.total} "
                 f"replicas x {F} frames in {w.elapsed_s:.6f} s; compiles in "
                 f"window {w.counts.get('compiles', 0)}, traces in window "
                 f"{w.counts.get('traces', 0)}")
        self.log(f"steps per batch: {steps} executed, {F} real, {steps - F} "
                 f"padded; batches completed "
                 f"{n_ok * -(-self.total // self.batch)}")
        phases = dict(w.host_seconds)
        phases["other"] = w.elapsed_s - sum(phases.values())
        self.log("host seconds by phase: " + json.dumps(
            {k: round(v, 6) for k, v in sorted(phases.items())}))


def run_cell(cell_name: str, seed: int, seconds: float, traced: bool,
             t_start: float, *, bench: Benchmark | None = None,
             require_tpu: bool = True, log=print) -> dict:
    """One run; returns the result object (the last line's JSON)."""
    with Session(cell_name, bench=bench, require_tpu=require_tpu,
                 log=log) as s:
        s.warm_up(seed)
        setup_s = time.time() - t_start
        w = s.window(seed, seconds, traced)
        mem = _memory_peak(s.used)
        s.describe(w)
        host = collect(s.cell, w.calls, seed)
    n_failed_calls = sum(c.error is not None for c in w.calls)
    numbers, failed, notes = check(s.cell, host, n_failed_calls)
    correct, checks = compare.judge(numbers)
    log("check: " + json.dumps(notes))
    n_ok = len(w.calls) - n_failed_calls
    F = s.cell.config["site"]["n_frames"]
    ctx = Context(
        cell=s.cell, chips=s.cell.chips, device_kind=s.dev.device_kind,
        setup_s=setup_s, elapsed_s=w.elapsed_s, calls=n_ok,
        replicas_per_call=s.total, real_site_ticks=n_ok * s.total * F,
        host_seconds=w.host_seconds, trace=w.trace,
    )
    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in s.bench.metrics_for(s.cell.name, kind):
        value = m.reader.read(ctx)
        if value is not None:
            metrics[m.name] = {"value": float(value), "unit": m.unit}
    device = {"platform": s.dev.platform, "kind": s.dev.device_kind,
              "count": s.n_devices, "memory_peak_bytes": mem}
    result = {"correct": bool(correct),
              "attempted": len(w.calls) * s.total, "failed": failed,
              "metrics": metrics, "device": device}
    if w.trace is not None:
        device["busy_s"] = w.trace.mean_busy_s()
        device["window_s"] = w.trace.window_s
        result["breakdown"] = {"device_ops": w.trace.top_ops(10),
                               "idle_gaps": w.trace.idle_gaps(10)}
    result["checks"] = checks
    return result
