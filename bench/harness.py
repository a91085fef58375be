"""One run of one cell: data from the seed, fit, warm-up, the measured
window through the program's serve loop, the metrics, and the check.

A cell is found by name alone. ``BENCHMARK.json`` names its configuration
and its traffic mix; the configuration's file names its generator
(``bench/generators/<name>.py``), its plain reference (``reference``, a
path in the checkout) and what ``LassoSession.fit`` takes (``session``:
solver settings, and where given a ``mesh`` and ``groups``); the mix is
``bench/mixes/<traffic>.json``;
the check's limits are ``bench/checks/<cell>.json``; each per-layer metric
is read by ``bench/layer_metrics/<metric>.py``. Adding any of them takes new
files and new entries, and no edit here.

The window drives ``ServeLoop.run`` -> ``SessionExecutor.dispatch`` ->
``LassoSession.path``. Latency is timed from each query's due time, so a
stall of the loop shows on every query behind it.
"""

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import time
from pathlib import Path

import numpy as np

from bench import stats, traffic
from bench import trace as trace_mod

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_SECONDS = 10.0        # the traced window of a --trace 1 run, at most
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

# seed streams: each part of a run draws from its own
DICTIONARY, QUERIES, ARRIVALS, SAMPLE = 0, 1, 3, 4


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell needs."""


# ---------------------------------------------------------------------------
# finding a cell's files by name
# ---------------------------------------------------------------------------

def _json(path: Path):
    return json.loads(Path(path).read_text())


def load_module(path: Path):
    """Import one file of the benchmark by its path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + path.stem.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    mix: dict
    checks: dict
    end_to_end: list
    per_layer: list
    root: Path

    @property
    def bench(self) -> Path:
        return self.root / "bench"

    def generator(self):
        return load_module(self.bench / "generators"
                           / f"{self.config['generator']['name']}.py")

    def layer_metric(self, name: str):
        return load_module(self.bench / "layer_metrics" / f"{name}.py")

    def reference(self):
        """The configuration's own plain reference module: ``certify``
        judges served answers, ``reference_path`` is the control."""
        return load_module(self.root / self.config["reference"])


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = _json(root / "BENCHMARK.json")
    matches = [w for w in spec["workloads"] if w["name"] == name]
    if not matches:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in spec['workloads']]}")
    workload = matches[0]
    entry = next(c for c in spec["configs"] if c["name"] == workload["config"])
    return Cell(
        name=name, workload=workload, config=_json(root / entry["file"]),
        mix=_json(root / "bench" / "mixes" / f"{workload['traffic']}.json"),
        checks=_json(root / "bench" / "checks" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        root=root)


# ---------------------------------------------------------------------------
# seeds
# ---------------------------------------------------------------------------

def _sequence(seed: int, stream: int):
    return np.random.SeedSequence([int(seed) % 2 ** 64, stream])


def rng(seed: int, stream: int):
    return np.random.default_rng(_sequence(seed, stream))


def jax_key(seed: int, stream: int):
    import jax
    words = _sequence(seed, stream).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


# ---------------------------------------------------------------------------
# what the window records
# ---------------------------------------------------------------------------

class Recorder:
    """Dispatch records, step statistics and compile events of a run; the
    served answers of a sample of queries drawn from the seed."""

    def __init__(self, clock, sample_size: int, sample_rng, eligible):
        self.clock = clock
        self.dispatches = []
        self.compiles = []
        self.sample = []
        self._seen = 0
        self._size = sample_size
        self._rng = sample_rng
        self._eligible = eligible

    def on_compile(self, event, duration_secs, **kwargs):
        if event == BACKEND_COMPILE:
            self.compiles.append(self.clock.now())

    def on_complete(self, ticket):
        """Reservoir sampling over the answers the check may compare; every
        other answer is let go at once, so a run holds O(sample) answers."""
        keep = (ticket.ok and ticket.converged and ticket.result is not None
                and self._eligible(ticket))
        if keep:
            self._seen += 1
            if len(self.sample) < self._size:
                self.sample.append(ticket)
                return
            j = int(self._rng.integers(0, self._seen))
            if j < self._size:
                self.sample[j].result = None
                self.sample[j] = ticket
                return
        ticket.result = None


class RecordingExecutor:
    """The program's executor, with each dispatch's span and step
    statistics recorded (and a host span in the profiler's trace)."""

    def __init__(self, inner, recorder: Recorder):
        self.inner = inner
        self.recorder = recorder

    @property
    def version(self) -> int:
        return self.inner.version

    def dispatch(self, Y, n_live: int, batch_id: int, now: float):
        import jax
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            handle = self.inner.dispatch(Y, n_live, batch_id, now)
        done = self.recorder.clock.now()
        try:
            lanes = handle.result()
        except Exception:            # the loop's isolation path handles it
            lanes = None
        steps = []
        if lanes and lanes[0].result is not None:
            steps = [dataclasses.asdict(s) for s in lanes[0].result.stats]
        self.recorder.dispatches.append({
            "batch_id": batch_id, "t": now, "t_done": done, "n_live": n_live,
            "padded_b": int(np.shape(Y)[0]), "steps": steps})
        return handle


def _clock():
    import jax
    from repro.launch import serve_loop as sl

    class Clock(sl.WallClock):
        def advance_to(self, t: float) -> None:
            with jax.profiler.TraceAnnotation("bench.wait"):
                super().advance_to(t)

    return Clock()


class WindowArrivals:
    """The mix's arrival script; where the mix says so, the window's end
    closes the source and no further query is offered."""

    def __init__(self, script, clock, t_end: float, close_at_end: bool):
        from repro.launch import serve_loop as sl
        self._inner = sl.ScriptedArrivals(script)
        self._clock = clock
        self._t_end = t_end
        self._close = close_at_end

    def peek_time(self):
        if self._close and self._clock.now() >= self._t_end:
            return None
        return self._inner.peek_time()

    def pop(self, now: float):
        return self._inner.pop(now)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def set_compile_cache(root: Path) -> None:
    """JAX's persistent compile cache at a fixed path in the checkout (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def fit_options(session: dict, devs) -> dict:
    """What the configuration's ``session`` adds to ``LassoSession.fit``:
    ``mesh`` ({"axes": [...], "shape": [...]}) as a mesh over the cell's
    own devices, ``groups`` as an int. An absent key is not passed. A mesh
    that does not hold exactly the cell's chips is an error."""
    options = {}
    if "mesh" in session:
        from jax.sharding import Mesh
        shape = tuple(int(k) for k in session["mesh"]["shape"])
        if math.prod(shape) != len(devs):
            raise ValueError(f"mesh {shape} holds {math.prod(shape)} "
                             f"devices; the cell has {len(devs)} chips")
        options["mesh"] = Mesh(np.array(devs).reshape(shape),
                               tuple(session["mesh"]["axes"]))
    if "groups" in session:
        options["groups"] = int(session["groups"])
    return options


def session_config(session: dict):
    from repro.core import PathConfig, ScreenSpec, SolveSpec
    return PathConfig(
        screen=ScreenSpec(rule=session["rule"]),
        solve=SolveSpec(strategy=session["strategy"], tol=session["tol"],
                        max_iter=session["max_iter"]))


def make_data(cell: Cell, seed: int, n_queries: int):
    """Dictionary and window queries, on the device from the seed, in one
    jitted call each; the queries come back to the host, where the serve
    loop takes them."""
    import jax
    gen = cell.generator()
    params = dict(cell.config["generator"]["params"])
    params.update(cell.mix.get("queries", {}))
    with jax.default_matmul_precision("highest"):
        X, state = jax.jit(lambda k: gen.dictionary(k, params))(
            jax_key(seed, DICTIONARY))
        draw = jax.jit(lambda k, X, s, c: gen.queries(k, params, X, s, c),
                       static_argnums=3)
        Y = np.asarray(draw(jax_key(seed, QUERIES), X, state, n_queries))
    X.block_until_ready()
    return X, Y


def _executor(cell: Cell, session):
    from repro.launch import serve_loop as sl
    grid = cell.mix["grid"]
    return sl.SessionExecutor(session, num_lambdas=grid["num_lambdas"],
                              lo_frac=grid["lo_frac"],
                              hi_frac=grid["hi_frac"])


def _policy(mix: dict):
    from repro.launch import serve_loop as sl
    p = mix["policy"]
    return sl.ServePolicy(b_max=p["b_max"], deadline_s=p["deadline_ms"] / 1e3,
                          queue_cap=p["queue_cap"],
                          max_in_flight=p["max_in_flight"])


def warm_up(executor, mix: dict, script, log) -> None:
    """Serve the window's first ``warmup.batches`` batches once, untimed.

    The program compiles a set of programs for each power-of-two bucket of
    kept features that a lambda step meets: the bucket's column gather, its
    jitted step epilogue, the Lipschitz power iterations and the solver
    loops (``core/path.py``, ``core/solver.py``). Which buckets a window
    meets follows its own queries. A backlog is served in order in full
    batches, so replaying its first batches, more than the window can
    serve, compiles every program the window uses and no other. The replay
    also fills the session's per-bucket cache of Lipschitz eigenvectors,
    which starts each next solve of that bucket, as it would be after any
    served traffic; each run logs what its window compiled. Beyond that
    cache the program keeps nothing of a query between calls, so the
    window's work is that of unseen queries."""
    b = mix["policy"]["b_max"]
    count = min(mix["warmup"]["batches"], len(script) // b)
    for i in range(count):
        batch = np.stack([y for _, y in script[i * b:(i + 1) * b]])
        executor.dispatch(batch, b, -1, 0.0).result()
        if (i + 1) % 16 == 0:
            log(f"warm-up: {i + 1} of {count} batches served")


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, require_tpu: bool = True,
             log=lambda *a: None) -> dict:
    """One run; returns the result line's fields (and the check's readings
    under ``check``)."""
    import jax
    from repro.core import LassoSession
    from repro.launch import serve_loop as sl

    set_compile_cache(cell.root)
    devs = devices(cell.workload["chips"], require_tpu)
    placement = fit_options(cell.config["session"], devs)
    peaks = _peaks(devs[0].device_kind, require_tpu)
    mix = cell.mix
    window = min(seconds, TRACE_SECONDS) if trace else float(seconds)
    offsets, close = traffic.arrival_offsets(mix["arrivals"], window,
                                             rng(seed, ARRIVALS))
    X, Y = make_data(cell, seed, len(offsets))
    script = [(float(o), Y[i]) for i, o in enumerate(offsets)]
    log(f"data: X {X.shape}, {len(Y)} window queries")

    session = LassoSession.fit(X, config=session_config(
        cell.config["session"]), **placement)
    executor = _executor(cell, session)
    warm_up(executor, mix, script, log)
    log(f"fit and warm-up done at {time.perf_counter() - t_process:.3f} s")

    clock = _clock()
    eligible_end = [math.inf]
    recorder = Recorder(
        clock, cell.checks["sample"], rng(seed, SAMPLE),
        (lambda t: t.t_complete <= eligible_end[0]) if close
        else (lambda t: True))
    jax.monitoring.register_event_duration_secs_listener(recorder.on_compile)
    trace_dir = cell.root / ".bench_trace" / cell.name
    try:
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=options)
        t0 = clock.now()
        t_end = t0 + window
        if close:
            eligible_end[0] = t_end
        arrivals = WindowArrivals([(t0 + t, y) for t, y in script], clock,
                                  t_end, close)
        loop = sl.ServeLoop(arrivals, RecordingExecutor(executor, recorder),
                            policy=_policy(mix), clock=clock,
                            on_complete=recorder.on_complete)
        report = loop.run()
        t_stop = clock.now()
        if trace:
            jax.profiler.stop_trace()
    finally:
        jax.monitoring.unregister_event_duration_listener(recorder.on_compile)
    log(f"window {window:.1f} s closed; loop ended {t_stop - t_end:.3f} s "
        f"after it")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    tickets = ticket_rows(report.tickets)
    record = {"cell": cell.name, "config": cell.config, "mix": mix,
              "window": [t0, t_end], "close_at_end": close,
              "tickets": tickets, "offered": len(offsets),
              "dispatches": recorder.dispatches,
              "compiles_in_window": sum(t0 <= c <= t_end
                                        for c in recorder.compiles),
              "device": None}
    steps = [s for d in recorder.dispatches for s in d["steps"]]
    log(f"window: {record['compiles_in_window']} programs compiled or "
        f"loaded; {len(recorder.dispatches)} dispatches; kept at most "
        f"{max((s['n_kept'] for s in steps), default=0)} features, bucket "
        f"{max((s['bucket'] for s in steps), default=0)}")
    result = {"attempted": len(tickets),
              "failed": sum(not (t["ok"] and t["converged"])
                            for t in tickets),
              "device": device}
    if trace:
        events = trace_mod.read_xplane(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        reduced = trace_mod.reduce(events)
        reduced["window_s"] = t_stop - t0
        for name, sec in reduced["device_ops"]:
            log(f"device op {sec:.6f} s: {name}")
        reduced["peaks"] = peaks
        record["device"] = reduced
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["metrics"] = layer_metrics(cell, record)
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        result["metrics"] = end_to_end(cell, record, t0 - t_process)

    # the check, once the program's state is let go
    sample = [(t.qid, t.result) for t in recorder.sample]
    X64 = np.asarray(X, np.float64)
    del session, executor, loop, report, recorder, X
    gc.collect()
    result["check"] = check(cell, X64, Y, sample)
    result["check"]["unanswered"] = {"value": unanswered(record), "limit": 0}
    return result


def _peaks(kind: str, require_tpu: bool):
    from bench import roofline
    try:
        return roofline.peaks(kind)
    except KeyError:
        if require_tpu:
            raise
        return None


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def ticket_rows(tickets) -> list:
    """The serve loop's tickets as plain rows; ``due`` is the time the
    arrival script set, so a query that waited upstream of a full queue
    (``t_admit > t_arrive``) is timed from when it was due."""
    return [{"qid": t.qid, "due": t.t_arrive, "admit": t.t_admit,
             "dispatch": t.t_dispatch, "complete": t.t_complete,
             "ok": t.ok, "converged": bool(t.converged)} for t in tickets]


def latencies_ms(record: dict) -> list:
    """Completion minus due time of every query due in the window; one that
    failed or did not converge is infinitely late."""
    t0, t_end = record["window"]
    return [(t["complete"] - t["due"]) * 1e3
            if t["ok"] and t["converged"] and t["complete"] is not None
            else math.inf
            for t in record["tickets"] if t0 <= t["due"] < t_end]


def end_to_end(cell: Cell, record: dict, setup_s: float) -> dict:
    t0, t_end = record["window"]
    lat = latencies_ms(record)
    done = sum(t["ok"] and t["converged"] and t["complete"] is not None
               and t["complete"] <= t_end for t in record["tickets"])
    values = {
        "latency_p95_ms": lambda: stats.percentile(lat, 95.0),
        "latency_p50_ms": lambda: stats.percentile(lat, 50.0),
        "qps": lambda: done / (t_end - t0),
        "setup_s": lambda: setup_s,
    }
    return {m["name"]: {"value": values[m["name"]](), "unit": m["unit"]}
            for m in cell.end_to_end}


def layer_metrics(cell: Cell, record: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        value = cell.layer_metric(m["name"]).read(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def check(cell: Cell, X64, Y, sample) -> dict:
    """Certify every sampled answer against the configuration's plain
    reference; the worst reading of each number beside its limit."""
    worst = cell.reference().certify(
        X64, Y[[qid for qid, _ in sample]],
        [(r.lambdas, r.betas, r.masks) for _, r in sample], cell.mix["grid"],
        cell.config["session"])
    limits = cell.checks["limits"]
    out = {k: {"value": worst[k], "limit": limits[k]} for k in worst}
    out["compared"] = {"value": len(sample), "at_least": 1}
    return out


def unanswered(record: dict) -> int:
    """Queries the window took up that never completed."""
    return sum(t["complete"] is None for t in record["tickets"])


def is_correct(checked: dict) -> bool:
    """Every number at or under its limit, and at least one answer
    compared."""
    ok = checked["compared"]["value"] >= checked["compared"]["at_least"]
    for k, v in checked.items():
        if k != "compared":
            ok &= v["value"] is not None and v["value"] <= v["limit"]
    return bool(ok)


def check_lines(checked: dict) -> list:
    lines = []
    for k, v in checked.items():
        if "limit" in v:
            lines.append(f"check {k} {v['value']!r} limit {v['limit']!r}")
        else:
            lines.append(f"check {k} {v['value']!r} at least "
                         f"{v['at_least']!r}")
    return lines
