"""One run of one benchmark cell: set-up, the measured window, the check.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.
Everything that belongs to one of them is found by name:

  * ``bench/configs/<config>.json``: the matrix (Table-1 statistics and
    the models that build it), how it is registered, and its limits;
  * ``bench/traffic/<mix>.json``: the driver kind and its parameters;
  * ``bench/drivers/<kind>.py``: ``size(session, i)`` (products that
    request ``i`` carries) and ``request(session, i)`` (send it and wait);
  * ``bench/metrics/<metric>.py``: ``read(ctx)``, a per-layer metric or
    ``None`` where it finds nothing to read.

A run builds the matrix on the host, the client's vectors on the device,
registers the matrix in an ``SpMVService`` with a ``KernelTuner``, warms
the cell's own op and shapes, and drives the traffic for ``seconds``.
Then it compares a sample of the served products, drawn from the seed,
with the float64 host reference (``bench/reference.py``) and reads the
guard ladders: a request that raised or was served below the tuned rung
counts as failed, and a registration that degraded fails the run.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: the persistent compilation cache: one fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: products of a run compared with the reference, drawn from the seed
SAMPLE = 16
#: seconds of the window that a ``--trace 1`` run profiles
TRACE_SECONDS = 5.0


def read_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at :data:`CACHE_DIR`, with every
    program written to it however short its compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return CACHE_DIR


@dataclass
class Cell:
    name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @staticmethod
    def load(name: str, benchmark: Optional[Dict[str, Any]] = None
             ) -> "Cell":
        bm = benchmark or read_json(ROOT, "BENCHMARK.json")
        wl = next((w for w in bm["workloads"] if w["name"] == name), None)
        if wl is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")

        def here(m):
            return name in m.get("workloads", [name])
        e2e = [m for m in bm["end_to_end"] if here(m)]
        moved = {m["name"] for m in e2e}
        per = [m for m in bm["per_layer"] if here(m) and m["moves"] in moved]
        return Cell(name=name,
                    config=read_json(BENCH, "configs", wl["config"] + ".json"),
                    traffic=read_json(BENCH, "traffic",
                                      wl["traffic"] + ".json"),
                    end_to_end=e2e, per_layer=per)


class Reservoir:
    """A uniform sample of ``k`` items from a stream, drawn from ``rng``."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.seen, self.items = k, rng, 0, []

    def offer(self, item: Any) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            r = int(self.rng.integers(0, self.seen))
            if r < self.k:
                self.items[r] = item


@dataclass
class Session:
    """What a driver sees: the service, the key, the client's vectors,
    the traffic's parameters and a generator drawn from the seed.  Drivers
    report each product through :meth:`done`."""
    svc: Any
    key: str
    xs: List[Any]
    traffic: Dict[str, Any]
    rng: np.random.Generator
    sample: Reservoir
    recording: bool = False
    latencies: List[float] = field(default_factory=list)
    state: Dict[str, Any] = field(default_factory=dict)   # the driver's own

    def done(self, t_sent: float, t_ready: float, y: Any, j: int) -> None:
        """Product ``y`` of vector ``xs[j]``, sent at ``t_sent`` and ready
        at ``t_ready``."""
        if self.recording:
            self.latencies.append(t_ready - t_sent)
            self.sample.offer((y, j))


def _key_of(seed: int) -> int:
    """A 31-bit PRNG key for JAX from any whole-number seed."""
    return int(np.random.default_rng(seed).integers(0, 2**31 - 1))


def make_vectors(n: int, count: int, seed: int) -> List[Any]:
    """``count`` standard-normal float32 vectors of length ``n``, made on
    the device in one jitted call."""
    import jax

    @jax.jit
    def make(key):
        xs = jax.random.normal(key, (count, n), "float32")
        return tuple(xs[i] for i in range(count))

    return list(jax.block_until_ready(make(jax.random.PRNGKey(_key_of(seed)))))


def to_program_csr(host):
    from repro.core.formats import CSR
    return CSR(data=host.data, cols=host.cols, indptr=host.indptr,
               shape=(host.n_rows, host.n_cols), nnz=host.nnz)


def _compile_counter() -> Tuple[Callable[[], int], Callable[[], None]]:
    """Count JAX compilations while armed (``arm``), read with ``count``."""
    import jax.monitoring as mon
    state = {"armed": False, "n": 0}

    def listener(event: str, duration: float, **_: Any) -> None:
        if state["armed"] and event.endswith("backend_compile_duration"):
            state["n"] += 1

    mon.register_event_duration_secs_listener(listener)

    def arm() -> None:
        state["armed"] = True
    return (lambda: state["n"]), arm


@dataclass
class Prepared:
    """A cell after set-up: the matrix registered and warmed."""
    cell: Cell
    host: Any                   # table1.HostCSR
    svc: Any                    # SpMVService
    entry: Any                  # its MatrixEntry for the cell's matrix
    driver: Any
    sess: Session
    sink: Any                   # repro.obs spans and events of registration
    register_s: float
    marks: Dict[str, float]
    next_request: int = 0

    @property
    def guard(self):
        return self.entry.guards[self.driver.OP]

    def degraded(self) -> int:
        plan = self.entry.plan
        return int(plan is None or getattr(plan, "rule", "") == "degraded"
                   or getattr(plan, "tier", "") != "kernel"
                   or bool(self.sink.named("service.register_degraded")))

    def new_vectors(self, seed: int) -> None:
        """The client's vectors, generator and sample, drawn from ``seed``."""
        s = self.sess
        s.xs = make_vectors(self.host.n_cols, int(s.traffic["pool"]), seed)
        s.rng = np.random.default_rng(seed)
        s.sample = Reservoir(SAMPLE, np.random.default_rng([seed, 1]))
        s.state.clear()


def prepare(cell: Cell, seed: int) -> Prepared:
    """Set-up: the matrix on the host, the vectors on the device, the
    registration with its first product (``register_s``) and the warm-up
    requests of the cell's traffic."""
    import repro.obs as obs
    from repro.core.autotune import TuningDB
    from repro.core.kernel_tune import KernelTuner
    from repro.serve.spmv_service import SpMVService

    from bench import table1

    cfg, traffic = cell.config, cell.traffic
    driver = load_module("drivers", traffic["driver"])
    reg = cfg["register"]
    marks = {"start": time.perf_counter()}
    host = table1.synthesize(cfg["matrix"], int(cfg["matrix"]["seed"]))
    csr = to_program_csr(host)
    marks["synthesized"] = time.perf_counter()
    sink = obs.InMemorySink()
    svc = SpMVService(
        tuner=KernelTuner(db=TuningDB(machine="bench", c=1.0, records=[],
                                      d_star={}),
                          max_candidates=reg["max_candidates"]),
        max_batch=int(traffic.get("max_batch", 32)))
    sess = Session(svc=svc, key=cfg["name"], xs=[], traffic=traffic,
                   rng=np.random.default_rng(seed),
                   sample=Reservoir(SAMPLE, np.random.default_rng(seed)))
    p = Prepared(cell=cell, host=host, svc=svc, entry=None, driver=driver,
                 sess=sess, sink=sink, register_s=0.0, marks=marks)
    p.new_vectors(seed)
    marks["vectors"] = time.perf_counter()
    tel = obs.enable(sink=sink)
    try:
        t0 = time.perf_counter()
        p.entry = svc.register(cfg["name"], csr, batch=int(traffic["batch"]),
                               expected_iterations=reg["expected_iterations"],
                               measure_baseline=reg["measure_baseline"])
        driver.request(sess, 0)
        p.register_s = time.perf_counter() - t0
    finally:
        tel.sinks.remove(sink)
        obs.disable()
    marks["registered"] = time.perf_counter()
    for i in range(1, 1 + int(traffic["warmup"])):
        driver.request(sess, i)
    p.next_request = 1 + int(traffic["warmup"])
    return p


@dataclass
class Window:
    seconds: float = 0.0
    requests: int = 0
    attempted: int = 0
    raised: int = 0
    failed: int = 0
    least_s: float = 0.0        # least time of the products (with peaks)
    compiles: int = 0
    latencies: List[float] = field(default_factory=list)
    samples: List[Tuple[np.ndarray, int]] = field(default_factory=list)
    errors: List[str] = field(default_factory=list)


def drive(p: Prepared, seconds: float, pk: Optional[Dict[str, Any]] = None,
          trace_dir: Optional[str] = None) -> Window:
    """The measured window: the cell's requests, back to back, for
    ``seconds``; then the served sample, copied to the host."""
    from bench.devtrace import capture
    from bench.work import least_seconds

    count_compiles, arm = _compile_counter()
    sess, host, w = p.sess, p.host, Window()
    fallbacks_before = p.guard.snapshot()["fallback_calls"]
    sess.latencies = []
    arm()
    sess.recording = True
    t_start = time.perf_counter()
    with capture(trace_dir) if trace_dir else contextlib.nullcontext():
        deadline = t_start + seconds
        while time.perf_counter() < deadline:
            i = p.next_request
            p.next_request += 1
            k = p.driver.size(sess, i)
            w.attempted += k
            w.requests += 1
            try:
                p.driver.request(sess, i)
                if pk is not None:
                    w.least_s += least_seconds(host.n_rows, host.n_cols,
                                               host.nnz, k, pk)
            except Exception as e:  # noqa: BLE001 — counted and reported
                w.raised += k
                w.errors.append(repr(e))
    w.seconds = time.perf_counter() - t_start
    sess.recording = False
    w.compiles = count_compiles()
    fallbacks = p.guard.snapshot()["fallback_calls"] - fallbacks_before
    w.failed = w.raised + fallbacks * p.driver.per_call(sess)
    w.latencies = sorted(sess.latencies)
    w.samples = [(np.asarray(y), j) for y, j in sess.sample.items]
    return w


def row_err(p: Prepared, w: Window, ref=None) -> float:
    """The worst row error of the window's sampled products against the
    float64 host reference (infinity when nothing was sampled)."""
    from bench.reference import HostReference
    ref = ref or HostReference(p.host)
    return max((ref.row_err(y, np.asarray(p.sess.xs[j])) for y, j in
                w.samples), default=float("inf"))


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_process: float) -> Dict[str, Any]:
    """One run of ``cell``; returns the result line's object.  The caller
    has made sure JAX sees the chips the cell needs."""
    import jax
    from bench.devtrace import load as load_trace
    from bench.peaks import peaks

    dev = jax.devices()[0]
    pk = peaks(dev.device_kind) if dev.platform == "tpu" else None
    p = prepare(cell, seed)
    setup_s = time.perf_counter() - t_process
    tmp = tempfile.TemporaryDirectory() if trace else None
    w = drive(p, min(seconds, TRACE_SECONDS) if trace else seconds, pk,
              tmp.name if trace else None)

    # -- after the window: guards, memory, the trace, the reference ----------
    fallbacks = p.guard.snapshot()["fallback_calls"]
    degraded = p.degraded()
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    tr = None
    if trace:
        tr = load_trace(tmp.name)
        tmp.cleanup()
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
    err = row_err(p, w)
    limit = cell.config["limits"]["row_err"]
    checks = {
        "row_err": {"value": err, "limit": limit},
        "compared": {"value": len(w.samples), "limit": 1},
        "fallbacks": {"value": fallbacks, "limit": 0},
        "raised": {"value": w.raised, "limit": 0},
        "degraded": {"value": degraded, "limit": 0},
    }
    correct = (err <= limit and len(w.samples) >= 1 and fallbacks == 0
               and w.raised == 0 and degraded == 0)

    out: Dict[str, Any] = {"correct": bool(correct),
                           "attempted": w.attempted, "failed": w.failed}
    if trace:
        ctx = {"trace": tr, "spans": p.sink.spans(), "least_s": w.least_s,
               "peaks": pk}
        metrics = {}
        for m in cell.per_layer:
            v = load_module("metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = {"device_ops": tr.top_ops(),
                            "idle_gaps": tr.idle_gaps()}
    else:
        e2e = {
            "products_per_s": (w.attempted - w.raised) / w.seconds,
            "p95_ms": 1e3 * _quantile(w.latencies, 0.95),
            "register_s": p.register_s,
            "setup_s": setup_s,
        }
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
        out["device"] = device
    mk = p.marks
    out["setup"] = {"start_s": mk["start"] - t_process,
                    "synthesize_s": mk["synthesized"] - mk["start"],
                    "vectors_s": mk["vectors"] - mk["synthesized"],
                    "register_s": p.register_s,
                    "warmup_s": t_process + setup_s - mk["registered"]}
    out["window"] = {"seconds": w.seconds, "requests": w.requests,
                     "products": w.attempted, "compiles": w.compiles,
                     "errors": w.errors[:3]}
    out["checks"] = checks
    return out


def _quantile(sorted_values: List[float], q: float) -> float:
    """The ``q`` quantile, nearest rank (a value that was observed)."""
    if not sorted_values:
        return float("nan")
    idx = max(0, int(np.ceil(q * len(sorted_values))) - 1)
    return sorted_values[idx]


def print_result(out: Dict[str, Any]) -> None:
    """The checks as the last lines of standard error, the result as the
    last line of standard output."""
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
