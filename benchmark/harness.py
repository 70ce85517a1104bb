"""The benchmark harness: one cell, one seed, one process.

``BENCHMARK.json`` at the checkout's root names the cells. A cell is a
configuration (``benchmark/configs/<config>.json``: the deployment's
sizes, error profiles and racon's settings, its ``racon`` block) under
a traffic mix (``benchmark/traffic/<traffic>.json``: the input formats
and how jobs arrive); its correctness limits are
``benchmark/limits/<cell>.json``, and each per-layer metric is read by
``benchmark/metrics/<metric>.py``. The harness finds all of them by the
names in ``BENCHMARK.json``, so a new cell, configuration, mix or
metric is a new file and an entry there, never an edit here. Both
kinds of racon job are data:

- contig polishing (kC): a mix's ``overlaps`` is ``paf`` or ``sam``,
  read-to-draft overlaps, and the program prints one record per contig
  of the configuration;
- read self-correction (kF): the configuration's ``racon`` block says
  ``"fragment_correction": true`` (the CLI's ``-f``), the mix's
  ``overlaps`` is ``ava_paf`` (all-vs-all overlaps among the reads,
  every read a target, ``benchmark/gen/ava.py``), and the program
  prints each corrected read as ``<read>r``; the contigs are the
  stretches of genome the reads are drawn from.

A run (``run``):

1. set-up: find the chip, generate warm-up jobs outside the timed
   stream and polish them until two in a row need no new program (the
   programs the cell's jobs use are then compiled or read from the
   compile cache), then generate the timed jobs (``timed_job``: drawn
   from the seed, or a fixed pool in an order the seed draws);
2. the window: polish the timed jobs back to back through
   ``racon_tpu.cli.main([... "--backend", "jax" ...])`` in this
   process, until ``--seconds`` have passed; the job running then is
   finished and counted. With ``--trace 1`` the window is the first
   timed job alone, run under the JAX profiler with the program's spans
   bridged into it (collecting the profile after a job outlasts a
   window: about 130 s on four chips); under a pool, warm-up is that
   job polished once;
3. after the window: read the device's peak memory, compare every timed
   job's polished records with their truths, found by the names the
   program prints (``benchmark/reference.py``), and print the result
   line.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import io
import json
import math
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

from benchmark import reference
from benchmark.gen import genome

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
# Generated jobs, job logs and traces: inside the checkout, gitignored.
WORK_DIR = os.path.join(ROOT, ".bench_work")
BACKEND = "jax"
MAX_WARMUP = 6
# The key that a pooled traffic mix draws its jobs from, whatever the seed.
POOL_KEY = 0


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell needs."""


# ------------------------------------------------------------------ layout

def load_spec(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def find_cell(spec: Dict, name: str, root: str = ROOT) -> Dict:
    """Everything one cell needs, found by the names in the spec."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bench = os.path.join(root, "benchmark")
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name])]
    end_to_end = [m for m in spec["end_to_end"]
                  if name in m.get("workloads", [name])]
    return {
        "name": name, "chips": int(w["chips"]),
        "config": _load_json(os.path.join(root, cfg_entry["file"])),
        "traffic": _load_json(os.path.join(bench, "traffic",
                                           w["traffic"] + ".json")),
        "limits": _load_json(os.path.join(bench, "limits", name + ".json")),
        "end_to_end": end_to_end, "per_layer": per_layer,
        "readers": {m["name"]: os.path.join(bench, "metrics",
                                            m["name"] + ".py")
                    for m in per_layer},
    }


def load_reader(path: str) -> Callable[[Dict], Optional[float]]:
    """The ``read(ctx)`` function of one per-layer metric's file."""
    name = "benchmark_metric_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def list_cells(root: str = ROOT) -> List[Dict]:
    """Each cell with its configuration, traffic and per-layer metrics,
    every file resolved: what ``run.py --list`` prints."""
    spec = load_spec(root)
    out = []
    for w in spec["workloads"]:
        c = find_cell(spec, w["name"], root)
        for path in c["readers"].values():
            load_reader(path)
        out.append({"workload": w["name"], "config": w["config"],
                    "traffic": w["traffic"], "chips": c["chips"],
                    "end_to_end": [m["name"] for m in c["end_to_end"]],
                    "per_layer": sorted(c["readers"])})
    return out


# ------------------------------------------------------------------ device

def require_chips(n: int) -> Dict:
    """The device as JAX reports it; raises :class:`NoChip` when JAX
    runs on the host's CPU or sees fewer than ``n`` devices."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip("JAX finds no accelerator (platform 'cpu')")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips; JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n}


def chip_argv(n: int) -> List[str]:
    """The CLI's arguments that put a run on the cell's ``n`` chips: none
    for one chip (the CLI's single-device path), else ``--dp n``, which
    shards the consensus chunks over a ``dp`` mesh of the first ``n``
    devices."""
    return ["--dp", str(n)] if n > 1 else []


def memory_peak_bytes(n: int) -> int:
    """Peak bytes in use on the fullest of the first ``n`` devices (0
    where the backend keeps no statistics)."""
    import jax
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.devices()[:n]]
    return max(peaks, default=0)


class CompileCounter:
    """Counts, per phase of the run (``set-up``, ``window``), the
    executables JAX builds or loads: every backend compile request, how
    many of them the persistent cache answered, and their seconds."""

    def __init__(self):
        import jax.monitoring as mon
        self.phase = "setup"
        self.counts = {p: {"compile_requests": 0, "compile_cache_hits": 0,
                           "compile_s": 0.0} for p in ("setup", "window")}
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.counts[self.phase]["compile_requests"] += 1
            self.counts[self.phase]["compile_s"] += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.counts[self.phase]["compile_cache_hits"] += 1


# -------------------------------------------------------------------- jobs

def cli_argv(cell: Dict, paths: List[str]) -> List[str]:
    """The CLI's arguments for a job of the cell: racon's settings from
    the configuration's ``racon`` block, and ``-f`` (read
    self-correction, every read a target) where it says
    ``"fragment_correction": true``."""
    r = cell["config"]["racon"]
    fragment = ["-f"] if r.get("fragment_correction") else []
    return ["--backend", BACKEND, *chip_argv(cell["chips"]),
            "-w", str(r["window_length"]), "-q", str(r["quality_threshold"]),
            "-e", str(r["error_threshold"]), "-m", str(r["match"]),
            "-x", str(r["mismatch"]), "-g", str(r["gap"]), *fragment,
            *paths]


@contextlib.contextmanager
def _routed(out_path: str, log_path: str):
    """Route the CLI's standard output (its FASTA) and standard error
    (its log) to files: file descriptors 1 and 2, for native code and
    JAX, and ``sys.stdout``/``sys.stderr`` over them, for Python code
    (whatever stream objects the caller had installed)."""
    sys.stdout.flush()
    sys.stderr.flush()
    saved = os.dup(1), os.dup(2)
    saved_py = sys.stdout, sys.stderr
    fds = (os.open(out_path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644),
           os.open(log_path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644))
    os.dup2(fds[0], 1)
    os.dup2(fds[1], 2)
    for fd in fds:
        os.close(fd)
    sys.stdout = io.TextIOWrapper(io.FileIO(1, "w", closefd=False),
                                  write_through=True)
    sys.stderr = io.TextIOWrapper(io.FileIO(2, "w", closefd=False),
                                  write_through=True)
    try:
        yield
    finally:
        sys.stdout.flush()
        sys.stderr.flush()
        sys.stdout, sys.stderr = saved_py
        os.dup2(saved[0], 1)
        os.dup2(saved[1], 2)
        for fd in saved:
            os.close(fd)


def _tail(path: str, n: int = 1500) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, 2)
            f.seek(max(f.tell() - n, 0))
            return f.read().decode(errors="replace")
    except OSError:
        return ""


def polish(cell: Dict, job: Dict, extra: List[str] = ()) -> Dict:
    """Polish one generated job through ``racon_tpu.cli.main`` in this
    process, on the cell's chips. Returns its wall seconds, the metrics
    registry's snapshot for this job alone, its polished contigs (None
    when the CLI failed or its output is malformed) and why it
    failed."""
    from racon_tpu import cli
    from racon_tpu.obs import metrics as obs_metrics
    out_path = os.path.join(job["dir"], "polished.fasta")
    log_path = os.path.join(job["dir"], "polish.log")
    obs_metrics.reset()
    why = None
    t0 = time.perf_counter()
    with _routed(out_path, log_path):
        try:
            rc = cli.main([*extra, *cli_argv(cell, job["paths"])])
        except Exception as exc:    # one job's fault is counted, not fatal
            rc, why = None, f"{type(exc).__name__}: {exc}"
    wall = time.perf_counter() - t0
    reg = obs_metrics.registry().snapshot()
    contigs = None
    if why is None and rc != 0:
        why = f"the CLI returned {rc}"
    if why is None:
        try:
            with open(out_path, "rb") as f:
                contigs = reference.parse_fasta(f.read())
        except (OSError, ValueError) as exc:
            why = f"malformed output: {exc}"
    if why is None and int(reg.get("res_degraded_windows", 0)) > 0:
        why = f"{int(reg['res_degraded_windows'])} window(s) degraded"
    if why is not None:
        print(f"benchmark: job {os.path.basename(job['dir'])} failed: {why}"
              f"\n{_tail(log_path)}", file=sys.stderr)
    return {"wall_s": wall, "registry": reg, "contigs": contigs,
            "failed": why}


def make_job(cell: Dict, key: int, stream: int, index: int,
             dirname: str) -> Dict:
    """Job ``index`` of ``stream`` drawn from ``key``, written to the
    run's directory ``dirname``: its paths, target bases, the truths of
    the records due back, keyed by the names the program prints, and,
    where the program may drop some records (read self-correction), the
    truths of those under ``optional``."""
    d = os.path.join(WORK_DIR, cell["name"], dirname)
    return {"dir": d, **genome.make_job(cell["config"], cell["traffic"],
                                        genome.job_rng(key, stream, index),
                                        d)}


def warmup_job(cell: Dict, seed: int, k: int) -> Dict:
    """The ``k``-th warm-up job of a mix without a pool: drawn from the
    seed, in a stream of its own."""
    return make_job(cell, seed, genome.WARMUP_STREAM, k, f"warmup{k}")


def timed_job(cell: Dict, seed: int, i: int) -> Dict:
    """The ``i``-th timed job of ``seed``.

    A traffic mix that names ``pool_jobs`` (P) polishes a fixed pool of
    P jobs drawn from ``POOL_KEY``, in an order the seed draws, so that
    every seed does the same work and needs the same programs (the
    scheduler's shapes follow the bases). Without it each job is drawn
    from the seed."""
    pool = cell["traffic"].get("pool_jobs")
    if not pool:
        return make_job(cell, seed, genome.TIMED_STREAM, i, f"job{i}")
    order = genome.job_rng(seed, genome.ORDER_STREAM, 0).permutation(pool)
    return make_job(cell, POOL_KEY, genome.TIMED_STREAM,
                    int(order[i % pool]), f"job{i}")


def jobs_to_ready(seconds: float, pace_s: List[float]) -> int:
    """Timed jobs to have ready: a quarter more than a window of
    ``seconds`` holds at the pace of the fastest of ``pace_s``, and
    one."""
    return max(2, math.ceil(1.25 * seconds / min(pace_s)) + 1)


# --------------------------------------------------------------------- run

def process_age_s() -> float:
    """Seconds since this process started (the kernel's record)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return 0.0


def check(cell: Dict, jobs: List[Dict]) -> Dict:
    """Compare every timed job's polished contigs with their truths.

    The numbers compared, each with its limit from
    ``benchmark/limits/<cell>.json`` (a number the file gives no limit
    is not compared):

    - ``missing``: contigs of any job that never came (exact, limit 0);
    - ``err_pct``: the worst job's errors per 100 bases, the truth
      that no polished base reached counted among them;
    - ``block_pct``: the most errors per 100 bases in any
      ``reference.REPORT_BLOCK`` bases of any job.

    Records a job may drop (its ``optional`` truths: corrected reads
    with too few overlaps) are neither compared nor charged when
    absent; ``optional`` counts them and those that came back.
    """
    lim = cell["limits"]
    missing, err_pct, block_pct = 0, 0.0, 0.0
    optional = {"reads": 0, "back": 0}
    for job in jobs:
        opt = job.get("optional", {})
        optional["reads"] += len(opt)
        back = job["contigs"] or {}
        optional["back"] += sum(1 for n in opt if back.get(n))
        if job["contigs"] is None:
            missing += len(job["truths"])
            continue
        r = reference.compare_job(job["contigs"], job["truths"])
        missing += len(r["missing"])
        if r["bases"]:
            err_pct = max(err_pct, 100.0 * r["errors"] / r["bases"])
        block_pct = max(block_pct, r["worst_block_pct"])
    values = {"missing": missing, "err_pct": err_pct,
              "block_pct": block_pct}
    limits = {"missing": 0, **{k: lim[k] for k in values if k in lim}}
    numbers = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = bool(jobs) and all(v["value"] <= v["limit"]
                            for v in numbers.values())
    verdict = {"correct": ok, "numbers": numbers}
    if any("optional" in job for job in jobs):
        verdict["optional"] = optional
    return verdict


def run(workload: str, seed: int, seconds: float, trace: bool,
        root: str = ROOT, emit=print) -> int:
    """One run of one cell; prints the result as the last line of
    standard output. Returns the exit code."""
    t_setup = time.perf_counter() - process_age_s()
    cell = find_cell(load_spec(root), workload, root)
    try:
        device = require_chips(cell["chips"])
    except NoChip as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 2
    import racon_tpu  # noqa: F401  the system under test, from the checkout
    readers = {n: load_reader(p) for n, p in cell["readers"].items()}
    shutil.rmtree(os.path.join(WORK_DIR, cell["name"]), ignore_errors=True)
    trace_dir = os.path.join(WORK_DIR, cell["name"], "trace")
    if trace:
        # The program's spans (racon_tpu/obs/trace.py) become profiler
        # annotations, so idle gaps can be named by what the host did.
        os.environ["RACON_TPU_TRACE_XPROF"] = "1"
    # The program's result caches stay off (the serial CLI's default):
    # a pooled mix's window polishes the inputs its warm-up polished.
    os.environ["RACON_TPU_CACHE"] = "0"
    counter = CompileCounter()

    # The scheduler's survivor buckets depend on the bases. A pooled mix
    # warms up on the very jobs its window polishes, in the window's
    # order, so that every program the window needs is built: as many
    # as the window holds at the pace of the warm-up jobs that built no
    # program (a job that compiles is no measure of a window's pace).
    # Without a pool, warm-up jobs share the timed jobs' layout, so they
    # use the same chunk shapes, and warm-up goes on until two jobs in a
    # row need no program this process has not built yet (or
    # MAX_WARMUP). A traced window is one job; with a pool, warm-up
    # polishes that very job once.
    pool = cell["traffic"].get("pool_jobs")
    warm_s: List[float] = []
    pace_s: List[float] = []
    ready: List[Dict] = []
    clean = 0
    for k in range(pool or MAX_WARMUP):
        before = counter.counts["setup"]["compile_requests"]
        if pool:
            ready.append(timed_job(cell, seed, k))
        w = polish(cell, ready[k] if pool else warmup_job(cell, seed, k))
        if w["failed"]:
            print("benchmark: a warm-up job failed", file=sys.stderr)
            return 1
        warm_s.append(w["wall_s"])
        built = counter.counts["setup"]["compile_requests"] > before
        clean = 0 if built or k == 0 else clean + 1
        if clean:
            pace_s.append(w["wall_s"])
        if pool and (trace or pace_s and
                     k + 1 >= jobs_to_ready(seconds, pace_s)):
            break
        if not pool and clean == 2:
            break
    n_ready = 1 if trace else jobs_to_ready(seconds, pace_s or warm_s)
    ready += [timed_job(cell, seed, i) for i in range(len(ready), n_ready)]
    setup_s = time.perf_counter() - t_setup

    done: List[Dict] = []
    gen_s = 0.0
    profile_stop_s = 0.0
    counter.phase = "window"
    t0 = time.perf_counter()
    while True:
        i = len(done)
        if i == len(ready):
            tg = time.perf_counter()
            ready.append(timed_job(cell, seed, i))
            gen_s += time.perf_counter() - tg
        job = ready[i]
        extra: List[str] = []
        if trace:
            import jax
            extra = ["--trace", os.path.join(job["dir"], "spans.jsonl")]
            # Device and host events, the program's annotations among
            # them; no Python call tracing (it slows the host several
            # fold and fills the trace).
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        res = polish(cell, job, extra)
        done.append({**job, **res})
        if trace:
            tp = time.perf_counter()
            jax.profiler.stop_trace()
            _untrace()
            profile_stop_s = time.perf_counter() - tp
            break
        if time.perf_counter() - t0 - gen_s >= seconds:
            break
    wall = time.perf_counter() - t0 - gen_s
    emit(json.dumps({"setup": {"seconds": setup_s, "warmup_s": warm_s,
                               **counter.counts["setup"]},
                     "window": {"jobs": len(done), "wall_s": wall,
                                "generate_s": gen_s,
                                "job_walls_s": [j["wall_s"] for j in done],
                                "profile_stop_s": profile_stop_s,
                                **counter.counts["window"]},
                     # Real consensus lanes each chip of a dp mesh got
                     # in the first timed job (none on one chip).
                     "dp_shard_lanes": {
                         k: v for k, v in done[0]["registry"].items()
                         if k.startswith("dp_shard_lanes_")}}))
    device["memory_peak_bytes"] = memory_peak_bytes(cell["chips"])
    gc.collect()

    verdict = check(cell, done)
    failed = sum(1 for j in done if j["failed"])
    result = {"correct": verdict["correct"], "attempted": len(done),
              "failed": failed, "metrics": {}, "device": device}
    if not trace:
        bases = sum(j["draft_bp"] for j in done)
        values = {"polished_bases_per_s": bases / wall, "setup_s": setup_s}
        for m in cell["end_to_end"]:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        from benchmark import trace_reduce
        red = trace_reduce.reduce_dir(
            trace_dir, os.path.join(done[0]["dir"], "spans.jsonl"),
            cell["chips"])
        ctx = {"registry": done[0]["registry"], "chips": cell["chips"],
               "draft_mb": done[0]["draft_bp"] / 1e6, "trace": red}
        for m in cell["per_layer"]:
            v = readers[m["name"]](ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            device["busy_by_chip"] = red["busy_by_chip"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    if "optional" in verdict:
        result["optional"] = verdict["optional"]
        print(f"optional records {verdict['optional']['reads']} back "
              f"{verdict['optional']['back']}", file=sys.stderr)
    result["checks"] = verdict["numbers"]
    for k, v in verdict["numbers"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    emit(json.dumps(result))
    return 0


def _untrace() -> None:
    """Close the program's span tracer that the traced job's
    ``--trace`` opened: ``racon_tpu.obs.trace.configure`` keeps the
    last tracer for later calls, and the jobs after the traced one
    run as untraced jobs do."""
    from racon_tpu.obs import trace as obs_trace
    # A private name of the program's: fail loudly if it goes away, as
    # every later job of a traced window would then stay traced.
    if not hasattr(obs_trace, "_tracer"):
        raise RuntimeError("racon_tpu.obs.trace has no _tracer to close")
    obs_trace._tracer = None
