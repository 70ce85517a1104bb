"""Measured dp-scaling curve: 1/2/4/8-worker ledger fleets over one
synthetic genome.

For each requested worker count N this script runs a complete
``--ledger-dir`` fleet of N real CLI subprocesses over the same
synthetic genome, gates the merged FASTA byte-identical against a
single serial run, and measures fleet throughput as total polished
windows (from the fleet metric model, racon_tpu/obs/fleet.py) over the
fleet's wall clock. The curve is emitted as one JSON object::

    {"dp_workers": [1, 2, 4], "dp_windows_per_sec_1": ...,
     "dp_windows_per_sec_2": ..., "dp_windows_per_sec_4": ...,
     "dp_scaling_efficiency": rate_N / (N * rate_1), ...}

``--out <path>`` writes it to a file as well::

    python scripts/dp_scaling_bench.py --out /tmp/dp.json

Worker counts: ``--workers 1,2,4,8`` (default ``auto`` = 1,2,4 plus 8
when the host has >= 8 CPUs). An explicitly requested count the host
cannot run (more workers than CPUs) is a **hard error** — silently
benching fewer workers would publish a scaling curve that was never
measured. On this CPU image the curve measures the *fleet machinery's*
scaling (sharding, leases, per-process JAX compute); on a TPU pod each
worker binds its own chip and the same curve reads as chip scaling.
"""

import json
import os
import subprocess
import sys
import tempfile
import time
from typing import List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from racon_tpu.utils import envspec

import numpy as np                                   # noqa: E402

BASES = np.frombuffer(b"ACGT", np.uint8)
BOOT = ("import sys; from racon_tpu import cli; "
        "sys.exit(cli.main(sys.argv[1:]))")
N_CONTIGS = 8
N_READS = 6
DEFAULT_COUNTS = (1, 2, 4, 8)


def _noisy(rng, truth):
    out = []
    for b in truth:
        r = rng.random()
        if r < 0.03:
            continue
        out.append(int(rng.integers(0, 4)) if r < 0.06 else int(
            np.searchsorted(BASES, b)))
    return bytes(BASES[np.array(out)])


def _write_inputs(d, contig_len: int):
    rng = np.random.default_rng(41)
    drafts, reads, paf = [], [], []
    for c in range(N_CONTIGS):
        truth = BASES[rng.integers(0, 4, contig_len + 40 * c)]
        draft = _noisy(rng, truth)
        drafts.append(b">c%d\n%s\n" % (c, draft))
        for i in range(N_READS):
            r = _noisy(rng, truth)
            rid = f"r{c}_{i}"
            reads.append(b">%s\n%s\n" % (rid.encode(), r))
            paf.append(f"{rid}\t{len(r)}\t0\t{len(r)}\t+\tc{c}"
                       f"\t{len(draft)}\t0\t{len(draft)}"
                       f"\t{min(len(r), len(draft))}"
                       f"\t{max(len(r), len(draft))}\t60")
    with open(os.path.join(d, "draft.fasta"), "wb") as fh:
        fh.write(b"".join(drafts))
    with open(os.path.join(d, "reads.fasta"), "wb") as fh:
        fh.write(b"".join(reads))
    with open(os.path.join(d, "ovl.paf"), "w") as fh:
        fh.write("\n".join(paf) + "\n")


def _cmd(d, *extra):
    return [sys.executable, "-c", BOOT, "--backend", "jax", *extra,
            os.path.join(d, "reads.fasta"), os.path.join(d, "ovl.paf"),
            os.path.join(d, "draft.fasta")]


def _env():
    e = dict(os.environ)
    for k in ("RACON_TPU_FAULTS", "RACON_TPU_TRACE", "RACON_TPU_OBS_DIR",
              "RACON_TPU_OBS_FLUSH_S"):
        e.pop(k, None)
    # One shard per contig: every worker count up to 8 has enough
    # shards to keep all workers busy, and the partition is identical
    # across counts, so per-N differences are scheduling, not layout.
    e["RACON_TPU_DIST_SHARDS"] = str(N_CONTIGS)
    return e


def _run_fleet(d, n_workers: int, timeout_s: float):
    """One complete N-worker fleet; returns (merged_bytes, wall_s,
    fleet_model)."""
    from racon_tpu.obs import fleet as obs_fleet
    ledger = os.path.join(d, f"ledger_{n_workers}")
    env = _env()
    t0 = time.perf_counter()
    procs = [subprocess.Popen(
        _cmd(d, "--ledger-dir", ledger, "--workers", str(n_workers),
             "--worker-id", f"w{i}"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env)
        for i in range(n_workers)]
    outs = []
    for p in procs:
        o, err = p.communicate(timeout=timeout_s)
        if p.returncode != 0:
            for q in procs:
                q.kill()
            raise RuntimeError(
                f"[dp-scaling] worker exited {p.returncode} in the "
                f"{n_workers}-worker fleet:\n{err.decode()}")
        outs.append(o)
    wall = time.perf_counter() - t0
    emitters = [o for o in outs if o]
    if len(emitters) != 1:
        raise RuntimeError(
            f"[dp-scaling] expected exactly one merge emitter, got "
            f"{len(emitters)} in the {n_workers}-worker fleet")
    model = obs_fleet.aggregate(ledger)
    return emitters[0], wall, model


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    out_path = None
    if "--out" in argv:
        i = argv.index("--out")
        out_path = argv[i + 1]
        del argv[i:i + 2]
    counts_arg = "auto"
    if "--workers" in argv:
        i = argv.index("--workers")
        counts_arg = argv[i + 1]
        del argv[i:i + 2]
    contig_len = int(argv[argv.index("--contig-len") + 1]) \
        if "--contig-len" in argv else 300
    timeout_s = float(envspec.read("RACON_TPU_DP_TIMEOUT"))

    ncpu = os.cpu_count() or 1
    if counts_arg == "auto":
        counts = [n for n in DEFAULT_COUNTS if n <= max(4, ncpu)]
        dropped = [n for n in DEFAULT_COUNTS if n not in counts]
        if dropped:
            print(f"[dp-scaling] host has {ncpu} CPUs: skipping "
                  f"{dropped} worker count(s) (request explicitly "
                  "with --workers to force)", file=sys.stderr)
    else:
        counts = sorted({int(p) for p in counts_arg.split(",")})
        bad = [n for n in counts if n < 1]
        if bad:
            print(f"[dp-scaling] error: invalid worker count(s) {bad}",
                  file=sys.stderr)
            return 2
        # The loud-failure contract: an explicitly requested count the
        # host cannot actually run is an error, NOT a silent downgrade
        # to fewer workers.
        over = [n for n in counts if n > ncpu]
        if over:
            print(f"[dp-scaling] error: requested worker count(s) "
                  f"{over} exceed the host's {ncpu} CPUs — each fleet "
                  "worker is a full polisher process; benching fewer "
                  "would mislabel the curve. Drop the count or use "
                  "a larger host.", file=sys.stderr)
            return 1
    if max(counts) > N_CONTIGS:
        print(f"[dp-scaling] error: worker count {max(counts)} "
              f"exceeds the workload's {N_CONTIGS} shards — workers "
              "beyond the shard count would sit idle and dilute the "
              "curve", file=sys.stderr)
        return 1

    with tempfile.TemporaryDirectory() as d:
        _write_inputs(d, contig_len)

        # Serial baseline: correctness gate + the window count every
        # fleet must reproduce.
        proc = subprocess.run(_cmd(d), capture_output=True, env=_env())
        if proc.returncode != 0:
            print(proc.stderr.decode(), file=sys.stderr)
            return 1
        base = proc.stdout
        assert base.count(b">") == N_CONTIGS

        rates = {}
        windows_total = None
        for n in counts:
            merged, wall, model = _run_fleet(d, n, timeout_s)
            if merged != base:
                print(f"[dp-scaling] error: {n}-worker merged output "
                      "differs from serial run", file=sys.stderr)
                return 1
            windows = model["fleet"].get("poa_windows_total", 0)
            if not windows:
                print(f"[dp-scaling] error: fleet model for n={n} "
                      "reports zero polished windows", file=sys.stderr)
                return 1
            if windows_total is None:
                windows_total = windows
            rates[n] = windows / wall
            print(f"[dp-scaling] n={n}: {windows} windows in "
                  f"{wall:.2f}s = {rates[n]:.2f} windows/s "
                  f"(merge byte-identical to serial)", file=sys.stderr)

    n_max = max(counts)
    out = {"dp_workers": counts,
           "dp_total_windows": windows_total,
           "dp_scaling_efficiency": round(
               rates[n_max] / (n_max * rates[1]), 3) if 1 in rates
           else None}
    for n, r in rates.items():
        out[f"dp_windows_per_sec_{n}"] = round(r, 2)
    text = json.dumps(out, sort_keys=True)
    print(text)
    if out_path:
        from racon_tpu.utils.atomicio import atomic_write_text
        atomic_write_text(out_path, text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
