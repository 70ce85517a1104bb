"""Gateway subsystem tests: the routing policy matrix, the job→ledger
adapter (journal↔ledger state machine), nonce-fenced gateway fail-over,
the shared warm-pool layout, and byte-identity of fleet-executed vs
in-process jobs (racon_tpu/gateway/, docs/GATEWAY.md)."""

import contextlib
import io
import json
import os
import sys

import numpy as np
import pytest

from racon_tpu.distributed.autoscaler import AutoscalePolicy, decide
from racon_tpu.gateway import dispatch as gw_dispatch
from racon_tpu.gateway import ha as gw_ha
from racon_tpu.gateway import policy as gw_policy
from racon_tpu.gateway.dispatch import (FleetDispatchError, RouteDecision,
                                        decide_route, fleet_paths,
                                        run_fleet_job, worker_cli_argv)
from racon_tpu.gateway.ha import GatewayLease, GatewayLeaseLost
from racon_tpu.obs import fleet as obs_fleet
from racon_tpu.obs import metrics as obs_metrics
from racon_tpu.obs import trace as obs_trace
from racon_tpu.resilience import faults
from racon_tpu.server.engine import JobSpec
from racon_tpu.server.jobs import Job, open_store

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASES = np.frombuffer(b"ACGT", np.uint8)

GATE_ENVS = (gw_dispatch.ENV_GATE_FLEET, gw_dispatch.ENV_MIN_TARGETS,
             gw_dispatch.ENV_QUEUE_PRESSURE, gw_dispatch.ENV_GATE_WORKERS,
             gw_ha.ENV_LEASE_S, gw_ha.ENV_STANDBY_POLL_S)


@pytest.fixture(autouse=True)
def gateway_sandbox(monkeypatch):
    """Keep the process-global injector/registry/tracer — and this
    suite's env knobs — out of other tests."""
    from racon_tpu.distributed import autoscaler as asc
    for env in GATE_ENVS + (asc.ENV_MIN, asc.ENV_MAX, asc.ENV_INTERVAL,
                            asc.ENV_MAX_SPAWNS, asc.ENV_DEADLINE,
                            asc.ENV_FAULT_PLAN, faults.ENV_FAULTS,
                            obs_trace.ENV_TRACE, obs_trace.ENV_TRACE_CTX,
                            "RACON_TPU_CACHE_DIR"):
        monkeypatch.delenv(env, raising=False)
    faults.configure(None)
    obs_metrics.reset()
    yield
    faults.configure(None)
    obs_metrics.reset()


# ------------------------------------------------------ routing policy


def test_route_matrix_disabled_size_and_pressure(monkeypatch):
    """The policy matrix: unarmed → always local; armed → fleet on
    size or on queue pressure, local otherwise."""
    # Unarmed: even a huge job under a deep queue stays local.
    d = decide_route(None, 10_000, queue_depth=99)
    assert d == RouteDecision("local", "fleet-disabled", 10_000, 99)

    monkeypatch.setenv(gw_dispatch.ENV_GATE_FLEET, "1")
    monkeypatch.setenv(gw_dispatch.ENV_MIN_TARGETS, "4")
    monkeypatch.setenv(gw_dispatch.ENV_QUEUE_PRESSURE, "2")
    cases = [
        # (n_targets, queue_depth) -> route
        (4, 0, "fleet"),    # at the size threshold
        (400, 0, "fleet"),  # far past it
        (3, 0, "local"),    # small, idle daemon
        (3, 1, "local"),    # small, shallow queue
        (1, 2, "fleet"),    # queue-pressure override on a tiny job
    ]
    for n, depth, want in cases:
        got = decide_route(None, n, queue_depth=depth)
        assert got.route == want, (n, depth, got)
        assert (got.n_targets, got.queue_depth) == (n, depth)
    # Reasons name the clause that fired — they land in the gate span.
    assert "n_targets 4 >= 4" in decide_route(None, 4).reason
    assert "queue_depth 2 >= 2" in \
        decide_route(None, 1, queue_depth=2).reason


def test_count_targets_counts_fasta_records(tmp_path):
    """The size signal is the record count of the target file, not an
    artifact of the index scan's return shape (a single-contig job must
    be able to stay local)."""
    p = tmp_path / "t.fasta"
    p.write_text(">c0\nACGT\n")
    assert gw_dispatch.count_targets(str(p)) == 1
    p.write_text(">c0\nACGT\n>c1\nAC\n>c2\nGGTT\n")
    assert gw_dispatch.count_targets(str(p)) == 3


def test_route_fault_site_fires_before_decision(monkeypatch):
    """The declared ``gate/route`` site injects at the routing seam."""
    monkeypatch.setenv(gw_dispatch.ENV_GATE_FLEET, "1")
    faults.configure("gate/route:0")
    with pytest.raises(faults.InjectedFault):
        decide_route(None, 10_000)


def test_worker_cli_argv_carries_identity_flags(tmp_path):
    """The fleet worker argv replays the JobSpec's identity contract —
    every output-affecting flag, the shared ledger, nothing else."""
    spec = JobSpec("r.fa", "o.paf", "d.fa", window_length=250,
                   match=3, backend="jax", include_unpolished=True)
    argv = worker_cli_argv(spec, str(tmp_path / "ledger"), 3)
    assert argv[:3] == ["r.fa", "o.paf", "d.fa"]
    assert "--include-unpolished" in argv
    for flag, want in (("--window-length", "250"), ("--match", "3"),
                       ("--backend", "jax"), ("--workers", "3")):
        assert argv[argv.index(flag) + 1] == want
    assert argv[argv.index("--ledger-dir") + 1] == \
        str(tmp_path / "ledger")


# --------------------------------------------------- warm-pool layout


def test_fleet_paths_key_stability(tmp_path):
    """Run dirs are keyed by job fingerprint (resubmission and standby
    adoption attach to the same ledger); the result CAS is shared
    across every job under one gateway."""
    state = str(tmp_path / "state")
    fp_a = "a" * 64
    fp_b = "b" * 64
    p1 = fleet_paths(state, fp_a)
    p2 = fleet_paths(state, fp_a)
    p3 = fleet_paths(state, fp_b)
    assert p1 == p2, "same fingerprint must map to the same run dir"
    assert p1.run_dir != p3.run_dir
    assert p1.run_dir == os.path.join(state, "fleet", fp_a[:16])
    assert p1.ledger_dir == os.path.join(p1.run_dir, "ledger")
    # Shared across jobs: one CAS per gateway root.
    assert p1.cas_dir == p3.cas_dir
    assert os.path.dirname(p1.cas_dir) == p1.root


# -------------------------------------------------- gateway fail-over


def test_lease_first_claim_blocks_live_standby(tmp_path):
    a = GatewayLease(str(tmp_path), "gw1", lease_s=30.0)
    assert a.try_acquire()
    assert a.epoch == 1 and not a.adopted
    a.verify()
    a.renew()
    b = GatewayLease(str(tmp_path), "gw2", lease_s=30.0)
    assert not b.try_acquire(), "live lease must not be stealable"
    assert not b.acquire(poll_s=0.01, deadline_s=0.05)


def test_lease_release_hands_off_without_adoption(tmp_path):
    """Clean drain: release leaves a marker (never unlinks), the next
    claim is instant, and it is NOT an adoption — the released
    gateway's jobs were drained, not orphaned."""
    a = GatewayLease(str(tmp_path), "gw1", lease_s=30.0)
    assert a.try_acquire()
    a.release()
    assert os.path.isfile(a.path), "release must never unlink"
    b = GatewayLease(str(tmp_path), "gw2", lease_s=30.0)
    assert b.acquire(poll_s=0.01, deadline_s=1.0)
    assert b.epoch == 2 and not b.adopted
    with pytest.raises(GatewayLeaseLost):
        a.verify()


def test_lease_steal_after_expiry_is_adoption_and_fences(tmp_path):
    """The kill-drill edge: a dead primary's expired lease is stolen
    (skewed clock, exactly the shard-ledger drill), the steal counts
    as an adoption, and the fenced primary can no longer renew."""
    a = GatewayLease(str(tmp_path), "gw1", lease_s=30.0)
    assert a.try_acquire()
    faults.configure("skew=1e9")
    b = GatewayLease(str(tmp_path), "gw2", lease_s=30.0)
    assert b.try_acquire()
    assert b.adopted and b.epoch == 2
    faults.configure(None)
    with pytest.raises(GatewayLeaseLost):
        a.renew()
    # The stale gateway also loses the adoption race outright: the
    # thief's lease is live now, so a late try_acquire gets nothing.
    assert not a.try_acquire()


def test_lease_adoption_race_loser_sees_foreign_nonce(tmp_path,
                                                      monkeypatch):
    """Two standbys steal the same expired lease: the loser's rewrite
    is overwritten before its re-read, so the nonce check fails and
    try_acquire reports False instead of a split-brain claim."""
    a = GatewayLease(str(tmp_path), "gw1", lease_s=0.0)
    assert a.try_acquire()  # deadline == now: instantly stealable
    real_write = gw_ha.atomic_write_bytes

    def racing_write(path, blob):
        real_write(path, blob)
        rec = json.loads(blob)
        rec["nonce"] = "feedfacefeedface"  # the winner lands after us
        real_write(path, (json.dumps(rec, sort_keys=True) +
                          "\n").encode())

    monkeypatch.setattr(gw_ha, "atomic_write_bytes", racing_write)
    loser = GatewayLease(str(tmp_path), "gw2", lease_s=30.0)
    assert not loser.try_acquire()
    assert loser.nonce == ""
    with pytest.raises(GatewayLeaseLost):
        loser.verify()


def test_lease_adopt_fault_site_breaks_adopting_standby(tmp_path):
    """The declared ``gate/adopt`` site fires on the adoption edge —
    the drill can kill a standby at the exact moment it wins."""
    a = GatewayLease(str(tmp_path), "gw1", lease_s=0.0)
    assert a.try_acquire()
    faults.configure("gate/adopt:0")
    b = GatewayLease(str(tmp_path), "gw2", lease_s=30.0)
    with pytest.raises(faults.InjectedFault):
        b.try_acquire()


# ------------------------------------------------- autoscaling policy


def test_service_target_boosts_on_queue_signals(monkeypatch):
    """service_target layers queue depth and wait-p95 boosts over the
    stock open-work clamp, publishes gate_fleet_target, and respects
    the policy's max."""
    monkeypatch.setenv(gw_dispatch.ENV_QUEUE_PRESSURE, "4")
    pol = AutoscalePolicy(1, 8, 0.5, 16, 0.0)
    reg = obs_metrics.registry()
    assert gw_policy.service_target(2, pol) == decide(2, pol) == 2
    reg.set("serve_queue_depth_peak", 4)
    assert gw_policy.service_target(2, pol) == 3
    for _ in range(20):
        obs_metrics.record_hist("serve_queue_wait_s", 1.0)
    assert gw_policy.service_target(2, pol) == 4
    assert reg.get("gate_fleet_target") == 4
    # The boost never pushes past the policy ceiling.
    assert gw_policy.service_target(8, pol) == 8
    # None open_work (unreadable ledger) still clamps to max.
    assert gw_policy.service_target(None, pol) == 8


def test_service_target_damped_by_fleet_drain_rate(tmp_path,
                                                   monkeypatch):
    """A fleet already draining faster than work arrives gets no
    pressure boost — the signals must not oscillate the fleet size."""
    monkeypatch.setenv(gw_dispatch.ENV_QUEUE_PRESSURE, "1")
    pol = AutoscalePolicy(1, 8, 0.5, 16, 0.0)
    reg = obs_metrics.registry()
    reg.set("serve_queue_depth_peak", 9)
    ld = str(tmp_path / "ledger")
    obs = os.path.join(ld, obs_fleet.OBS_SUBDIR)
    os.makedirs(obs)
    assert gw_policy.fleet_windows_per_sec(ld) == 0.0
    assert gw_policy.service_target(2, pol, ledger_dir=ld) == 3
    with open(os.path.join(obs, "worker_w1.metrics.jsonl"), "w") as fh:
        fh.write(json.dumps({
            "schema": obs_fleet.SNAPSHOT_SCHEMA, "worker_id": "w1",
            "run_fp": "f" * 16, "wall_s": 2.0,
            "metrics": {"poa_windows_total": 400}}) + "\n")
    assert gw_policy.fleet_windows_per_sec(ld) == 200.0
    assert gw_policy.service_target(2, pol, ledger_dir=ld) == 2


def test_record_gate_events_and_extras():
    reg = obs_metrics.registry()
    obs_metrics.record_gate("route_fleet", "j1", "acme",
                            decision="fleet")
    obs_metrics.record_gate("route_local", "j2", "acme")
    obs_metrics.record_gate("adopt", "j1", "acme", epoch=2)
    obs_metrics.record_gate("fleet_run", "j1", "acme", wall_s=1.5)
    with pytest.raises(ValueError):
        obs_metrics.record_gate("no-such-event", "j1", "acme")
    snap = reg.snapshot()
    assert snap["gate_routed_fleet"] == 1
    assert snap["gate_routed_local"] == 1
    assert snap["gate_adoptions"] == 1
    assert snap["gate_fleet_runs"] == 1
    assert snap["gate_fleet_wall_s"] == 1.5
    # Gauges merge last-wins across shards; counters sum.
    assert obs_metrics.merge_kind("gate_fleet_target") == "last"
    assert obs_metrics.merge_kind("gate_routed_fleet") == "sum"


# --------------------------------------------- the job→ledger adapter


def _mutate(rng, truth):
    out = []
    for b in truth:
        r = rng.random()
        if r < 0.03:
            continue
        if r < 0.06:
            out.append(BASES[rng.integers(0, 4)])
        else:
            out.append(b)
    return bytes(bytearray(out))


def _write_inputs(d, n_contigs=2, n_reads=6, clen=300, seed=11):
    rng = np.random.default_rng(seed)
    drafts, reads, paf = [], [], []
    for ci in range(n_contigs):
        truth = BASES[rng.integers(0, 4, clen)]
        draft = _mutate(rng, truth)
        drafts.append(b">c%d\n%s\n" % (ci, draft))
        for i in range(n_reads):
            r = _mutate(rng, truth)
            name = f"c{ci}r{i}"
            reads.append(b">" + name.encode() + b"\n" + r + b"\n")
            paf.append(f"{name}\t{len(r)}\t0\t{len(r)}\t+\tc{ci}"
                       f"\t{len(draft)}\t0\t{len(draft)}"
                       f"\t{min(len(r), len(draft))}"
                       f"\t{max(len(r), len(draft))}\t60")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "draft.fasta"), "wb") as fh:
        fh.write(b"".join(drafts))
    with open(os.path.join(d, "reads.fasta"), "wb") as fh:
        fh.write(b"".join(reads))
    with open(os.path.join(d, "ovl.paf"), "w") as fh:
        fh.write("\n".join(paf) + "\n")


def _spec_for(d):
    return JobSpec(os.path.join(d, "reads.fasta"),
                   os.path.join(d, "ovl.paf"),
                   os.path.join(d, "draft.fasta"), backend="jax")


def _run_cli_bytes(argv):
    from racon_tpu import cli
    stdout = io.StringIO()
    stdout.buffer = io.BytesIO()
    with contextlib.redirect_stdout(stdout), \
            contextlib.redirect_stderr(io.StringIO()):
        rc = cli.main(argv)
    assert rc == 0
    return stdout.buffer.getvalue()


def _solo_cli_bytes(d):
    return _run_cli_bytes(["--backend", "jax",
                           os.path.join(d, "reads.fasta"),
                           os.path.join(d, "ovl.paf"),
                           os.path.join(d, "draft.fasta")])


def _seed_fleet_ledger(state, spec):
    """Run one in-process ledger worker with the exact argv the
    gateway hands its autoscaled fleet, publishing out.fasta under the
    job's fleet run dir."""
    paths = fleet_paths(state, spec.fingerprint())
    os.makedirs(paths.ledger_dir, exist_ok=True)
    argv = worker_cli_argv(spec, paths.ledger_dir, 1)
    return _run_cli_bytes(argv + ["--worker-id", "seed"]), paths


def _wait_finished(job, timeout_s=120.0):
    assert job.finished.wait(timeout_s), \
        f"job {job.id} still {job.state} after {timeout_s}s"


def test_run_fleet_job_commits_ledger_output_byte_identical(tmp_path):
    """The adapter state machine: a fleet-produced out.fasta is
    re-committed contig-by-contig through the job's own checkpoint
    store, so the journal, /stream, and recovery see a fleet job
    exactly like a local one — and the bytes match the solo CLI."""
    d = str(tmp_path / "in")
    _write_inputs(d, n_contigs=3)
    base = _solo_cli_bytes(d)
    spec = _spec_for(d)
    state = str(tmp_path / "state")
    fleet_out, paths = _seed_fleet_ledger(state, spec)
    assert fleet_out == base, "ledger worker diverged from solo CLI"
    obs_metrics.reset()

    job = Job("j0001", "acme", spec, str(tmp_path / "jobs" / "j0001"))
    store = open_store(job)
    assert run_fleet_job(job, state, store) == 3
    store.close()
    assert job.result_bytes() == base
    snap = obs_metrics.registry().snapshot()
    assert snap["gate_fleet_runs"] == 1
    assert snap["gate_fleet_wall_s"] >= 0

    # Restart/adoption replay: a resumed store's committed prefix is
    # re-emitted byte-for-byte from the shard — zero recompute, and
    # the finished ledger short-circuits the supervisor entirely.
    job2 = Job("j0001", "acme", spec, str(tmp_path / "jobs" / "j0001"))
    store2 = open_store(job2)
    assert len(store2.committed) == 3
    assert run_fleet_job(job2, state, store2) == 3
    store2.close()
    assert job2.result_bytes() == base


def test_run_fleet_job_resumes_partial_prefix(tmp_path):
    """Adoption mid-job: tid 0 already committed in the journal's
    store, tids 1-2 still owed — the adapter re-emits the prefix from
    the store and commits only the remainder."""
    d = str(tmp_path / "in")
    _write_inputs(d, n_contigs=3)
    base = _solo_cli_bytes(d)
    spec = _spec_for(d)
    state = str(tmp_path / "state")
    _seed_fleet_ledger(state, spec)

    recs = gw_dispatch._split_fasta(base)
    assert len(recs) == 3
    job = Job("j0002", "acme", spec, str(tmp_path / "jobs" / "j0002"))
    store = open_store(job)
    nl = recs[0].index(b"\n")
    store.commit(0, bytes(recs[0][1:nl]), bytes(recs[0][nl + 1:-1]))
    assert run_fleet_job(job, state, store) == 3
    assert len(store.committed) == 3
    store.close()
    assert job.result_bytes() == base


def test_run_fleet_job_plumbs_shared_caches_and_fails_loud(tmp_path,
                                                           monkeypatch):
    """Worker env plumbing (the CAS satellite): every spawned worker
    inherits the fleet result CAS under the gateway root and sets no
    compile-cache directory of its own — and a supervisor that produces no merged
    output is a loud FleetDispatchError, never a silent empty job."""
    d = str(tmp_path / "in")
    _write_inputs(d)
    spec = _spec_for(d)
    state = str(tmp_path / "state")
    paths = fleet_paths(state, spec.fingerprint())
    seen = {}

    class _FakeScaler:
        def __init__(self, ledger_dir, argv, **kw):
            seen.update(kw, ledger_dir=ledger_dir, argv=argv)

        def run(self):
            return 0  # "success", but never publishes out.fasta

    monkeypatch.setattr("racon_tpu.distributed.autoscaler.Autoscaler",
                        _FakeScaler)
    job = Job("j0003", "acme", spec, str(tmp_path / "jobs" / "j0003"))
    store = open_store(job)
    with pytest.raises(FleetDispatchError, match="without a merged"):
        run_fleet_job(job, state, store, trace_ctx="cafe" * 4 + ":7")
    store.close()
    env = seen["extra_env"]
    assert not any("JAX_CACHE" in k or "COMPILATION_CACHE" in k
                   for k in env)
    assert env["RACON_TPU_CACHE_DIR"] == paths.cas_dir
    assert env["RACON_TPU_TRACE_CTX"] == "cafe" * 4 + ":7"
    assert seen["ledger_dir"] == paths.ledger_dir
    assert seen["trace_dir"] == os.path.join(paths.ledger_dir, "obs")
    assert os.path.isdir(paths.cas_dir)

    class _DeadScaler(_FakeScaler):
        def run(self):
            return 71

    monkeypatch.setattr("racon_tpu.distributed.autoscaler.Autoscaler",
                        _DeadScaler)
    store = open_store(job)
    with pytest.raises(FleetDispatchError, match="exited 71"):
        run_fleet_job(job, state, store)
    store.close()


# ------------------------------------------- daemon routing end-to-end


def test_daemon_routes_by_policy_byte_identical(tmp_path, monkeypatch):
    """The tentpole seam: an armed daemon ships a big-enough job to
    the fleet path (here a pre-published ledger — the same
    short-circuit a resubmitted fingerprint hits) and keeps small jobs
    on the in-process batcher; both streams are byte-identical to the
    solo CLI and the gate_* counters tell the routes apart."""
    from racon_tpu.server.daemon import PolishServer

    d1 = str(tmp_path / "in1")
    d2 = str(tmp_path / "in2")
    _write_inputs(d1, seed=11)
    _write_inputs(d2, seed=22)
    base1 = _solo_cli_bytes(d1)
    base2 = _solo_cli_bytes(d2)
    state = str(tmp_path / "state")
    _seed_fleet_ledger(state, _spec_for(d1))
    obs_metrics.reset()

    monkeypatch.setenv(gw_dispatch.ENV_GATE_FLEET, "1")
    monkeypatch.setenv(gw_dispatch.ENV_MIN_TARGETS, "1")
    server = PolishServer(state)
    j1 = server.submit("acme", _spec_for(d1))
    _wait_finished(j1)
    # Small-job route: raise the bar so the second job stays local.
    monkeypatch.setenv(gw_dispatch.ENV_MIN_TARGETS, "99")
    j2 = server.submit("umbrella", _spec_for(d2))
    _wait_finished(j2)
    for b in server._batchers.values():
        b.close()
    assert (j1.state, j2.state) == ("done", "done"), (j1.error, j2.error)
    assert j1.result_bytes() == base1
    assert j2.result_bytes() == base2
    snap = obs_metrics.registry().snapshot()
    assert snap["gate_routed_fleet"] == 1
    assert snap["gate_routed_local"] == 1
    assert snap["gate_fleet_runs"] == 1
    assert snap["serve_jobs_completed"] == 2


# --------------------------------------------------- gate observability


def test_gate_spans_validate_and_render(tmp_path):
    """obs_report --job stitches gateway spans into the same timeline
    as daemon and worker spans, and the validator holds gate spans to
    their declared attr contract."""
    sys.path.insert(0, REPO)
    from scripts import obs_report

    tid = "deadbeefcafef00d"
    obs = os.path.join(str(tmp_path), obs_fleet.OBS_SUBDIR)
    os.makedirs(obs)

    def span(sid, kind, name, t0, **attrs):
        return {"ev": "span", "id": sid, "parent": None, "kind": kind,
                "name": name, "t0": t0, "dur_s": 0.1, **attrs}

    def trace_file(path, begin, spans):
        with open(path, "w") as fh:
            fh.write(json.dumps({"ev": "begin", "schema": 1,
                                 "unix_time": begin}) + "\n")
            for s in spans:
                fh.write(json.dumps(s) + "\n")

    trace_file(os.path.join(obs, "daemon.jsonl"), 100.0, [
        span(1, "gate", "route_fleet", 0.1, trace_id=tid, job="j1",
             tenant="acme", parent_id=0, decision="fleet",
             reason="n_targets 4 >= 1"),
        span(2, "gate", "fleet_run", 0.9, trace_id=tid, job="j1",
             tenant="acme", parent_id=0, decision="fleet"),
    ])
    trace_file(os.path.join(obs, "worker_as0.jsonl"), 101.0, [
        span(1, "phase", "polish", 0.2, trace_id=tid, run_fp="fp1",
             worker_id="as0"),
    ])
    assert obs_report.validate(
        obs_report.load_trace(os.path.join(obs, "daemon.jsonl"))) == []
    out = io.StringIO()
    assert obs_report._render_job(str(tmp_path), tid, out=out) == 0
    text = out.getvalue()
    assert f"job {tid}: 3 span(s) across 2 process(es)" in text
    assert "gate/route_fleet" in text and "gate/fleet_run" in text
    assert "job=j1 tenant=acme" in text
    assert "decision=fleet" in text and "reason=n_targets" in text

    # A gate span missing its contract attrs is a validation error.
    bad = os.path.join(str(tmp_path), "bad.jsonl")
    trace_file(bad, 103.0, [span(1, "gate", "adopt", 0.1,
                                 trace_id=tid, job="j1")])
    errs = obs_report.validate(obs_report.load_trace(bad))
    assert errs and any("tenant" in e for e in errs)
