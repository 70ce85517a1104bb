"""Fail-slow hardening tests: deadline derivation, the guard watchdog,
hang/stall fault actions, pipeline stall detection/recovery, ledger
lease release, straggler flagging, and the /healthz liveness view
(racon_tpu/resilience/watchdog.py, docs/RESILIENCE.md "Fail-slow")."""

import contextlib
import io
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from racon_tpu.obs import metrics as obs_metrics
from racon_tpu.ops import budget
from racon_tpu.resilience import faults, retry, watchdog

from synth_windows import BASES, build_subdel_windows, mutate_subdel


#: Every env knob this subsystem reads — scrubbed around each test so
#: an operator shell (or a neighbouring test) can't leak configuration.
_ENVS = (
    "RACON_TPU_DEADLINE_H2D", "RACON_TPU_DEADLINE_D2H",
    "RACON_TPU_DEADLINE_DISPATCH", "RACON_TPU_DEADLINE_MBPS",
    "RACON_TPU_DEADLINE_CELLS_PER_S", "RACON_TPU_DEADLINE_SCALE",
    watchdog.ENV_TERMINAL, "RACON_TPU_STALL_S",
    faults.ENV_HANG_S, faults.ENV_STALL_S,
    "RACON_TPU_STRAGGLER_FRAC", "RACON_TPU_PIPELINE",
)


@pytest.fixture(autouse=True)
def failslow_sandbox(monkeypatch):
    monkeypatch.delenv(retry.ENV_RETRY, raising=False)
    monkeypatch.delenv(faults.ENV_FAULTS, raising=False)
    for name in _ENVS:
        monkeypatch.delenv(name, raising=False)
    retry.configure(None)
    faults.configure(None)
    obs_metrics.reset()
    watchdog.reset()
    yield
    retry.configure(None)
    faults.configure(None)
    obs_metrics.reset()
    watchdog.reset()


# ------------------------------------------------------ deadline budgets


def test_deadline_derivation_defaults():
    # base + bytes / (MB/s * 1e6), all at the documented defaults.
    assert budget.transfer_deadline_s(0, "h2d") == 60.0
    assert budget.transfer_deadline_s(10 * 10**6, "h2d") == 100.0
    assert budget.transfer_deadline_s(0, "d2h") == 300.0
    assert budget.dispatch_deadline_s(0) == 300.0
    assert budget.dispatch_deadline_s(4 * 10**6) == 302.0
    # Negative sizes clamp instead of shrinking the budget.
    assert budget.transfer_deadline_s(-5, "h2d") == 60.0


def test_deadline_env_overrides(monkeypatch):
    monkeypatch.setenv("RACON_TPU_DEADLINE_H2D", "10")
    monkeypatch.setenv("RACON_TPU_DEADLINE_MBPS", "1.0")
    monkeypatch.setenv("RACON_TPU_DEADLINE_SCALE", "2.0")
    assert budget.transfer_deadline_s(5 * 10**6, "h2d") == 2.0 * 15.0
    # base <= 0 disables the whole site class, scale notwithstanding.
    monkeypatch.setenv("RACON_TPU_DEADLINE_H2D", "0")
    assert budget.transfer_deadline_s(5 * 10**6, "h2d") == 0.0
    monkeypatch.setenv("RACON_TPU_DEADLINE_DISPATCH", "-1")
    assert budget.dispatch_deadline_s(10**9) == 0.0


def test_deadline_env_invalid(monkeypatch):
    monkeypatch.setenv("RACON_TPU_DEADLINE_H2D", "abc")
    with pytest.raises(ValueError, match="RACON_TPU_DEADLINE_H2D"):
        budget.transfer_deadline_s(0, "h2d")
    monkeypatch.delenv("RACON_TPU_DEADLINE_H2D")
    monkeypatch.setenv("RACON_TPU_DEADLINE_MBPS", "0")
    with pytest.raises(ValueError, match="RACON_TPU_DEADLINE_MBPS"):
        budget.transfer_deadline_s(1, "h2d")
    monkeypatch.delenv("RACON_TPU_DEADLINE_MBPS")
    with pytest.raises(ValueError, match="direction"):
        budget.transfer_deadline_s(0, "sideways")


def test_site_deadline_prefix_classes(monkeypatch):
    monkeypatch.setenv("RACON_TPU_DEADLINE_H2D", "7")
    monkeypatch.setenv("RACON_TPU_DEADLINE_D2H", "8")
    monkeypatch.setenv("RACON_TPU_DEADLINE_DISPATCH", "9")
    assert watchdog.site_deadline("h2d/chunk") == 7.0
    assert watchdog.site_deadline("d2h/align") == 8.0
    assert watchdog.site_deadline("dispatch/chunk") == 9.0
    assert watchdog.site_deadline("sched/flags") == 9.0
    assert watchdog.site_deadline("ckpt/manifest") == 0.0


# -------------------------------------------------------------- guard


def test_guard_passes_result_and_exceptions():
    assert watchdog.guard("t/s", 5.0, lambda a, b=0: a + b, 2, b=3) == 5
    with pytest.raises(KeyError):
        watchdog.guard("t/s", 5.0,
                       lambda: (_ for _ in ()).throw(KeyError("x")))
    assert "res_watchdog_breach_total" not in \
        obs_metrics.registry().snapshot()


def test_guard_disabled_runs_inline():
    # deadline <= 0: the body runs on the caller thread (no pool hop).
    assert watchdog.guard("t/s", 0.0, threading.get_ident) == \
        threading.get_ident()


def test_guard_breach_raises_and_counts():
    t0 = time.monotonic()
    with pytest.raises(watchdog.DispatchTimeout) as ei:
        watchdog.guard("d2h/slow", 0.15, time.sleep, 1.0)
    assert time.monotonic() - t0 < 1.0   # did NOT wait out the sleep
    assert ei.value.site == "d2h/slow"
    assert ei.value.deadline_s == 0.15
    snap = obs_metrics.registry().snapshot()
    assert snap["res_watchdog_breach_total"] == 1
    assert snap["res_watchdog_site_d2h_slow"] == 1
    h = watchdog.health_snapshot()
    assert h["status"] == "ok"           # non-terminal breach: still ok
    assert h["watchdog_breaches"] == 1
    assert h["last_breach"]["site"] == "d2h/slow"


def test_guard_ambient_deadline_visible_to_body():
    seen = watchdog.guard("t/s", 5.0, watchdog.ambient_deadline)
    assert seen == 5.0
    assert watchdog.ambient_deadline() == 0.0   # caller thread: unarmed


def test_terminal_breach_escalates(monkeypatch):
    monkeypatch.setenv(watchdog.ENV_TERMINAL, "1")
    with pytest.raises(watchdog.WatchdogTerminal) as ei:
        watchdog.guard("dispatch/chunk", 0.1, time.sleep, 0.8)
    assert watchdog.is_terminal(ei.value)
    wrapped = RuntimeError("stage boom")
    wrapped.__cause__ = ei.value
    assert watchdog.is_terminal(wrapped)
    assert not watchdog.is_terminal(RuntimeError("plain"))
    snap = obs_metrics.registry().snapshot()
    assert snap["res_watchdog_terminal_total"] == 1
    assert watchdog.health_snapshot()["status"] == "terminal"


def test_is_terminal_through_stage_error():
    from racon_tpu.pipeline.stages import StageError
    term = watchdog.WatchdogTerminal("dispatch/chunk", 1, 1)
    try:
        try:
            raise term
        except watchdog.WatchdogTerminal as exc:
            raise StageError("compute", exc) from exc
    except StageError as err:
        assert watchdog.is_terminal(err)
    assert not watchdog.is_terminal(StageError("compute",
                                               ValueError("x")))


def test_health_snapshot_stall_state():
    assert watchdog.health_snapshot()["status"] == "ok"
    watchdog.note_stall(4)
    h = watchdog.health_snapshot()
    assert h["status"] == "stalled" and h["pipeline_stalls"] == 1


# ------------------------------------------------- hang/stall injection


def test_fault_spec_hang_stall_grammar():
    faults.FaultInjector("a:0!hang=0.5;b:1!stall=2")      # parses
    faults.FaultInjector("a:0!hang")                      # default dur
    for bad in ("s:0!stall=x", "s:0!raise=3", "s:0!hang=-1",
                "s:0!kill=2"):
        with pytest.raises(faults.FaultSpecError):
            faults.FaultInjector(bad)


def test_stall_action_delays_then_proceeds():
    faults.configure("x/y:0!stall=0.3")
    t0 = time.monotonic()
    faults.maybe_fault("x/y")            # index 0: sleeps, no raise
    assert time.monotonic() - t0 >= 0.25
    t0 = time.monotonic()
    faults.maybe_fault("x/y")            # index 1: clean
    assert time.monotonic() - t0 < 0.1


def test_retry_detects_hang_and_recovers():
    """The acceptance loop on one site: an injected hang outlives the
    deadline, the guard converts it to DispatchTimeout (transient), and
    the retry's second attempt succeeds — bounded wall, same result."""
    faults.configure("h2d/chunk:0!hang=0.6")
    pol = retry.RetryPolicy(attempts=3, base=0.0, jitter=0.0)
    t0 = time.monotonic()
    out = retry.call("h2d/chunk", lambda: "ok", policy=pol,
                     deadline_s=0.15)
    assert out == "ok"
    assert time.monotonic() - t0 < 2.0
    snap = obs_metrics.registry().snapshot()
    assert snap["res_watchdog_breach_total"] == 1
    assert snap["res_retry_total"] == 1
    assert snap["res_fault_injected_total"] >= 1


# --------------------------------------------------------- chaos drill


_CHAOS_SITES = ("h2d/chunk", "dispatch/chunk", "d2h/chunk")
_CHAOS_ACTIONS = ("", "!hang=0.4", "!stall=0.2")   # "" = raise


@pytest.mark.slow
def test_chaos_mixed_faults_byte_identical(monkeypatch):
    """Seeded chaos: random mixes of raise/hang/stall across the device
    choke points must always converge to byte-identical output within a
    bounded wall — never a hang (the thread join is the outer
    watchdog)."""
    import random

    from racon_tpu.ops.poa import PoaEngine

    clean = build_subdel_windows(10, seed=7)
    PoaEngine(backend="jax", log=io.StringIO()).consensus_windows(clean)
    want = [w.consensus for w in clean]

    monkeypatch.setenv("RACON_TPU_DEADLINE_H2D", "0.3")
    monkeypatch.setenv("RACON_TPU_DEADLINE_D2H", "0.3")
    monkeypatch.setenv("RACON_TPU_DEADLINE_DISPATCH", "0.5")
    retry.configure(retry.RetryPolicy(attempts=3, base=0.0, jitter=0.0))
    for seed in range(4):
        rng = random.Random(seed)
        spec = ";".join(
            f"{site}:{rng.randrange(2)}{rng.choice(_CHAOS_ACTIONS)}"
            for site in rng.sample(_CHAOS_SITES,
                                   rng.randint(1, len(_CHAOS_SITES))))
        faults.configure(spec)
        ws = build_subdel_windows(10, seed=7)
        result = {}

        def run(ws=ws, result=result):
            try:
                PoaEngine(backend="jax",
                          log=io.StringIO()).consensus_windows(ws)
                result["ok"] = True
            except Exception as exc:  # typed failure is acceptable...
                result["exc"] = exc

        th = threading.Thread(target=run, daemon=True)
        th.start()
        th.join(45.0)
        # ...a hang is not.
        assert not th.is_alive(), f"seed {seed} hung (spec {spec!r})"
        assert result.get("ok"), \
            f"seed {seed}: {result.get('exc')!r} (spec {spec!r})"
        assert [w.consensus for w in ws] == want, \
            f"seed {seed}: output diverged (spec {spec!r})"
        faults.configure(None)
        watchdog.reset()


# ------------------------------------------------ pipeline stall drill


def _write_inputs(d, n_contigs=2, n_reads=6, clen=300):
    rng = np.random.default_rng(11)
    drafts, reads, paf = [], [], []
    for ci in range(n_contigs):
        truth = BASES[rng.integers(0, 4, clen)]
        draft = mutate_subdel(rng, truth)
        drafts.append(b">c%d\n%s\n" % (ci, draft))
        for i in range(n_reads):
            r = mutate_subdel(rng, truth)
            name = f"c{ci}r{i}"
            reads.append(b">" + name.encode() + b"\n" + r + b"\n")
            paf.append(f"{name}\t{len(r)}\t0\t{len(r)}\t+\tc{ci}"
                       f"\t{len(draft)}\t0\t{len(draft)}"
                       f"\t{min(len(r), len(draft))}"
                       f"\t{max(len(r), len(draft))}\t60")
    (d / "draft.fasta").write_bytes(b"".join(drafts))
    (d / "reads.fasta").write_bytes(b"".join(reads))
    (d / "ovl.paf").write_text("\n".join(paf) + "\n")


def _run_cli(d, *extra):
    from racon_tpu import cli

    class _Capture(io.StringIO):
        pass

    stdout = _Capture()
    stdout.buffer = io.BytesIO()
    err = io.StringIO()
    with contextlib.redirect_stdout(stdout), \
            contextlib.redirect_stderr(err):
        rc = cli.main(["--backend", "jax", *extra,
                       str(d / "reads.fasta"), str(d / "ovl.paf"),
                       str(d / "draft.fasta")])
    return rc, stdout.buffer.getvalue(), err.getvalue()


@pytest.mark.slow
def test_pipeline_stall_detected_and_recovered(tmp_path, monkeypatch):
    """A wedged stage body (hang at pipe/pack) trips the stall detector
    within the window; the abort cascade surfaces PipelineStalled, the
    streaming driver re-polishes the un-retired tail on the host, and
    the output stays byte-identical."""
    _write_inputs(tmp_path)
    rc, base, _ = _run_cli(tmp_path)
    assert rc == 0 and base.count(b">") == 2

    monkeypatch.setenv("RACON_TPU_PIPELINE", "1")
    monkeypatch.setenv("RACON_TPU_STALL_S", "0.5")
    faults.configure("pipe/pack:0!hang=3")
    t0 = time.monotonic()
    rc, out, err = _run_cli(tmp_path)
    assert rc == 0, err
    assert out == base
    assert "stall detected" in err
    snap = obs_metrics.registry().snapshot()
    assert snap["pipe_stall_events"] >= 1
    assert watchdog.health_snapshot()["pipeline_stalls"] >= 1
    # The run must beat a full hang wait-out by a wide margin is not
    # guaranteed (shutdown joins the waking stage), but it must finish.
    assert time.monotonic() - t0 < 30.0


def test_stall_window_env(monkeypatch):
    from racon_tpu.pipeline.stages import stall_window_s
    assert stall_window_s() == 300.0
    monkeypatch.setenv("RACON_TPU_STALL_S", "2.5")
    assert stall_window_s() == 2.5
    monkeypatch.setenv("RACON_TPU_STALL_S", "nope")
    with pytest.raises(ValueError, match="RACON_TPU_STALL_S"):
        stall_window_s()


# --------------------------------------------- ledger release / merge


def test_ledger_release_enables_instant_reclaim(tmp_path):
    from racon_tpu.distributed.ledger import WorkLedger
    d = str(tmp_path / "led")
    led = WorkLedger.open(d, "fp1", n_targets=2, workers=1,
                          lease_s=60.0, n_shards=1)
    a = led.claim_shard("wA")
    assert a is not None and a.name == "shard_0"
    assert led.claim_shard("wB") is None        # live-leased elsewhere
    led.release(a)
    b = led.claim_shard("wB")                   # no lease term wait
    assert b is not None and b.worker == "wB"
    led.release(a)                              # stale nonce: no-op
    led.verify(b)                               # wB's lease untouched
    evs = [e["ev"] for e in led.events()]
    assert "release" in evs
    snap = obs_metrics.registry().snapshot()
    assert snap["dist_releases"] == 1


def test_merge_write_fault_leaves_no_partial_output(tmp_path):
    """The dist/merge!term class of drill at unit scale: a fault mid-
    merge-write must leave NO out.fasta (tmp unlinked), and the redo
    produces the full byte-identical file."""
    from racon_tpu.distributed.ledger import WorkLedger
    from racon_tpu.resilience import checkpoint as ckpt
    d = str(tmp_path / "led")
    led = WorkLedger.open(d, "fp1", n_targets=2, workers=1,
                          lease_s=60.0, n_shards=1)
    claim = led.claim_shard("w0")
    store = ckpt.CheckpointStore.create(led.shard_ckpt_dir(0),
                                        led.shard_fp(0))
    store.commit(0, b"c0", b"AAAA")
    store.commit(1, b"c1", b"CCCC")
    store.close()
    led.complete(claim)
    assert led.claim_merge("w0") is not None

    faults.configure("dist/merge_write:1")      # die on the 2nd blob
    with pytest.raises(faults.InjectedFault):
        led.merge()
    assert not os.path.exists(led.out_path)
    leftovers = [n for n in os.listdir(d) if ".tmp." in n]
    assert leftovers == []

    faults.configure(None)
    total, emitted = led.merge()
    assert emitted == 2
    blob = open(led.out_path, "rb").read()
    assert blob == b">c0\nAAAA\n>c1\nCCCC\n" and total == len(blob)


def test_atomic_writer_clean_and_aborted(tmp_path):
    from racon_tpu.utils.atomicio import atomic_writer
    p = str(tmp_path / "out.bin")
    with atomic_writer(p) as fh:
        fh.write(b"hello")
    assert open(p, "rb").read() == b"hello"
    with pytest.raises(RuntimeError, match="boom"):
        with atomic_writer(p) as fh:
            fh.write(b"garbage")
            raise RuntimeError("boom")
    assert open(p, "rb").read() == b"hello"     # prior bytes intact
    assert [n for n in os.listdir(str(tmp_path)) if ".tmp." in n] == []


# ---------------------------------------------- stragglers + /healthz


def _shard(d, wid, windows, wall, run_fp="fpX"):
    rec = {"schema": 1, "seq": 0, "worker_id": wid, "run_fp": run_fp,
           "unix_time": 0.0, "wall_s": wall, "final": True,
           "metrics": {"poa_windows_total": windows}}
    with open(os.path.join(d, f"worker_{wid}.metrics.jsonl"),
              "w", encoding="utf-8") as fh:
        fh.write(json.dumps(rec) + "\n")


def test_straggler_flagging(tmp_path, monkeypatch):
    from racon_tpu.obs.fleet import FleetObsError, aggregate
    d = str(tmp_path)
    _shard(d, "fast1", 1000, 10.0)   # 100 w/s
    _shard(d, "fast2", 900, 10.0)    # 90 w/s  -> median 90, cutoff 45
    _shard(d, "slow", 100, 10.0)     # 10 w/s  -> flagged
    _shard(d, "merge", 0, 10.0)      # rate 0: merge-only, never flagged
    model = aggregate(d)
    assert model["stragglers"] == ["slow"]
    assert model["workers"]["slow"]["straggler"] is True
    assert model["workers"]["merge"]["straggler"] is False
    assert model["workers"]["fast1"]["straggler"] is False
    monkeypatch.setenv("RACON_TPU_STRAGGLER_FRAC", "0.05")
    assert aggregate(d)["stragglers"] == []
    monkeypatch.setenv("RACON_TPU_STRAGGLER_FRAC", "2.0")
    with pytest.raises(FleetObsError):
        aggregate(d)


def test_straggler_needs_two_positive_rates(tmp_path):
    from racon_tpu.obs.fleet import aggregate
    d = str(tmp_path)
    _shard(d, "only", 100, 10.0)
    _shard(d, "merge", 0, 10.0)
    model = aggregate(d)                 # 1 positive rate: no flags
    assert model["stragglers"] == []


def test_healthz_endpoint():
    from racon_tpu.obs.export import serve_metrics
    state = {"status": "ok", "watchdog_breaches": 0}
    srv = serve_metrics(0, lambda: "# EOF\n",
                        health=lambda: dict(state))
    port = srv.server_address[1]
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=5) as r:
            assert r.status == 200
            assert json.loads(r.read())["status"] == "ok"
        state["status"] = "terminal"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=5)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["status"] == "terminal"
        # Any other path still serves the metrics render.
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5) as r:
            assert r.status == 200 and r.read() == b"# EOF\n"
    finally:
        srv.shutdown()
