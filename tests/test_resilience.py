"""Resilience subsystem tests: retry/backoff, fault injection,
checkpoint/resume (racon_tpu/resilience/, docs/RESILIENCE.md)."""

import contextlib
import io
import json
import os

import numpy as np
import pytest

from racon_tpu.obs import metrics as obs_metrics
from racon_tpu.resilience import checkpoint as ckpt
from racon_tpu.resilience import faults, retry

from synth_windows import BASES, build_subdel_windows, mutate_subdel


@pytest.fixture(autouse=True)
def resilience_sandbox(monkeypatch):
    """Keep the process-global injector/policy/registry out of other
    tests (and other tests' env out of these)."""
    monkeypatch.delenv(retry.ENV_RETRY, raising=False)
    monkeypatch.delenv(faults.ENV_FAULTS, raising=False)
    retry.configure(None)
    faults.configure(None)
    obs_metrics.reset()
    yield
    retry.configure(None)
    faults.configure(None)
    obs_metrics.reset()


# ----------------------------------------------------------- retry policy


def test_backoff_schedule_deterministic():
    a = retry.RetryPolicy(attempts=5, base=0.05, seed=3)
    b = retry.RetryPolicy(attempts=5, base=0.05, seed=3)
    assert a.schedule("h2d/chunk") == b.schedule("h2d/chunk")
    # Jitter is per-site: same policy, different site, different phase.
    assert a.schedule("h2d/chunk") != a.schedule("d2h/chunk")
    # Exponential growth under the cap, within the jitter band.
    sched = a.schedule("h2d/chunk")
    assert len(sched) == 4
    for k, d in enumerate(sched, 1):
        ideal = min(0.05 * 2.0 ** (k - 1), a.max_delay)
        assert ideal * 0.9 <= d <= ideal * 1.1


def test_backoff_cap_and_no_jitter():
    p = retry.RetryPolicy(attempts=10, base=1.0, multiplier=4.0,
                          max_delay=2.5, jitter=0.0)
    assert p.schedule()[-1] == 2.5
    assert p.delay(1) == 1.0            # jitter=0: exact


def test_policy_rejects_zero_attempts():
    with pytest.raises(ValueError, match="invalid attempts"):
        retry.RetryPolicy(attempts=0)


def test_default_policy_env(monkeypatch):
    monkeypatch.setenv(retry.ENV_RETRY, "attempts=7,base=0.2,seed=9")
    retry.configure(None)
    pol = retry.default_policy()
    assert (pol.attempts, pol.base, pol.seed) == (7, 0.2, 9)
    monkeypatch.setenv(retry.ENV_RETRY, "attempts")
    retry.configure(None)
    with pytest.raises(ValueError, match="invalid RACON_TPU_RETRY"):
        retry.default_policy()


# ------------------------------------------------------------- retry.call


def test_call_recovers_from_transient_failures():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("link hiccup")
        return 42

    pol = retry.RetryPolicy(attempts=4, base=0.0, jitter=0.0)
    assert retry.call("t/site", flaky, policy=pol) == 42
    assert len(calls) == 3
    snap = obs_metrics.registry().snapshot()
    assert snap["res_retry_total"] == 2
    assert snap["res_retry_site_t_site"] == 2


def test_call_propagates_nontransient_immediately():
    calls = []

    def buggy():
        calls.append(1)
        raise KeyError("logic error")

    with pytest.raises(KeyError):
        retry.call("t/site", buggy,
                   policy=retry.RetryPolicy(attempts=4, base=0.0))
    assert len(calls) == 1
    assert "res_retry_total" not in obs_metrics.registry().snapshot()


def test_call_exhaustion_degradation_signal():
    def always_down():
        raise TimeoutError("still down")

    pol = retry.RetryPolicy(attempts=3, base=0.0, jitter=0.0)
    with pytest.raises(retry.RetryExhausted) as ei:
        retry.call("d2h/chunk", always_down, policy=pol)
    assert ei.value.site == "d2h/chunk"
    assert ei.value.attempts == 3
    assert isinstance(ei.value.__cause__, TimeoutError)
    snap = obs_metrics.registry().snapshot()
    assert snap["res_retry_exhausted"] == 1
    assert snap["res_retry_total"] == 2     # last try isn't "retried"


def test_call_runs_injector_inside_retry_loop():
    """The acceptance scenario: a fault plan hitting the first N call
    indices at a site is absorbed by N retries of one logical call."""
    faults.configure("h2d/chunk:0,1,2")
    pol = retry.RetryPolicy(attempts=4, base=0.0, jitter=0.0)
    assert retry.call("h2d/chunk", lambda: "ok", policy=pol) == "ok"
    snap = obs_metrics.registry().snapshot()
    assert snap["res_retry_total"] == 3
    assert snap["res_fault_injected_total"] == 3


# --------------------------------------------------------- fault injector


def test_injector_explicit_indices():
    inj = faults.FaultInjector("x/y:0,2")
    with pytest.raises(faults.InjectedFault) as ei:
        inj.check("x/y")
    assert (ei.value.site, ei.value.index) == ("x/y", 0)
    inj.check("x/y")                        # index 1: clean
    with pytest.raises(faults.InjectedFault):
        inj.check("x/y")                    # index 2
    inj.check("other/site")                 # unlisted site: never fires
    assert inj.counts() == {"x/y": 3, "other/site": 1}
    assert [f[1] for f in inj.fired] == [0, 2]


def test_injector_probability_is_seed_deterministic():
    def pattern(seed):
        inj = faults.FaultInjector(f"s:p=0.5;seed={seed}")
        out = []
        for _ in range(64):
            try:
                inj.check("s")
                out.append(0)
            except faults.InjectedFault:
                out.append(1)
        return out

    assert pattern(1) == pattern(1)
    assert pattern(1) != pattern(2)
    assert 10 < sum(pattern(1)) < 54        # roughly fair coin


def test_injector_spec_errors():
    for bad in ("h2d/chunk", "s:p=1.5", "s:x,y", "s:0!explode",
                "seed=abc", ":0"):
        with pytest.raises(faults.FaultSpecError):
            faults.FaultInjector(bad)


def test_maybe_fault_unarmed_is_noop(monkeypatch):
    faults.configure(None)
    faults.maybe_fault("h2d/chunk")         # no injector: must not raise


# ------------------------------------------------------------- checkpoint


def test_checkpoint_roundtrip(tmp_path):
    d = str(tmp_path / "ck")
    with ckpt.CheckpointStore.create(d, "fp1") as store:
        store.commit(0, b"c0 LN:i:5", b"ACGTA")
        store.commit_dropped(1)
        store.commit(2, b"c2", b"TTT")

    res = ckpt.CheckpointStore.resume(d, "fp1")
    assert sorted(res.committed) == [0, 1, 2]
    assert res.read_emitted(0) == b">c0 LN:i:5\nACGTA\n"
    assert res.read_emitted(1) is None
    assert res.read_emitted(2) == b">c2\nTTT\n"
    res.close()
    snap = obs_metrics.registry().snapshot()
    assert snap["res_ckpt_commits"] == 3
    assert snap["res_ckpt_resumes"] == 1


def test_checkpoint_torn_tail_and_orphan_shard_recovery(tmp_path):
    d = str(tmp_path / "ck")
    store = ckpt.CheckpointStore.create(d, "fp1")
    store.commit(0, b"c0", b"AAAA")
    store.commit(1, b"c1", b"CCCC")
    store.close()
    # Crash between shard append and manifest append: orphaned shard
    # bytes plus a torn (newline-less, half-written) manifest record.
    with open(store.shard_path, "ab") as fh:
        fh.write(b">c2\nGG")
    with open(store.manifest_path, "ab") as fh:
        fh.write(b'{"ev": "contig", "tid": 2, "off')

    res = ckpt.CheckpointStore.resume(d, "fp1")
    assert sorted(res.committed) == [0, 1]
    assert res.read_emitted(1) == b">c1\nCCCC\n"
    # Shard truncated back to the last referenced byte...
    assert os.path.getsize(res.shard_path) == len(b">c0\nAAAA\n"
                                                  b">c1\nCCCC\n")
    # ...and the manifest rewritten to the valid prefix.
    lines = open(res.manifest_path, "rb").read().splitlines()
    assert len(lines) == 3 and json.loads(lines[0])["ev"] == "begin"
    res.close()


class _Died(BaseException):
    """Stand-in for os._exit in in-process crash drills — BaseException
    so no library except-clause can swallow the 'death'."""


@pytest.fixture
def soft_crash(monkeypatch):
    """Intercept the injector's hard-exit seam so kill/torn faults are
    testable in-process; yields the exception type the 'death' raises."""
    monkeypatch.setattr(faults, "hard_exit",
                        lambda code: (_ for _ in ()).throw(_Died(code)))
    return _Died


def test_first_commit_fsyncs_directory(tmp_path, monkeypatch):
    """The crash-consistency fix: file fsync alone doesn't make a fresh
    file's directory entry durable, so the first append after creating
    the store must also fsync the directory — and later commits must
    not keep paying for it."""
    from racon_tpu.utils import atomicio
    synced = []
    monkeypatch.setattr(atomicio, "fsync_dir",
                        lambda p: synced.append(os.path.abspath(p)))
    d = str(tmp_path / "ck")
    store = ckpt.CheckpointStore.create(d, "fp1")    # begin header
    store.commit(0, b"c0", b"AAAA")                  # first commit
    assert synced.count(os.path.abspath(d)) >= 2     # meta + appends
    synced.clear()
    store.commit(1, b"c1", b"CCCC")
    store.commit_dropped(2)
    assert synced == []          # directory entry already durable
    store.close()


def test_kill_between_appends_leaves_resumable_store(tmp_path,
                                                     soft_crash):
    """Eviction in the mid-commit window (after the shard append,
    before the manifest record): the orphaned shard bytes are discarded
    on resume and only that contig recomputes."""
    faults.configure("ckpt/manifest:1!kill")
    d = str(tmp_path / "ck")
    store = ckpt.CheckpointStore.create(d, "fp1")
    store.commit(0, b"c0", b"AAAA")
    with pytest.raises(soft_crash):
        store.commit(1, b"c1", b"CCCC")
    store.close()
    # c1's shard bytes landed, its manifest record didn't.
    assert b">c1\n" in open(store.shard_path, "rb").read()
    faults.configure(None)
    res = ckpt.CheckpointStore.resume(d, "fp1")
    assert sorted(res.committed) == [0]
    assert os.path.getsize(res.shard_path) == len(b">c0\nAAAA\n")
    res.commit(1, b"c1", b"CCCC")                    # recompute works
    assert res.read_emitted(1) == b">c1\nCCCC\n"
    res.close()


def test_torn_manifest_fault_roundtrip(tmp_path, soft_crash):
    """The torn action at ckpt/manifest writes *half* the record
    durably then dies — resume must truncate to the last valid record
    and rewrite the manifest clean."""
    faults.configure("ckpt/manifest:1!torn")
    d = str(tmp_path / "ck")
    store = ckpt.CheckpointStore.create(d, "fp1")
    store.commit(0, b"c0", b"AAAA")
    with pytest.raises(soft_crash):
        store.commit(1, b"c1", b"CCCC")
    store.close()
    raw = open(store.manifest_path, "rb").read()
    assert not raw.endswith(b"\n")       # genuinely torn tail
    faults.configure(None)
    res = ckpt.CheckpointStore.resume(d, "fp1")
    assert sorted(res.committed) == [0]
    lines = open(res.manifest_path, "rb").read()
    assert lines.endswith(b"\n") and lines.count(b"\n") == 2
    res.close()


def test_checkpoint_fingerprint_mismatch_refuses(tmp_path):
    d = str(tmp_path / "ck")
    ckpt.CheckpointStore.create(d, "fp1").close()
    with pytest.raises(ckpt.CheckpointError, match="refusing to resume"):
        ckpt.CheckpointStore.resume(d, "fp2")
    with pytest.raises(ckpt.CheckpointError, match="unreadable"):
        ckpt.CheckpointStore.resume(str(tmp_path / "nope"), "fp1")


def test_run_fingerprint_sensitivity(tmp_path):
    p = tmp_path / "in.fasta"
    p.write_bytes(b">a\nACGT\n")
    base = ckpt.run_fingerprint({"match": 5}, [str(p)])
    assert base == ckpt.run_fingerprint({"match": 5}, [str(p)])
    assert base != ckpt.run_fingerprint({"match": 6}, [str(p)])
    p.write_bytes(b">a\nACGA\n")
    assert base != ckpt.run_fingerprint({"match": 5}, [str(p)])


# ----------------------------------- v2 segmented manifests (docs/AVA.md)


def test_v2_roundtrip_segments_and_dropped(tmp_path):
    """A segment_targets=4 store amortizes 5 commits (one dropped) into
    2 run-length manifest records, and resume expands them back into
    per-target records indistinguishable from v1's."""
    d = str(tmp_path / "ck")
    with ckpt.CheckpointStore.create(d, "fp1",
                                     segment_targets=4) as store:
        store.commit(0, b"r0", b"ACGTA")
        store.commit(1, b"r1", b"TTT")
        store.commit_dropped(2)
        store.commit(3, b"r3", b"GG")       # seals segment [0, 4)
        store.commit(4, b"r4", b"CCCC")     # tail: sealed at close()

    recs = [json.loads(x) for x in
            open(os.path.join(d, ckpt.MANIFEST_NAME),
                 "rb").read().splitlines()]
    assert recs[0]["manifest"] == ckpt.MANIFEST_V2
    assert recs[0]["seg_targets"] == 4
    assert [r["ev"] for r in recs[1:]] == ["seg", "seg"]
    assert recs[1] == {"ev": "seg", "start": 0, "end": 4, "offset": 0,
                       "lengths": [10, 8, 0, 7]}   # >name\ndata\n blobs

    res = ckpt.CheckpointStore.resume(d, "fp1")
    assert res.segment_targets == 4         # mode from the header
    assert sorted(res.committed) == [0, 1, 2, 3, 4]
    assert res.read_emitted(0) == b">r0\nACGTA\n"
    assert res.read_emitted(2) is None
    assert res.read_emitted(4) == b">r4\nCCCC\n"
    # Unsealed commits still serve live bytes (flushed, not yet fsync'd).
    res.commit(5, b"r5", b"AA")
    assert res.read_emitted(5) == b">r5\nAA\n"
    res.close()
    snap = obs_metrics.registry().snapshot()
    assert snap["res_ckpt_commits"] == 6
    assert snap["res_ckpt_seals"] == 3      # full, close, post-resume close


def test_v2_discontinuity_seals_segment(tmp_path):
    """A target-id gap (shard bounds are contiguous, but a worker can
    skip ahead after a steal) must seal the open segment — run-length
    records cannot span a hole."""
    d = str(tmp_path / "ck")
    with ckpt.CheckpointStore.create(d, "fp1",
                                     segment_targets=8) as store:
        store.commit(0, b"r0", b"AA")
        store.commit(1, b"r1", b"CC")
        store.commit(5, b"r5", b"GG")       # gap: seals [0, 2) first
    recs = [json.loads(x) for x in
            open(os.path.join(d, ckpt.MANIFEST_NAME),
                 "rb").read().splitlines()]
    segs = [(r["start"], r["end"]) for r in recs if r["ev"] == "seg"]
    assert segs == [(0, 2), (5, 6)]
    res = ckpt.CheckpointStore.resume(d, "fp1")
    assert sorted(res.committed) == [0, 1, 5]
    assert res.read_emitted(5) == b">r5\nGG\n"
    res.close()


def test_v2_crash_loses_at_most_unsealed_segment(tmp_path):
    """An abandoned store (no close, so no tail seal) forfeits exactly
    the unsealed segment: its flushed shard bytes are truncated on
    resume and those targets recompute."""
    d = str(tmp_path / "ck")
    store = ckpt.CheckpointStore.create(d, "fp1", segment_targets=2)
    store.commit(0, b"r0", b"AAAA")
    store.commit(1, b"r1", b"CCCC")         # seals [0, 2)
    store.commit(2, b"r2", b"GGGG")         # unsealed; flushed to shard
    sealed_end = store.committed[1]["offset"] + \
        store.committed[1]["length"]
    assert os.path.getsize(store.shard_path) > sealed_end
    # No close(): simulate eviction mid-segment.
    res = ckpt.CheckpointStore.resume(d, "fp1")
    assert sorted(res.committed) == [0, 1]
    assert os.path.getsize(res.shard_path) == sealed_end
    res.commit(2, b"r2", b"GGGG")           # recompute works
    res.close()
    fin = ckpt.CheckpointStore.resume(d, "fp1")
    assert fin.read_emitted(2) == b">r2\nGGGG\n"
    fin.close()


def test_v2_torn_seal_fault_at_segment_boundary(tmp_path, soft_crash):
    """The ckpt/manifest torn drill on a v2 store lands exactly on a
    segment seal: half the segment record becomes durable, recovery
    drops it and truncates the shard back to the last sealed segment."""
    faults.configure("ckpt/manifest:1!torn")
    d = str(tmp_path / "ck")
    store = ckpt.CheckpointStore.create(d, "fp1", segment_targets=2)
    store.commit(0, b"r0", b"AAAA")
    store.commit(1, b"r1", b"CCCC")         # seal #1 (fault index 0)
    store.commit(2, b"r2", b"GGGG")
    with pytest.raises(soft_crash):
        store.commit(3, b"r3", b"TTTT")     # seal #2 tears and dies
    raw = open(store.manifest_path, "rb").read()
    assert not raw.endswith(b"\n")          # genuinely torn tail
    faults.configure(None)
    res = ckpt.CheckpointStore.resume(d, "fp1")
    assert sorted(res.committed) == [0, 1]
    assert os.path.getsize(res.shard_path) == len(b">r0\nAAAA\n"
                                                  b">r1\nCCCC\n")
    clean = open(res.manifest_path, "rb").read()
    assert clean.endswith(b"\n") and clean.count(b"\n") == 2
    res.commit(2, b"r2", b"GGGG")
    res.commit(3, b"r3", b"TTTT")
    res.close()
    fin = ckpt.CheckpointStore.resume(d, "fp1")
    assert sorted(fin.committed) == [0, 1, 2, 3]
    assert fin.read_emitted(3) == b">r3\nTTTT\n"
    fin.close()


def test_v2_compaction_byte_identity(tmp_path, monkeypatch):
    """Compaction merges adjacent contiguous segments and atomically
    rewrites the manifest; recovery from the compacted store must be
    byte-identical to its uncompacted twin."""
    def fill(d, compact):
        monkeypatch.setenv(ckpt.ENV_AVA_COMPACT, compact)
        with ckpt.CheckpointStore.create(d, "fp1",
                                         segment_targets=2) as store:
            for tid in range(8):
                store.commit(tid, b"r%d" % tid, b"ACGT" * (tid + 1))

    a = str(tmp_path / "compacted")
    b = str(tmp_path / "plain")
    fill(a, "2")        # compact every 2 seals
    fill(b, "0")        # never compact
    monkeypatch.delenv(ckpt.ENV_AVA_COMPACT)

    n_lines = lambda d: open(os.path.join(d, ckpt.MANIFEST_NAME),
                             "rb").read().count(b"\n")
    assert n_lines(b) == 5                  # header + 4 seg records
    assert n_lines(a) < n_lines(b)

    ra = ckpt.CheckpointStore.resume(a, "fp1")
    rb = ckpt.CheckpointStore.resume(b, "fp1")
    assert sorted(ra.committed) == sorted(rb.committed) == list(range(8))
    for tid in range(8):
        assert ra.read_emitted(tid) == rb.read_emitted(tid)
    ra.close()
    rb.close()
    snap = obs_metrics.registry().snapshot()
    assert snap["res_ckpt_compactions"] >= 1


def test_v1_stores_unaffected_by_v2_code(tmp_path):
    """segment_targets=0 (the kC default) writes a byte-for-byte v1
    manifest: per-target records carrying names, no header mode flag."""
    d = str(tmp_path / "ck")
    with ckpt.CheckpointStore.create(d, "fp1",
                                     segment_targets=0) as store:
        store.commit(0, b"c0", b"ACGT")
    recs = [json.loads(x) for x in
            open(os.path.join(d, ckpt.MANIFEST_NAME),
                 "rb").read().splitlines()]
    assert "manifest" not in recs[0]
    assert recs[1]["name"] == "c0"
    res = ckpt.CheckpointStore.resume(d, "fp1")
    assert res.segment_targets == 0
    assert res.read_emitted(0) == b">c0\nACGT\n"
    res.close()


# ------------------------------------------- degradation + CLI integration


def test_degradation_to_host_is_bit_identical():
    """Retry exhaustion at a transfer site must not change output: the
    chunk's windows reroute to the host path, which is bit-identical to
    the device path by design."""
    from racon_tpu.ops.poa import PoaEngine

    clean = build_subdel_windows(8, seed=5)
    PoaEngine(backend="jax", log=io.StringIO()).consensus_windows(clean)

    retry.configure(retry.RetryPolicy(attempts=2, base=0.0, jitter=0.0))
    faults.configure("h2d/chunk:p=1.0")     # every upload attempt fails
    degraded = build_subdel_windows(8, seed=5)
    log = io.StringIO()
    PoaEngine(backend="jax", log=log).consensus_windows(degraded)

    assert [w.consensus for w in degraded] == \
        [w.consensus for w in clean]
    assert "host path" in log.getvalue()
    snap = obs_metrics.registry().snapshot()
    assert snap["res_retry_exhausted"] >= 1
    assert snap["res_degraded_windows"] >= 1


def test_verbose_timing_path_h2d_retry_envelope():
    """A transient upload fault on the chunk path is retried with
    unchanged output: a one-shot h2d/chunk fault on the put_chunk_bufs
    upload is absorbed by one retry inside its watchdog envelope,
    without exhausting the budget or degrading to the host. The name
    predates the single chunk dispatch path."""
    from racon_tpu.ops.poa import PoaEngine

    clean = build_subdel_windows(6, seed=7)
    PoaEngine(backend="jax", log=io.StringIO()).consensus_windows(clean)
    obs_metrics.reset()

    retry.configure(retry.RetryPolicy(attempts=3, base=0.0, jitter=0.0))
    faults.configure("h2d/chunk:0")
    faulted = build_subdel_windows(6, seed=7)
    PoaEngine(backend="jax", log=io.StringIO()).consensus_windows(faulted)

    assert [w.consensus for w in faulted] == \
        [w.consensus for w in clean]
    snap = obs_metrics.registry().snapshot()
    assert snap["res_fault_injected_total"] >= 1
    assert snap["res_fault_site_h2d_chunk"] >= 1
    assert snap["res_retry_total"] >= 1
    assert "res_retry_exhausted" not in snap


@pytest.mark.parametrize("status,transient", [
    ("RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem", False),
    ("INVALID_ARGUMENT: block shape (4, 128) is not tiled", False),
    ("UNIMPLEMENTED: failed to legalize arith.maxsi", False),
    ("FAILED_PRECONDITION: program rejected", False),
    ("INTERNAL: transfer stream reset", True),
    ("UNAVAILABLE: device busy", True),
])
def test_runtime_error_status_classes(status, transient):
    """Compiler and device refusals are not transient: the same call
    would be refused again. Other runtime errors still retry."""
    from jax.errors import JaxRuntimeError
    assert retry.is_transient(JaxRuntimeError(status)) is transient


@pytest.mark.parametrize("sched", ["0", "1"])
def test_compile_refusal_in_chunk_dispatch_raises(monkeypatch, sched):
    """A compile refusal inside the chunk dispatch reaches the caller:
    one attempt, no retry, no host degradation — the run fails instead
    of quietly finishing on the host path."""
    from jax.errors import JaxRuntimeError
    from racon_tpu.ops import device_poa
    from racon_tpu.ops.poa import PoaEngine
    from racon_tpu.sched import rounds

    calls = []

    def refuse(*args, **kwargs):
        calls.append(1)
        raise JaxRuntimeError(
            "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem "
            "while allocating on stack for %fw_dirs_band")

    monkeypatch.setenv("RACON_TPU_SCHED", sched)
    monkeypatch.setattr(device_poa, "device_chunk_packed", refuse)
    monkeypatch.setattr(rounds, "sched_unpack", refuse)
    retry.configure(retry.RetryPolicy(attempts=4, base=0.0, jitter=0.0))
    log = io.StringIO()
    with pytest.raises(JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        PoaEngine(backend="jax", log=log).consensus_windows(
            build_subdel_windows(4, seed=3))
    assert calls == [1]
    snap = obs_metrics.registry().snapshot()
    assert "res_retry_total" not in snap
    assert "res_degraded_windows" not in snap
    assert "host path" not in log.getvalue()


def test_first_compile_runs_outside_the_deadline():
    """A ``compiles`` call runs the first call of each signature
    without the watchdog; later calls with that signature are guarded,
    and a new shape or static value is a new (unguarded) compile."""
    import time
    from racon_tpu.resilience.watchdog import DispatchTimeout

    def jitted(x, *, secs):  # stands in for a compiling jit dispatch
        time.sleep(secs)

    pol = retry.RetryPolicy(attempts=1)
    small, big = np.zeros(4), np.zeros(8)
    assert retry.call("dispatch/chunk", jitted, small, secs=0.3,
                      policy=pol, deadline_s=0.05, compiles=True) is None
    with pytest.raises(retry.RetryExhausted) as ei:
        retry.call("dispatch/chunk", jitted, small, secs=0.3, policy=pol,
                   deadline_s=0.05, compiles=True)
    assert isinstance(ei.value.__cause__, DispatchTimeout)
    assert retry.call("dispatch/chunk", jitted, big, secs=0.3,
                      policy=pol, deadline_s=0.05, compiles=True) is None
    assert retry.call("dispatch/chunk", jitted, small, secs=0.2,
                      policy=pol, deadline_s=0.05, compiles=True) is None


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


def test_cli_resume_byte_identity(tmp_path):
    """The resume contract on the CLI surface: a completed checkpointed
    run re-emits byte-identically from the shard; a truncated manifest
    (simulated kill) resumes and still matches; a changed config
    refuses to resume."""
    _write_inputs(tmp_path)
    ck = str(tmp_path / "ck")

    rc, base, _ = _run_cli(tmp_path)
    assert rc == 0 and base.count(b">") == 2

    rc, fresh, _ = _run_cli(tmp_path, "--checkpoint-dir", ck)
    assert rc == 0 and fresh == base

    rc, resumed, err = _run_cli(tmp_path, "--checkpoint-dir", ck,
                                "--resume")
    assert rc == 0 and resumed == base
    assert "resuming: 2 contig(s)" in err

    # Kill simulation: drop the last manifest record; its contig must
    # recompute on resume with identical bytes.
    man = os.path.join(ck, ckpt.MANIFEST_NAME)
    lines = open(man, "rb").read().splitlines(keepends=True)
    open(man, "wb").write(b"".join(lines[:-1]))
    rc, partial, _ = _run_cli(tmp_path, "--checkpoint-dir", ck,
                              "--resume")
    assert rc == 0 and partial == base

    rc, _, err = _run_cli(tmp_path, "--checkpoint-dir", ck, "--resume",
                          "--match", "6")
    assert rc == 1 and "refusing to resume" in err


def test_cli_sigterm_mid_commit_resumes_byte_identical(tmp_path):
    """SIGTERM delivered in the mid-commit window (between the shard
    append and the manifest append, via the ckpt/manifest term action):
    the run exits 143, the half-committed contig's shard bytes are
    orphaned, and --resume still reproduces the serial bytes exactly."""
    _write_inputs(tmp_path)
    ck = str(tmp_path / "ck")
    rc, base, _ = _run_cli(tmp_path)
    assert rc == 0

    faults.configure("ckpt/manifest:1!term")
    rc, _, err = _run_cli(tmp_path, "--checkpoint-dir", ck)
    assert rc == 143, err
    assert "interrupted (signal 15); 1 contig(s) committed" in err
    # The second contig's shard bytes landed without a manifest record.
    shard_size = os.path.getsize(os.path.join(ck, ckpt.SHARD_NAME))
    man = open(os.path.join(ck, ckpt.MANIFEST_NAME), "rb").read()
    recs = [json.loads(x) for x in man.splitlines()]
    committed = [r for r in recs if r.get("ev") == "contig"]
    assert len(committed) == 1
    end = committed[0]["offset"] + committed[0]["length"]
    assert shard_size > end, "expected orphaned mid-commit shard bytes"

    faults.configure(None)
    rc, out, _ = _run_cli(tmp_path, "--checkpoint-dir", ck, "--resume")
    assert rc == 0 and out == base


def test_cli_resume_requires_checkpoint_dir(tmp_path):
    _write_inputs(tmp_path)
    rc, _, err = _run_cli(tmp_path, "--resume")
    assert rc == 1 and "--resume requires --checkpoint-dir" in err


@pytest.mark.ava
def test_ava_golden_resume_byte_identity(tmp_path):
    """Resume byte-identity on the reference acceptance inputs (the ava
    golden config tests/test_polisher.py gates on): full run vs
    checkpointed run vs resumed run, all byte-identical."""
    d = "/root/reference/test/data"
    if not os.path.isdir(d):
        pytest.skip("reference dataset not available")
    from racon_tpu import cli

    def run(*extra):
        stdout = io.StringIO()
        stdout.buffer = io.BytesIO()
        with contextlib.redirect_stdout(stdout), \
                contextlib.redirect_stderr(io.StringIO()):
            rc = cli.main([
                "--backend", "jax", *extra,
                os.path.join(d, "sample_reads.fastq.gz"),
                os.path.join(d, "sample_overlaps.paf.gz"),
                os.path.join(d, "sample_layout.fasta.gz")])
        assert rc == 0
        return stdout.buffer.getvalue()

    ck = str(tmp_path / "ck")
    base = run()
    assert run("--checkpoint-dir", ck) == base
    assert run("--checkpoint-dir", ck, "--resume") == base
