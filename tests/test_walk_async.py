"""Decoupled asynchronous column walk tests (ISSUE 14).

The fused forward+walk chunk dispatch splits into device_chunk_fwd
(ops/device_poa.py) + walk_chunk_packed (ops/colwalk.py); the streaming
executor's walk stage overlaps chunk N's walk with chunk N+1's forward
dispatch. These tests pin the contract: byte-identity of the split
against the fused program at the ops level and through the stream (the
4-gate SCHED x ADAPTIVE x PIPELINE x WALK_ASYNC matrix), the
``dispatch/walk`` fault/retry envelope (FLT002), stall detection on the
walk stage with host re-polish, and the automatic fused fallbacks.
"""

import numpy as np
import pytest

from racon_tpu.obs import metrics as obs_metrics
from racon_tpu.pipeline.streaming import stream_consensus
from racon_tpu.resilience import faults, retry, watchdog

from synth_windows import build_stream_windows


_ENVS = ("RACON_TPU_WALK_ASYNC", "RACON_TPU_WALK_QUEUE",
         "RACON_TPU_SCHED", "RACON_TPU_ADAPTIVE", "RACON_TPU_PIPELINE",
         "RACON_TPU_STALL_S", "RACON_TPU_WALK_K")


@pytest.fixture(autouse=True)
def walk_sandbox(monkeypatch):
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


def _chunk_fixture(seed=1):
    """One packed ChunkPlan plus the engine's dispatch parameters."""
    from racon_tpu.ops.poa import PoaEngine
    eng = PoaEngine(backend="jax")
    ws = [w for w in build_stream_windows(12, seed=seed) if w.n_layers >= 2]
    dev, _host, lq_max, la_max = eng._partition_device(ws)
    sp = eng._plan_device_slice(dev, lq_max, la_max)
    assert sp.groups
    plan = eng._make_chunk_plan(sp, sp.groups[0])
    rounds = eng.refine_rounds + 1
    return eng, plan, eng._round_scales(rounds), rounds


# -------------------------------------------------- ops-level parity


def test_walk_unit_parity_fused_vs_decoupled():
    """dispatch_chunk_fwd + dispatch_walk must produce the exact packed
    output bytes of the fused dispatch_chunk — the split composes the
    same traced bodies, so the d2h buffer is the equality witness."""
    from racon_tpu.ops.colwalk import dispatch_walk
    from racon_tpu.ops.device_poa import dispatch_chunk, dispatch_chunk_fwd

    eng, plan, scales, rounds = _chunk_fixture()
    fused = dispatch_chunk(plan, match=eng.match, mismatch=eng.mismatch,
                           gap=eng.gap, ins_scale=scales, rounds=rounds)
    fwd_out, meta = dispatch_chunk_fwd(
        plan, match=eng.match, mismatch=eng.mismatch, gap=eng.gap,
        ins_scale=scales, rounds=rounds)
    split = dispatch_walk(plan, fwd_out, meta)
    assert bytes(np.asarray(split)) == bytes(np.asarray(fused))


def test_walk_unit_parity_adaptive(monkeypatch):
    """Same witness with the adaptive while_loop in the shared round
    prefix — the fwd program embeds the identical early-exit chain."""
    from racon_tpu.ops.colwalk import dispatch_walk
    from racon_tpu.ops.device_poa import dispatch_chunk, dispatch_chunk_fwd

    monkeypatch.setenv("RACON_TPU_ADAPTIVE", "1")
    eng, plan, scales, rounds = _chunk_fixture(seed=5)
    fused = dispatch_chunk(plan, match=eng.match, mismatch=eng.mismatch,
                           gap=eng.gap, ins_scale=scales, rounds=rounds)
    fwd_out, meta = dispatch_chunk_fwd(
        plan, match=eng.match, mismatch=eng.mismatch, gap=eng.gap,
        ins_scale=scales, rounds=rounds)
    split = dispatch_walk(plan, fwd_out, meta)
    assert bytes(np.asarray(split)) == bytes(np.asarray(fused))


def test_dispatch_walk_fault_absorbed_by_retry():
    """An injected fault at the ``dispatch/walk`` site is transient:
    one retry re-dispatches and the output bytes are unchanged."""
    from racon_tpu.ops.colwalk import dispatch_walk
    from racon_tpu.ops.device_poa import dispatch_chunk, dispatch_chunk_fwd

    eng, plan, scales, rounds = _chunk_fixture()
    fused = dispatch_chunk(plan, match=eng.match, mismatch=eng.mismatch,
                           gap=eng.gap, ins_scale=scales, rounds=rounds)
    fwd_out, meta = dispatch_chunk_fwd(
        plan, match=eng.match, mismatch=eng.mismatch, gap=eng.gap,
        ins_scale=scales, rounds=rounds)
    faults.configure("dispatch/walk:0")
    split = dispatch_walk(plan, fwd_out, meta)
    assert bytes(np.asarray(split)) == bytes(np.asarray(fused))
    snap = obs_metrics.registry().snapshot()
    assert snap["res_fault_site_dispatch_walk"] == 1
    assert snap["res_retry_site_dispatch_walk"] == 1
    assert snap.get("res_retry_exhausted", 0) == 0


# ------------------------------------------------- stream differential


def _stream(windows, chunk=8, depth=2):
    from racon_tpu.ops.poa import PoaEngine
    ranges = list(stream_consensus(PoaEngine(backend="jax"), windows,
                                   chunk=chunk, depth=depth))
    flat = [i for s, e in ranges for i in range(s, e)]
    assert flat == list(range(len(windows)))
    return [w.consensus for w in windows]


def test_stream_walk_async_bit_identical_and_counted(monkeypatch):
    """On the decoupled path (fixed rounds, multi-chunk stream) the
    polished consensi match the serial engine bit for bit, and the
    walk_* telemetry proves the decoupled stage actually ran."""
    from racon_tpu.ops.poa import PoaEngine

    monkeypatch.setenv("RACON_TPU_SCHED", "0")
    serial = build_stream_windows(24, seed=3)
    PoaEngine(backend="jax").consensus_windows(serial)
    ref = [w.consensus for w in serial]

    monkeypatch.setenv("RACON_TPU_WALK_ASYNC", "1")
    obs_metrics.reset()
    assert _stream(build_stream_windows(24, seed=3)) == ref
    snap = obs_metrics.registry().snapshot()
    assert snap["walk_async_enabled"] == 1
    assert snap["walk_dispatches"] >= 1
    assert snap["walk_seconds"] > 0
    assert snap["walk_fused_chunks"] >= 1      # the last chunk
    assert "walk_queue_peak" in snap
    assert obs_metrics.walk_extras()  # non-empty after a recorded run

    monkeypatch.setenv("RACON_TPU_WALK_ASYNC", "0")
    obs_metrics.reset()
    assert _stream(build_stream_windows(24, seed=3)) == ref
    snap = obs_metrics.registry().snapshot()
    assert snap["walk_async_enabled"] == 0
    assert snap["walk_dispatches"] == 0


@pytest.mark.parametrize("sched", ["0", "1"])
@pytest.mark.parametrize("adaptive", ["0", "1"])
def test_stream_matrix_bit_identical(monkeypatch, sched, adaptive):
    """SCHED x ADAPTIVE x WALK_ASYNC: every combination streams to the
    serial engine's bytes. Under SCHED=1 the executor must fall back to
    fused dispatches (per-round flag pulls consume every walk)."""
    from racon_tpu.ops.poa import PoaEngine

    monkeypatch.setenv("RACON_TPU_SCHED", sched)
    monkeypatch.setenv("RACON_TPU_ADAPTIVE", adaptive)
    serial = build_stream_windows(16, seed=9)
    PoaEngine(backend="jax").consensus_windows(serial)
    ref = [w.consensus for w in serial]
    for walk in ("1", "0"):
        monkeypatch.setenv("RACON_TPU_WALK_ASYNC", walk)
        obs_metrics.reset()
        assert _stream(build_stream_windows(16, seed=9)) == ref, \
            f"SCHED={sched} ADAPTIVE={adaptive} WALK_ASYNC={walk}"
        snap = obs_metrics.registry().snapshot()
        if sched == "1" or walk == "0":
            assert snap.get("walk_dispatches", 0) == 0


# --------------------------------------------------- fused fallbacks


def test_single_chunk_stream_falls_back_fused(monkeypatch):
    """A one-chunk stream has nothing to overlap with: the last-chunk
    rule keeps it fused and the gauges say so."""
    monkeypatch.setenv("RACON_TPU_SCHED", "0")
    monkeypatch.setenv("RACON_TPU_WALK_ASYNC", "1")
    ws = build_stream_windows(8, seed=13)
    _stream(ws, chunk=32)
    snap = obs_metrics.registry().snapshot()
    assert snap["walk_dispatches"] == 0
    assert snap["walk_fused_chunks"] >= 1
    assert snap["walk_async_enabled"] == 1


def test_walk_queue_zero_disables_decoupling(monkeypatch):
    """RACON_TPU_WALK_QUEUE=0 is the queue-knob spelling of off."""
    monkeypatch.setenv("RACON_TPU_SCHED", "0")
    monkeypatch.setenv("RACON_TPU_WALK_ASYNC", "1")
    monkeypatch.setenv("RACON_TPU_WALK_QUEUE", "0")
    _stream(build_stream_windows(24, seed=3))
    snap = obs_metrics.registry().snapshot()
    assert snap["walk_dispatches"] == 0
    assert snap["walk_async_enabled"] == 0


# ------------------------------------------------------- stall drill


@pytest.mark.slow
def test_walk_stage_stall_detected_and_recovered(monkeypatch):
    """A wedged walk stage (hang at pipe/walk) trips the stall detector
    within the window; the abort cascade surfaces PipelineStalled and
    the streaming driver re-polishes the un-retired tail on the host —
    full coverage, bit-identical output."""
    from racon_tpu.ops.poa import PoaEngine

    monkeypatch.setenv("RACON_TPU_SCHED", "0")
    monkeypatch.setenv("RACON_TPU_WALK_ASYNC", "1")
    monkeypatch.setenv("RACON_TPU_STALL_S", "0.5")
    serial = build_stream_windows(24, seed=11)
    PoaEngine(backend="jax").consensus_windows(serial)
    ref = [w.consensus for w in serial]

    faults.configure("pipe/walk:0!hang=3")
    obs_metrics.reset()
    assert _stream(build_stream_windows(24, seed=11)) == ref
    snap = obs_metrics.registry().snapshot()
    assert snap["pipe_stall_events"] >= 1
    assert watchdog.health_snapshot()["pipeline_stalls"] >= 1
