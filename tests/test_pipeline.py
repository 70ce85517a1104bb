"""Streaming execution pipeline tests (racon_tpu/pipeline/).

Covers the three layers separately and end to end: bounded queues
(backpressure, close/abort semantics), the stage driver (ordering,
exception propagation without hangs, clean teardown on an abandoned
consumer), the slice tracker (in-order release under out-of-order
retirement), the gating truth table, and the differential contract —
``stream_consensus`` / ``polish_stream`` must be bit-identical to the
serial path (ISSUE: RACON_TPU_PIPELINE=0 and =1 produce identical
polished FASTA; the golden-config differential runs under the ``ava``
marker like the scheduler's).
"""

import threading
import time

import numpy as np
import pytest

from racon_tpu.pipeline import (BoundedQueue, Pipeline, PipelineAborted,
                                QueueClosed, StageError, configure,
                                pipeline_depth, pipeline_enabled)
from racon_tpu.pipeline.streaming import SliceTracker, stream_consensus

from synth_windows import BASES, build_stream_windows, mutate_indel


@pytest.fixture(autouse=True)
def _reset_cli_depth():
    """configure() installs process-global CLI state; undo per test."""
    yield
    configure(None)


# ------------------------------------------------------------- queues


def test_queue_fifo_and_close_drain():
    q = BoundedQueue("q", 4)
    for i in range(3):
        q.put(i)
    q.close()
    assert [q.get(), q.get(), q.get()] == [0, 1, 2]
    with pytest.raises(QueueClosed):
        q.get()
    with pytest.raises(RuntimeError, match="closed"):
        q.put(99)


def test_queue_backpressure_blocks_producer():
    """A full queue blocks the producer until the consumer drains —
    the mechanism that bounds in-flight HBM buffers."""
    q = BoundedQueue("q", 2)
    done = []

    def produce():
        for i in range(6):
            q.put(i)
        done.append(True)

    t = threading.Thread(target=produce, daemon=True)
    t.start()
    time.sleep(0.05)
    assert not done, "producer ran past the capacity bound"
    assert q.depth == 2
    got = [q.get() for _ in range(6)]
    t.join(timeout=5)
    assert done and got == list(range(6))
    m = q.metrics()
    assert m["peak"] == 2 and m["items"] == 6
    assert m["put_wait_s"] > 0


def test_queue_abort_unblocks_blocked_put_and_drops_items():
    q = BoundedQueue("q", 1)
    q.put(0)
    errs = []

    def blocked_put():
        try:
            q.put(1)
        except PipelineAborted:
            errs.append("put")

    t = threading.Thread(target=blocked_put, daemon=True)
    t.start()
    time.sleep(0.02)
    q.abort()
    t.join(timeout=5)
    assert errs == ["put"]
    with pytest.raises(PipelineAborted):
        q.get()            # abort drops queued items too


# ------------------------------------------------------------- stages


def test_pipeline_happy_path_preserves_order():
    pipe = Pipeline("t")
    qa = pipe.queue("a", 2)
    qb = pipe.queue("b", 2)
    pipe.source("src", lambda: iter(range(10)), qa)
    pipe.stage("sq", lambda x: x * x, qa, qb)
    with pipe:
        out = list(pipe.drain(qb))
    assert out == [i * i for i in range(10)]
    assert not pipe.alive


def test_stage_returning_none_consumes_item():
    side = []
    pipe = Pipeline("t")
    qa = pipe.queue("a", 2)
    qb = pipe.queue("b", 2)

    def route(x):
        if x % 2:
            side.append(x)
            return None
        return x

    pipe.source("src", lambda: iter(range(6)), qa)
    pipe.stage("route", route, qa, qb)
    with pipe:
        out = list(pipe.drain(qb))
    assert out == [0, 2, 4]
    assert side == [1, 3, 5]


def test_stage_exception_propagates_without_hang():
    """A mid-pipeline failure must abort every queue (unblocking the
    producer stuck on a full edge) and re-raise at the consumer with
    the original exception chained."""
    pipe = Pipeline("t")
    qa = pipe.queue("a", 1)
    qb = pipe.queue("b", 1)

    def boom(x):
        if x == 2:
            raise ValueError("stage blew up")
        return x

    pipe.source("src", lambda: iter(range(100)), qa)
    pipe.stage("boom", boom, qa, qb)
    t0 = time.perf_counter()
    with pipe:
        with pytest.raises(StageError, match="'boom' failed") as ei:
            list(pipe.drain(qb))
    assert isinstance(ei.value.__cause__, ValueError)
    assert not pipe.alive
    assert time.perf_counter() - t0 < 10, "teardown hung"


def test_abandoned_consumer_tears_down_cleanly():
    """Breaking out of drain() early (generator abandoned) must not
    leave the producer blocked forever on a full queue."""
    pipe = Pipeline("t")
    qa = pipe.queue("a", 1)
    pipe.source("src", lambda: iter(range(100)), qa)
    with pipe:
        for item in pipe.drain(qa):
            break                # consumer walks away mid-stream
    # __exit__ aborted the queues, unblocking the producer stuck on the
    # full edge, and joined it.
    assert not pipe.alive


# ------------------------------------------------------- slice tracker


def test_slice_tracker_releases_in_order():
    tr = SliceTracker()
    tr.register(0, 0, 8, 2)
    tr.register(1, 8, 16, 1)
    tr.register(2, 16, 20, 1)
    assert tr.retire(1) == []              # slice 0 still in flight
    assert tr.retire(0) == []              # 1 of 2 items
    assert tr.retire(0) == [(0, 0, 8), (1, 8, 16)]   # releases 0 AND 1
    assert tr.retire(2) == [(2, 16, 20)]
    assert tr.flush() == []


def test_slice_tracker_zero_item_slice_releases():
    tr = SliceTracker()
    tr.register(0, 0, 4, 0)                # all-trivial slice: no items
    tr.register(1, 4, 8, 1)
    assert tr.retire(1) == [(0, 0, 4), (1, 4, 8)]


def test_slice_tracker_lost_item_fails_loudly():
    tr = SliceTracker()
    tr.register(0, 0, 4, 2)
    tr.retire(0)
    with pytest.raises(RuntimeError, match="never completed"):
        tr.flush()
    tr2 = SliceTracker()
    tr2.register(0, 0, 4, 1)
    tr2.retire(0)
    with pytest.raises(RuntimeError, match="more items"):
        tr2.retire(0)


# -------------------------------------------------------------- gating


def test_gating_truth_table(monkeypatch):
    monkeypatch.delenv("RACON_TPU_PIPELINE", raising=False)
    configure(None)
    assert not pipeline_enabled()          # default: off
    monkeypatch.setenv("RACON_TPU_PIPELINE", "1")
    assert pipeline_enabled()              # env enables
    configure(0)
    assert not pipeline_enabled()          # CLI 0 disables
    configure(3)
    assert pipeline_enabled()
    monkeypatch.setenv("RACON_TPU_PIPELINE", "0")
    assert not pipeline_enabled()          # env 0 beats the CLI knob
    monkeypatch.setenv("RACON_TPU_PIPELINE", "false")
    assert not pipeline_enabled()


def test_gating_depth(monkeypatch):
    monkeypatch.delenv("RACON_TPU_PIPELINE_DEPTH", raising=False)
    configure(None)
    assert pipeline_depth() == 2           # DEFAULT_DEPTH
    configure(5)
    assert pipeline_depth() == 5
    configure(None)
    monkeypatch.setenv("RACON_TPU_PIPELINE_DEPTH", "7")
    assert pipeline_depth() == 7
    monkeypatch.setenv("RACON_TPU_PIPELINE_DEPTH", "bogus")
    with pytest.raises(ValueError, match="invalid"):
        pipeline_depth()
    with pytest.raises(ValueError, match="invalid pipeline depth"):
        configure(-1)


# ----------------------------------------------- streaming differential


def test_stream_consensus_bit_identical_to_serial():
    """The tentpole contract: the streaming executor shares the serial
    engine's slice planning, so its consensi are bit-identical, and its
    yielded ranges are ascending, contiguous, and cover every window."""
    from racon_tpu.obs import metrics as obs_metrics
    from racon_tpu.ops.poa import PoaEngine

    serial = build_stream_windows(24, seed=42)
    PoaEngine(backend="jax").consensus_windows(serial)

    streamed = build_stream_windows(24, seed=42)
    obs_metrics.reset()
    ranges = list(stream_consensus(PoaEngine(backend="jax"), streamed,
                                   chunk=8, depth=2))
    assert [w.consensus for w in streamed] == \
        [w.consensus for w in serial]
    # Ordered streaming: contiguous ascending cover of range(n).
    flat = [i for s, e in ranges for i in range(s, e)]
    assert flat == list(range(24))
    # The run recorded stage/queue gauges and a wall clock.
    snap = obs_metrics.registry().snapshot()
    assert snap.get("pipe_runs") == 1
    for key in ("pipe_stage_build_items", "pipe_stage_pack_items",
                "pipe_stage_compute_busy_s", "pipe_queue_run_peak",
                "pipe_wall_s"):
        assert key in snap, key
    extras = obs_metrics.pipeline_extras()
    assert "pipe_overlap_efficiency" in extras


def test_stream_consensus_abandoned_generator_closes_cleanly():
    from racon_tpu.ops.poa import PoaEngine
    ws = build_stream_windows(24, seed=7)
    gen = stream_consensus(PoaEngine(backend="jax"), ws, chunk=4, depth=1)
    next(gen)
    t0 = time.perf_counter()
    gen.close()                  # must abort queues + join stage threads
    assert time.perf_counter() - t0 < 10, "generator close hung"


def test_stream_consensus_empty_input():
    from racon_tpu.ops.poa import PoaEngine
    assert list(stream_consensus(PoaEngine(backend="jax"), [])) == []


def _write_two_contig_inputs(d, n_reads=8, clen=400):
    """Tiny two-contig polishing workload (obs_smoke.py's generator,
    doubled) — enough windows per contig to exercise the streaming
    assembler's multi-window joins and ordered emission."""
    rng = np.random.default_rng(11)
    drafts, reads, paf = [], [], []
    for ci in (1, 2):
        truth = BASES[rng.integers(0, 4, clen)]
        draft = mutate_indel(rng, truth)
        drafts.append(b">c%d\n%s\n" % (ci, draft))
        for i in range(n_reads):
            r = mutate_indel(rng, truth)
            name = f"c{ci}r{i}"
            reads.append(b">" + name.encode() + b"\n" + r + b"\n")
            paf.append(f"{name}\t{len(r)}\t0\t{len(r)}\t+\tc{ci}"
                       f"\t{len(draft)}\t0\t{len(draft)}"
                       f"\t{min(len(r), len(draft))}"
                       f"\t{max(len(r), len(draft))}\t60")
    (d / "draft.fasta").write_bytes(b"".join(drafts))
    (d / "reads.fasta").write_bytes(b"".join(reads))
    (d / "ovl.paf").write_text("\n".join(paf) + "\n")
    return d


def test_polish_stream_matches_polish(tmp_path, monkeypatch):
    """polish_stream (the pipeline path polish() delegates to under
    RACON_TPU_PIPELINE=1) emits the same records, in the same order,
    with the same names/tags, as the serial polish()."""
    from racon_tpu.models.polisher import PolisherType, create_polisher
    monkeypatch.delenv("RACON_TPU_PIPELINE", raising=False)
    _write_two_contig_inputs(tmp_path)

    def make():
        p = create_polisher(
            str(tmp_path / "reads.fasta"), str(tmp_path / "ovl.paf"),
            str(tmp_path / "draft.fasta"), PolisherType.kC,
            200, 10.0, 0.3, 5, -4, -8, backend="jax")
        p.initialize()
        return p

    serial = make().polish(True)
    streamed = list(make().polish_stream(True))
    assert [s.name for s in streamed] == [s.name for s in serial]
    assert [s.data for s in streamed] == [s.data for s in serial]
    assert len(serial) == 2


def test_polish_delegates_to_stream_when_enabled(tmp_path, monkeypatch):
    from racon_tpu.models.polisher import PolisherType, create_polisher
    _write_two_contig_inputs(tmp_path)

    def run():
        p = create_polisher(
            str(tmp_path / "reads.fasta"), str(tmp_path / "ovl.paf"),
            str(tmp_path / "draft.fasta"), PolisherType.kC,
            200, 10.0, 0.3, 5, -4, -8, backend="jax")
        p.initialize()
        return p.polish(True)

    monkeypatch.setenv("RACON_TPU_PIPELINE", "0")
    off = run()
    monkeypatch.setenv("RACON_TPU_PIPELINE", "1")
    on = run()
    assert [s.name for s in on] == [s.name for s in off]
    assert [s.data for s in on] == [s.data for s in off]


# Reference acceptance configs (tests/test_polisher.py::_GOLDEN_CONFIGS).
_GOLDEN_CONFIGS = [
    ("sample_reads.fastq.gz", "sample_overlaps.sam.gz", 500, (5, -4, -8)),
    ("sample_reads.fastq.gz", "sample_overlaps.paf.gz", 500, (5, -4, -8)),
    ("sample_reads.fasta.gz", "sample_overlaps.paf.gz", 500, (5, -4, -8)),
    ("sample_reads.fasta.gz", "sample_overlaps.sam.gz", 500, (5, -4, -8)),
    ("sample_reads.fastq.gz", "sample_overlaps.paf.gz", 1000, (5, -4, -8)),
    ("sample_reads.fastq.gz", "sample_overlaps.paf.gz", 500, (1, -1, -1)),
]
_GOLDEN_IDS = ["sam_fastq", "paf_fastq", "paf_fasta", "sam_fasta",
               "window1000", "edit_scores"]


@pytest.mark.ava
@pytest.mark.parametrize("reads,overlaps,window,scores",
                         _GOLDEN_CONFIGS, ids=_GOLDEN_IDS)
def test_pipeline_differential_golden(ref_data, monkeypatch, reads,
                                      overlaps, window, scores):
    """RACON_TPU_PIPELINE=0 and =1 must produce bit-identical polished
    FASTA on every reference acceptance config — the pipeline reuses
    the serial engine's slice planning, so any divergence is an
    executor bug, not noise. ci.sh runs the sam_fastq case in the
    default tier; --full runs all six."""
    from racon_tpu.models.polisher import PolisherType, create_polisher

    def run():
        p = create_polisher(
            ref_data(reads), ref_data(overlaps),
            ref_data("sample_layout.fasta.gz"), PolisherType.kC,
            window, 10.0, 0.3, *scores, backend="jax")
        p.initialize()
        return p.polish(True)

    monkeypatch.setenv("RACON_TPU_PIPELINE", "0")
    serial = run()
    monkeypatch.setenv("RACON_TPU_PIPELINE", "1")
    piped = run()
    assert [s.data for s in piped] == [s.data for s in serial]
    assert [s.name for s in piped] == [s.name for s in serial]


@pytest.mark.ava
@pytest.mark.parametrize("reads,overlaps,window,scores",
                         _GOLDEN_CONFIGS, ids=_GOLDEN_IDS)
def test_walk_async_differential_golden(ref_data, monkeypatch, reads,
                                        overlaps, window, scores):
    """RACON_TPU_WALK_ASYNC=0 and =1 must produce bit-identical
    polished FASTA on every reference acceptance config, on the path
    where the decoupled walk actually runs (pipeline on, fixed rounds —
    the scheduler keeps fused dispatches, see sched/scheduler.py). The
    walk dispatch composes the same traced bodies the fused program
    compiles, so any divergence is a handoff bug, not noise."""
    from racon_tpu.models.polisher import PolisherType, create_polisher

    def run():
        p = create_polisher(
            ref_data(reads), ref_data(overlaps),
            ref_data("sample_layout.fasta.gz"), PolisherType.kC,
            window, 10.0, 0.3, *scores, backend="jax")
        p.initialize()
        return p.polish(True)

    monkeypatch.setenv("RACON_TPU_PIPELINE", "1")
    monkeypatch.setenv("RACON_TPU_SCHED", "0")
    monkeypatch.setenv("RACON_TPU_WALK_ASYNC", "0")
    fused = run()
    monkeypatch.setenv("RACON_TPU_WALK_ASYNC", "1")
    decoupled = run()
    assert [s.data for s in decoupled] == [s.data for s in fused]
    assert [s.name for s in decoupled] == [s.name for s in fused]
