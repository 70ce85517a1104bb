"""The trace reduction: its pieces on made-up intervals, and its numbers
on a short trace recorded on a TPU v5e (``data/ovl.xplane.pb.gz``: half
a second of an ``ecoli_ont.paf`` job, with the job's span file)."""

import gzip
import os

import pytest

from benchmark import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_names_are_shortened():
    assert trace_reduce.op_name(
        "%fw_dirs_band_tile.7 = (u8[2048,1536,64]) custom-call()") == \
        "fw_dirs_band_tile"
    assert trace_reduce.op_name("%copy-start.27 = (s32[64]) copy-start()") \
        == "copy-start"
    assert trace_reduce.program_name("jit_sched_rounds(1181930341)") == \
        "jit_sched_rounds"


def test_union_and_idle_labels():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (6, 9)]) == \
        [(0, 3), (5, 9)]
    labels = [(5, 25, "dispatch:a", (0, 20)), (8, 9, "chunk:b", (0, 1)),
              (0, 100, "phase:p", (1, 100))]
    got = trace_reduce._label_time([(0, 10), (20, 30), (200, 210)], labels)
    assert dict(got) == {"phase:p": 10, "dispatch:a": 9, "chunk:b": 1,
                         "no span": 10}


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(DATA, "ovl.xplane.pb.gz"), "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    phases = trace_reduce.load_phases(os.path.join(DATA, "ovl.spans.jsonl"))
    return trace_reduce.reduce_xspace(pd, phases)


def test_the_recorded_trace_reduces_to_its_pinned_numbers(recorded):
    # The trace: one whole warm 20 kb ecoli_ont.paf job on a TPU v5e,
    # kept with the planes, lines and events the reduction reads (the
    # device's XLA Ops and XLA Modules, the host's program spans).
    r = recorded
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(1.648626097, abs=1e-9)
    assert r["busy_s"] == pytest.approx(0.422174961, abs=1e-9)
    assert r["idle_share"] == pytest.approx(0.74392316, abs=1e-8)
    assert trace_reduce.kernel_seconds(r, "^fw_dirs_band", True) == \
        pytest.approx(0.160943073, abs=1e-9)
    assert trace_reduce.kernel_seconds(r, "^fw_dirs_band", False) == \
        pytest.approx(0.028313295, abs=1e-9)
    assert r["device_ops"][0] == [
        "jit__tiled_chunk_breaking_points/fw_dirs_band_tile",
        pytest.approx(0.099668311, abs=1e-9)]
    gaps = dict(r["idle_gaps"])
    assert gaps["phase:aligned_overlaps"] == pytest.approx(1.161716266,
                                                           abs=1e-9)
    # Every idle nanosecond is named once; event times are float
    # nanoseconds since the epoch (about 256 ns apart at today's dates),
    # so the sums agree to a millisecond, not exactly.
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               abs=2e-3)
