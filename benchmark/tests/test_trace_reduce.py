"""The trace reduction: its pieces on made-up intervals, and its numbers
on a short trace recorded on a TPU v5e (``data/ovl.xplane.pb.gz``: half
a second of an ``ecoli_ont.paf`` job, with the job's span file)."""

import gzip
import os
from types import SimpleNamespace as NS

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


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=float(t0),
                               duration_ns=float(t1 - t0))
                            for t0, t1, n in evs])
        for ln, evs in lines.items()])


def _device(i, ops):
    """Device ``i`` running one program over ``ops`` ((t0, t1) in ns)."""
    return _plane(f"/device:TPU:{i}", {
        "XLA Modules": [(ops[0][0], ops[-1][1], "jit_sched_rounds(7)")],
        "XLA Ops": [(t0, t1, "%fusion.1 = f32[8] fusion()")
                    for t0, t1 in ops]})


@pytest.fixture
def four_chips_one_idle():
    # A 100 ns run span. Chip 0 is busy 60 ns, chips 1 and 2 20 ns
    # each, chip 3 holds a plane but ran nothing; a fifth device, not
    # the cell's, ran something that must not count.
    host = _plane("/host:CPU", {"python3": [
        (0, 100, "run:racon_tpu"), (0, 50, "align:native_redo")]})
    return NS(planes=[
        _device(0, [(0, 40), (60, 80)]), _device(1, [(50, 70)]),
        _device(2, [(80, 100)]), _plane("/device:TPU:3", {}),
        _device(4, [(0, 100)]), host])


def test_a_chip_that_ran_nothing_counts_as_idle(four_chips_one_idle):
    from benchmark import harness
    r = trace_reduce.reduce_xspace(four_chips_one_idle, chips=4)
    assert r["chips"] == 4 and r["devices"] == 3
    assert r["busy_by_chip"] == pytest.approx([60e-9, 20e-9, 20e-9, 0.0])
    assert r["busy_s"] == pytest.approx(25e-9)
    assert r["idle_share"] == pytest.approx(0.75)
    # Idle per chip, averaged: chip 0 10 ns under the redo span and 30
    # after it, chip 1 50 and 30, chip 2 50 and 30, chip 3 all 100.
    gaps = dict(r["idle_gaps"])
    assert gaps["align:native_redo"] == pytest.approx((10 + 50 + 50 + 50)
                                                      / 4 * 1e-9)
    assert gaps["no span"] == pytest.approx((30 + 30 + 30 + 50) / 4 * 1e-9)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    cell = harness.find_cell(harness.load_spec(), "ecoli_ont_dp4.paf")
    imbalance = harness.load_reader(
        cell["readers"]["device.chip_busy_imbalance"])
    idle = harness.load_reader(cell["readers"]["device.idle_share"])
    ctx = {"trace": r, "chips": 4, "registry": {}, "draft_mb": 0.8}
    assert imbalance(ctx) == pytest.approx(1 - 25 / 60)
    assert idle(ctx) == pytest.approx(0.75)


def test_without_a_chip_count_every_device_plane_counts(
        four_chips_one_idle):
    r = trace_reduce.reduce_xspace(four_chips_one_idle)
    assert r["chips"] == 5
    assert r["busy_by_chip"] == pytest.approx([60e-9, 20e-9, 20e-9, 0.0,
                                               100e-9])


def test_one_chip_has_no_imbalance_to_read(recorded):
    from benchmark import harness
    cell = harness.find_cell(harness.load_spec(), "ecoli_ont_dp4.paf")
    imbalance = harness.load_reader(
        cell["readers"]["device.chip_busy_imbalance"])
    assert recorded["busy_by_chip"] == [recorded["busy_s"]]
    assert imbalance({"trace": recorded, "chips": 1}) is None
    assert imbalance({"trace": None, "chips": 4}) is None
