"""A run whose timed path is broken underneath reads ``correct`` false.

Each test skips the harness's look for a chip and drives the rest of a
run of a small copy of a cell on the CPU (the program's jax backend,
its kernels' XLA twins), with the cell's own limits, once sound and
once for each fault a polishing cell can have:

- a step that returns its state unchanged: no window is polished, the
  draft comes back;
- half of the batch left out: each consensus call polishes the first
  half of its windows, and the rest keep their draft bases;
- half of the batch dropped: the last half of each consensus call's
  windows come back with no bases, so the contigs stop short;
- an answer altered where it is produced: one window's consensus per
  call comes back reversed;
- the exchange between chips left out (the four-chip cell alone, on
  four virtual CPU devices): the consensus programs' ``psum`` over the
  ``dp`` mesh returns each chip's own share of the votes.
"""

import json

import jax
import pytest

from benchmark import harness
from benchmark.tests.helpers import small_root


def _unchanged(orig):
    def consensus_windows(self, windows):
        for w in windows:
            w.set_backbone_consensus()
        return 0
    return consensus_windows


def _half(orig):
    def consensus_windows(self, windows):
        half = len(windows) // 2
        for w in windows[half:]:
            w.set_backbone_consensus()
        return orig(self, windows[:half])
    return consensus_windows


def _dropped(orig):
    def consensus_windows(self, windows):
        n = orig(self, windows)
        for w in windows[len(windows) // 2:]:
            w.consensus = b""
        return n
    return consensus_windows


def _altered(orig):
    def consensus_windows(self, windows):
        n = orig(self, windows)
        for w in windows:
            if w.consensus and len(w.consensus) > 100:
                w.consensus = w.consensus[::-1]
                break
        return n
    return consensus_windows


# Each cell at a size the CPU polishes in a minute or two.
CELLS = {"ecoli_ont.paf": 30_000, "yeast_clr.sam": 15_000,
         "ecoli_ont_dp4.paf": 30_000}


@pytest.fixture(scope="module", params=sorted(CELLS))
def cell_root(request, tmp_path_factory):
    cell = request.param
    return cell, small_root(tmp_path_factory.mktemp(cell), cell, CELLS[cell])


def _run(cell, root, monkeypatch):
    monkeypatch.setattr(harness, "WORK_DIR", root + "/.bench_work")
    monkeypatch.setattr(harness, "require_chips",
                        lambda n: {"platform": "cpu", "kind": "cpu",
                                   "count": n})
    lines = []
    rc = harness.run(cell, 2**31 + 3, 1.0, False, root=root,
                     emit=lines.append)
    assert rc == 0
    return json.loads(lines[-1])


@pytest.mark.parametrize("fault", [None, _unchanged, _half, _dropped,
                                   _altered],
                         ids=["sound", "unchanged", "half", "dropped",
                              "altered"])
def test_a_broken_timed_path_reads_not_correct(cell_root, monkeypatch,
                                               fault):
    from racon_tpu.ops.poa import PoaEngine
    if fault is not None:
        monkeypatch.setattr(PoaEngine, "consensus_windows",
                            fault(PoaEngine.consensus_windows))
    res = _run(*cell_root, monkeypatch)
    assert res["attempted"] >= 1
    assert res["correct"] is (fault is None), res["checks"]


@pytest.fixture
def no_exchange(monkeypatch):
    """Every ``psum`` over the ``dp`` axis returns its input: each chip
    keeps its own lanes' votes, and the replicated result is chip 0's.
    The programs traced before (and after) are dropped, so the fault is
    traced in and out."""
    psum = jax.lax.psum

    def own_share(x, axis_name, **kw):
        return x if axis_name == "dp" else psum(x, axis_name, **kw)

    jax.clear_caches()
    monkeypatch.setattr(jax.lax, "psum", own_share)
    yield
    monkeypatch.setattr(jax.lax, "psum", psum)
    jax.clear_caches()


def test_leaving_out_the_exchange_between_chips_reads_not_correct(
        tmp_path, monkeypatch, no_exchange):
    cell = "ecoli_ont_dp4.paf"
    assert len(jax.devices()) >= 4
    res = _run(cell, small_root(tmp_path, cell, CELLS[cell]), monkeypatch)
    assert res["attempted"] >= 1
    assert res["correct"] is False, res["checks"]
