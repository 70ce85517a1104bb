"""Spans and counters inside the overlap-alignment phase.

A small PAF job polished through ``racon_tpu.cli.main`` on the jax
backend: the ``align`` spans (models/polisher.py, ops/ovl_align.py, via
obs/metrics.py ``timed_span``) must cover the ``aligned overlaps``
logger phase, nest the chunk dispatches, and their counters must count
what the job aligned, the same on every run of the same job.
"""

import gzip
import json

import pytest

from racon_tpu import cli
from racon_tpu.obs import metrics as obs_metrics
from racon_tpu.obs import trace as obs_trace

from test_ovl_align import _write_dataset

ALIGN_SPANS = ("revcomp", "device_prep", "device_dispatch",
               "device_collect", "native_redo", "bp_walk")
PHASE_KEY = "phase_seconds_initialize_aligned_overlaps"


@pytest.fixture
def clean_obs(monkeypatch):
    monkeypatch.delenv(obs_trace.ENV_TRACE, raising=False)
    monkeypatch.delenv(obs_trace.ENV_XPROF, raising=False)
    prev = obs_trace._tracer
    obs_metrics.reset()
    yield
    obs_metrics.reset()
    obs_trace._tracer = prev


def _paf_bases(d):
    """q + t span of every PAF record (none carries a CIGAR)."""
    total = 0
    with gzip.open(f"{d}/overlaps.paf.gz", "rt") as f:
        for line in f:
            c = line.split("\t")
            total += int(c[3]) - int(c[2]) + int(c[8]) - int(c[7])
    return total


def _polish(d, capsys, extra=()):
    obs_metrics.reset()
    rc = cli.main(["--backend", "jax", *extra, f"{d}/reads.fasta.gz",
                   f"{d}/overlaps.paf.gz", f"{d}/draft.fasta.gz"])
    out = capsys.readouterr().out
    assert rc == 0 and out.startswith(">")
    return obs_metrics.registry().snapshot()


def test_align_spans_cover_phase_and_count(tmp_path, capsys, clean_obs):
    d = _write_dataset(tmp_path, n_reads=12, read_len=2000, seed=4,
                       rate=0.05, draft_len=6000)
    first = _polish(d, capsys)
    trace = tmp_path / "spans.jsonl"
    # The second run is warm (modules imported, programs compiled), as
    # every job after a process's first is.
    before = obs_trace._tracer
    reg = _polish(d, capsys, ["--trace", str(trace)])

    # Nothing fell back: every overlap was aligned on the device.
    assert reg["ovl_native_jobs"] == 0 and reg["ovl_device_jobs"] == 12
    assert reg.get("align_native_bases", 0) == 0
    assert "align_seconds_native_redo" not in reg
    assert reg["align_bases_total"] == _paf_bases(d)
    # The device handled every overlap, so none walked a CIGAR.
    assert reg["align_walk_overlaps"] == 0
    counters = ("align_bases_total", "align_walk_overlaps",
                "ovl_device_jobs", "ovl_native_jobs")
    assert {k: first.get(k) for k in counters} == \
        {k: reg.get(k) for k in counters}

    spans_s = sum(reg.get(f"align_seconds_{n}", 0.0) for n in ALIGN_SPANS)
    assert 0.95 <= spans_s / reg[PHASE_KEY] <= 1.01

    recs = [json.loads(ln) for ln in trace.read_text().splitlines()]
    spans = [r for r in recs if r["ev"] == "span"]
    align = {r["name"]: r for r in spans if r["kind"] == "align"}
    assert set(align) == set(ALIGN_SPANS) - {"native_redo"}
    assert all(r["overlaps"] > 0 for r in align.values())
    assert align["bp_walk"]["walked"] == 0
    # Sequential siblings, in the phase's order, under one parent.
    order = [align[n] for n in ALIGN_SPANS if n in align]
    assert len({r["parent"] for r in order}) == 1
    for a, b in zip(order, order[1:]):
        assert a["t0"] + a["dur_s"] <= b["t0"] + 1e-6
    # Chunk dispatches stay nested inside device_dispatch.
    chunks = [r for r in spans if r["kind"] == "dispatch"
              and r["name"] == "ovl_chunk"]
    assert chunks and all(r["parent"] == align["device_dispatch"]["id"]
                          for r in chunks)
    # The call's tracer is gone once it returns.
    assert obs_trace._tracer is before


def test_cli_trace_restores_previous_tracer(tmp_path, capsys, clean_obs):
    d = _write_dataset(tmp_path, n_reads=6, read_len=2000, seed=9,
                       rate=0.05, draft_len=4000)
    obs_trace._tracer = None
    traced = tmp_path / "traced.jsonl"
    _polish(d, capsys, ["--trace", str(traced)])
    assert traced.exists()
    assert obs_trace._tracer is None
    # A later call without --trace writes no span anywhere.
    _polish(d, capsys)
    assert not isinstance(obs_trace.get_tracer(), obs_trace.Tracer)
    assert sorted(p.name for p in tmp_path.glob("*.jsonl*")) == \
        ["traced.jsonl"]


def test_native_redo_counts_band_cells(tmp_path, capsys, clean_obs):
    """On the native backend every overlap is redone on the host; the
    ``native_redo`` span and the registry count the band cells, all of
    them in the vector pass where the host has one (every first band is
    at least 257 cells wide)."""
    from racon_tpu.native.aligner import vector_lanes
    d = _write_dataset(tmp_path, n_reads=6, read_len=2000, seed=7,
                       rate=0.1, draft_len=4000)
    trace = tmp_path / "spans.jsonl"
    obs_metrics.reset()
    rc = cli.main(["--backend", "native", "--trace", str(trace),
                   f"{d}/reads.fasta.gz", f"{d}/overlaps.paf.gz",
                   f"{d}/draft.fasta.gz"])
    assert rc == 0 and capsys.readouterr().out.startswith(">")
    reg = obs_metrics.registry().snapshot()
    assert reg["align_native_bases"] == reg["align_bases_total"] > 0
    # At least one band of lq + 1 rows of 257 cells per overlap.
    assert reg["align_native_cells"] >= 6 * 257 * 1000
    want = reg["align_native_cells"] if vector_lanes() else 0
    assert reg["align_native_vector_cells"] == want
    recs = [json.loads(ln) for ln in trace.read_text().splitlines()]
    redo, = [r for r in recs if r["ev"] == "span" and r["kind"] == "align"
             and r["name"] == "native_redo"]
    assert (redo["cells"], redo["vector_cells"]) == \
        (reg["align_native_cells"], reg["align_native_vector_cells"])
