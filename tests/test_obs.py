"""Observability tests: tracer JSONL, metrics registry, report/validate
(racon_tpu/obs/, scripts/obs_report.py)."""

import json
import sys

import pytest

from racon_tpu.obs import metrics as obs_metrics
from racon_tpu.obs import trace as obs_trace


@pytest.fixture
def tracer_sandbox():
    """Isolate the process tracer global; restore disabled state after."""
    prev = obs_trace._tracer
    yield
    cur = obs_trace._tracer
    if isinstance(cur, obs_trace.Tracer):
        cur.finish()
    obs_trace._tracer = prev


def _read_trace(path):
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


# ------------------------------------------------------------------ tracer

def test_tracer_writes_nested_spans(tmp_path, tracer_sandbox):
    p = tmp_path / "t.jsonl"
    tr = obs_trace.configure(str(p))
    with tr.span("run", "outer", tag=1):
        with tr.span("chunk", "inner", lanes=8):
            pass
        tr.point("transfer", "h2d/x", dur_s=0.01, bytes=100, dir="h2d")
    tr.finish(metrics={"a": 1})

    recs = _read_trace(p)
    assert recs[0]["ev"] == "begin" and recs[0]["schema"] == 1
    spans = {r["name"]: r for r in recs if r["ev"] == "span"}
    outer, inner = spans["outer"], spans["inner"]
    assert outer["parent"] is None and outer["tag"] == 1
    assert inner["parent"] == outer["id"] and inner["lanes"] == 8
    # Close-time emission: the child's line precedes the parent's.
    names = [r["name"] for r in recs if r["ev"] == "span"]
    assert names.index("inner") < names.index("outer")
    xfer = spans["h2d/x"]
    assert xfer["parent"] == outer["id"]
    assert xfer["bytes"] == 100 and xfer["dir"] == "h2d"
    assert recs[-1] == {"ev": "metrics", "a": 1}


def test_tracer_emit_retro_span(tmp_path, tracer_sandbox):
    import time
    p = tmp_path / "t.jsonl"
    tr = obs_trace.configure(str(p))
    t0 = time.perf_counter()
    tr.emit("phase", "late", t0, 0.5)
    tr.finish()
    (span,) = [r for r in _read_trace(p) if r["ev"] == "span"]
    assert span["kind"] == "phase" and span["dur_s"] == 0.5
    assert span["t0"] >= 0


def test_tracer_clock_bridges_to_profiler(tmp_path, monkeypatch,
                                          tracer_sandbox):
    """``unix_ns + t0 * 1e9`` is where the JAX profiler's host plane
    puts each span the tracer bridged into it."""
    import glob
    import time

    import jax
    from jax.profiler import ProfileData
    monkeypatch.setenv(obs_trace.ENV_XPROF, "1")
    p = tmp_path / "t.jsonl"
    tr = obs_trace.configure(str(p))
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        for i in range(3):
            with tr.span("align", f"clock{i}", overlaps=i):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    tr.finish()

    recs = _read_trace(p)
    unix_ns = recs[0]["unix_ns"]
    assert recs[0]["unix_time"] == pytest.approx(unix_ns / 1e9)
    (xplane,) = glob.glob(str(tmp_path / "prof" / "plugins" / "profile" /
                              "*" / "*.xplane.pb"))
    profile = ProfileData.from_file(xplane)
    planes = list(profile.planes)
    # On the CPU the host plane counts from the session's start, which
    # the "Task Environment" plane gives on the epoch clock (a TPU
    # host's events are on the epoch clock already).
    (origin,) = [v for plane in planes for k, v in plane.stats
                 if k == "profile_start_time"]
    starts = {e.name: origin + e.start_ns for plane in planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events}
    spans = [r for r in recs if r["ev"] == "span"]
    assert len(spans) == 3
    for s in spans:
        bridged = starts[f"{s['kind']}:{s['name']}"]
        assert abs(bridged - (unix_ns + s["t0"] * 1e9)) < 1e6


def test_timed_span_times_into_registry(tmp_path, monkeypatch,
                                        tracer_sandbox):
    """timed_span adds its seconds to ``<kind>_seconds_<name>`` with
    tracing off (no span anywhere) and on (a span with its attrs)."""
    monkeypatch.delenv(obs_trace.ENV_TRACE, raising=False)
    obs_trace._tracer = None
    obs_metrics.reset()
    with obs_metrics.timed_span("align", "probe", overlaps=1) as sp:
        sp.add(walked=2)
    off = obs_metrics.registry().get("align_seconds_probe")
    assert off >= 0
    assert not isinstance(obs_trace.get_tracer(), obs_trace.Tracer)

    p = tmp_path / "t.jsonl"
    obs_trace.configure(str(p))
    with obs_metrics.timed_span("align", "probe", overlaps=3) as sp:
        sp.add(walked=4)
    obs_trace.get_tracer().finish()
    on = obs_metrics.registry().get("align_seconds_probe") - off
    (span,) = [r for r in _read_trace(p) if r["ev"] == "span"]
    assert (span["kind"], span["name"]) == ("align", "probe")
    assert span["overlaps"] == 3 and span["walked"] == 4
    assert on == pytest.approx(span["dur_s"], abs=1e-3)
    obs_metrics.reset()


def test_configure_env_and_idempotence(tmp_path, monkeypatch,
                                       tracer_sandbox):
    p = tmp_path / "env.jsonl"
    monkeypatch.setenv(obs_trace.ENV_TRACE, str(p))
    obs_trace._tracer = None
    tr = obs_trace.get_tracer()
    assert isinstance(tr, obs_trace.Tracer) and tr.path == str(p)
    assert obs_trace.configure(str(p)) is tr      # same path: same tracer


def test_null_tracer_noop(monkeypatch):
    monkeypatch.delenv(obs_trace.ENV_TRACE, raising=False)
    tr = obs_trace.NullTracer()
    with tr.span("run", "x") as sp:
        sp.add(n=1).end()
    tr.emit("phase", "x", 0.0, 1.0)
    tr.point("transfer", "x")
    tr.finish(metrics={"a": 1})
    assert tr.enabled is False


# ---------------------------------------------------------------- registry

def test_registry_counters():
    reg = obs_metrics.MetricsRegistry()
    reg.inc("n")
    reg.inc("n", 2)
    reg.set("s", [1, 2])
    reg.set("_internal", "hidden")
    assert reg.get("n") == 3
    assert reg.snapshot() == {"n": 3, "s": [1, 2]}
    reg.reset()
    assert reg.snapshot() == {}


def test_transfer_extras_derivation():
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.record_h2d(2_000_000, 0.5, reg=reg)
    obs_metrics.record_h2d(2_000_000, 0.5, reg=reg)
    obs_metrics.record_d2h(1_000_000, 0.25, reg=reg)
    obs_metrics.record_flag_pull(8, 0.1, reg=reg)
    reg.inc("device_dispatches", 4)
    ex = obs_metrics.transfer_extras(reg)
    assert ex["h2d_bytes"] == 4_000_000 and ex["h2d_transfers"] == 2
    assert ex["h2d_mb_per_s"] == pytest.approx(4.0)
    assert ex["d2h_mb_per_s"] == pytest.approx(4.0)
    # Flag pulls sync on compute: never folded into the h2d/d2h numbers.
    assert ex["sched_flag_pulls"] == 1
    assert ex["sched_flag_pull_s"] == pytest.approx(0.1)
    assert ex["device_dispatches"] == 4


def test_transfer_extras_empty():
    assert obs_metrics.transfer_extras(obs_metrics.MetricsRegistry()) == {}


def test_redo_extras_derivation():
    reg = obs_metrics.MetricsRegistry()
    assert reg.snapshot() == {}
    reg.set("walk_chain_len", 161)
    # The chain gauge reports even on runs where no window ever flags.
    assert reg.snapshot() == {"walk_chain_len": 161}
    obs_metrics.record_redo(3, 0, reg=reg)
    obs_metrics.record_redo(1, 1, reg=reg)
    snap = reg.snapshot()
    assert snap["redo_passes"] == 2
    assert snap["redo_device_windows"] == 4
    assert snap["redo_host_windows"] == 1
    assert snap["walk_chain_len"] == 161


def _telem():
    from racon_tpu.sched.telemetry import SchedTelemetry
    t = SchedTelemetry(5)
    t.record_chunk(10)
    for _ in range(6):
        t.record_freeze(2, 1)
    for _ in range(4):
        t.record_freeze(4, 1)
    for r in range(5):
        t.record_round(r, 10 if r < 2 else 4)
    t.record_repack(0.0123)
    return t


def test_publish_sched_canonical_keys():
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.publish_sched(_telem(), reg)
    ex = obs_metrics.sched_extras(reg)
    assert set(ex) == set(obs_metrics.SCHED_KEYS)
    assert ex["sched_windows"] == 10
    assert ex["sched_rounds_hist"] == {"2": 6, "4": 4}
    assert ex["sched_repack_overhead_s"] == pytest.approx(0.0123)


def test_sched_summary_line_format_stable():
    """The stderr line must keep the pre-registry format."""
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.publish_sched(_telem(), reg)
    line = obs_metrics.sched_summary_line(reg)
    assert line.startswith("windows=10 chunks=1 frozen[r2:6 r4:4] ")
    assert "rounds_saved=" in line and line.endswith("repack=0.012s")
    # And SchedTelemetry.summary() routes through the same formatter.
    assert _telem().summary() == line


# -------------------------------------------------------------- obs_report

def _report():
    sys.path.insert(0, "/root/repo")
    from scripts import obs_report
    return obs_report


def test_obs_report_validate_and_render(tmp_path, tracer_sandbox, capsys):
    obs_report = _report()
    p = tmp_path / "t.jsonl"
    tr = obs_trace.configure(str(p))
    with tr.span("run", "r"):
        with tr.span("phase", "load"):
            pass
        # point() backdates by dur_s; keep it shorter than the span so
        # the containment check sees a realistic in-parent transfer.
        tr.point("transfer", "h2d/x", dur_s=0.001, bytes=1000, dir="h2d")
    tr.finish(metrics={"h2d_bytes": 1000})
    trace = obs_report.load_trace(str(p))
    assert obs_report.validate(trace) == []
    obs_report.render(trace)
    out = capsys.readouterr().out
    assert "run: r" in out and "load" in out
    assert "h2d" in out and "metrics:" in out
    assert obs_report.main([str(p), "--validate"]) == 0


def test_obs_report_flags_violations(tmp_path):
    obs_report = _report()
    p = tmp_path / "bad.jsonl"
    p.write_text(
        json.dumps({"ev": "begin", "schema": 1, "unix_time": 0}) + "\n" +
        # Negative duration + dangling parent.
        json.dumps({"ev": "span", "id": 0, "parent": 7, "kind": "run",
                    "name": "r", "t0": 0.0, "dur_s": -1.0}) + "\n")
    errs = obs_report.validate(obs_report.load_trace(str(p)))
    assert any("parent 7" in e for e in errs)
    assert any("dur_s" in e for e in errs)
    assert obs_report.main([str(p), "--validate"]) == 1


def test_obs_report_rejects_garbage(tmp_path):
    obs_report = _report()
    p = tmp_path / "junk.jsonl"
    p.write_text("not json\n")
    assert obs_report.main([str(p), "--validate"]) == 1
