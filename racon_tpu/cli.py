"""Command-line interface, mirroring the reference ``racon`` CLI.

Flags, defaults, help text, and output format follow the reference's
getopt table and help() (src/main.cpp:14-160): polished sequences are
emitted as FASTA on stdout, diagnostics go to stderr.
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Optional

from racon_tpu import __version__

_USAGE = "racon_tpu [options ...] <sequences> <overlaps> <target sequences>"


class _Interrupted(Exception):
    """SIGINT/SIGTERM re-raised as an exception so teardown runs in
    order: pipeline abort-cascade (generator close), checkpoint store
    close (commits are already fsync'd), trace finalization — then a
    conventional 128+signum exit instead of a traceback."""

    def __init__(self, signum: int):
        super().__init__(f"signal {signum}")
        self.signum = signum

_DESCRIPTION = """\
    <sequences>
        input file in FASTA/FASTQ format (can be compressed with gzip)
        containing sequences used for correction
    <overlaps>
        input file in MHAP/PAF/SAM format (can be compressed with gzip)
        containing overlaps between sequences and target sequences
    <target sequences>
        input file in FASTA/FASTQ format (can be compressed with gzip)
        containing sequences which will be corrected
"""


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="racon_tpu", usage=_USAGE, description=_DESCRIPTION,
        formatter_class=argparse.RawDescriptionHelpFormatter, add_help=False)
    ap.add_argument("paths", nargs="*", metavar="<file>")
    ap.add_argument("-u", "--include-unpolished", action="store_true",
                    help="output unpolished target sequences")
    ap.add_argument("-f", "--fragment-correction", action="store_true",
                    help="perform fragment correction instead of contig "
                         "polishing (overlaps file should contain dual/self "
                         "overlaps!)")
    ap.add_argument("-w", "--window-length", type=int, default=500,
                    help="default: 500; size of window on which POA is "
                         "performed")
    ap.add_argument("-q", "--quality-threshold", type=float, default=10.0,
                    help="default: 10.0; threshold for average base quality "
                         "of windows used in POA")
    ap.add_argument("-e", "--error-threshold", type=float, default=0.3,
                    help="default: 0.3; maximum allowed error rate used for "
                         "filtering overlaps")
    ap.add_argument("-m", "--match", type=int, default=5,
                    help="default: 5; score for matching bases")
    ap.add_argument("-x", "--mismatch", type=int, default=-4,
                    help="default: -4; score for mismatching bases")
    ap.add_argument("-g", "--gap", type=int, default=-8,
                    help="default: -8; gap penalty (must be negative)")
    ap.add_argument("-t", "--threads", type=int, default=1,
                    help="default: 1; OS threads for the native host "
                         "aligner (<=0 uses all cores); device execution "
                         "is batched, not threaded")
    ap.add_argument("--backend", choices=["auto", "jax", "native"],
                    default="auto",
                    help="default: auto; alignment backend — 'jax' targets "
                         "the TPU/accelerator, 'native' the C++ host "
                         "aligner, 'auto' picks by available hardware")
    ap.add_argument("--dp", type=int, default=0, metavar="N",
                    help="default: 0 (single device); shard consensus "
                         "chunks over a data-parallel mesh of N devices, "
                         "N >= 2 (see docs/DISTRIBUTED.md)")
    ap.add_argument("--distributed", action="store_true",
                    help="multi-host: call jax.distributed.initialize() "
                         "(coordinator/process env auto-detected on TPU "
                         "pods) before building the device mesh; combine "
                         "with --dp <total devices>")
    ap.add_argument("--pipeline-depth", type=int, default=None,
                    metavar="N",
                    help="default: unset (RACON_TPU_PIPELINE decides); "
                         "N>0 enables the streaming execution pipeline "
                         "with N in-flight chunks per stage (2 = double "
                         "buffering), 0 forces the serial path (see "
                         "docs/PIPELINE.md)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write a structured JSONL run trace to PATH "
                         "(same as RACON_TPU_TRACE=PATH; render with "
                         "scripts/obs_report.py — see "
                         "docs/OBSERVABILITY.md)")
    ap.add_argument("--checkpoint-dir", metavar="DIR", default=None,
                    help="checkpoint each polished contig into DIR "
                         "(FASTA shard + manifest, fsync'd per commit) "
                         "so a killed run can continue with --resume "
                         "(see docs/RESILIENCE.md)")
    ap.add_argument("--resume", action="store_true",
                    help="continue from --checkpoint-dir: committed "
                         "contigs re-emit byte-identically from the "
                         "shard, only the rest recompute; refuses if "
                         "inputs or output-affecting options changed")
    ap.add_argument("--cache-dir", metavar="DIR", default=None,
                    help="arm the content-addressed result cache in "
                         "DIR: a run whose inputs + options fingerprint "
                         "matches a stored entry re-emits it "
                         "byte-identically with zero consensus "
                         "dispatches (verify-on-hit; RACON_TPU_CACHE=0 "
                         "disables — see docs/CACHE.md)")
    ap.add_argument("--ledger-dir", metavar="DIR", default=None,
                    help="join (or start) the contig work ledger in "
                         "DIR as one worker of a preemptible fleet: "
                         "targets are sharded, leased, checkpointed "
                         "per shard, and stolen from evicted workers; "
                         "exactly one worker emits the merged FASTA "
                         "(see docs/DISTRIBUTED.md)")
    ap.add_argument("--workers", type=int, default=1, metavar="N",
                    help="default: 1; fleet size hint for the ledger's "
                         "shard partition (~2 shards per worker); only "
                         "the first worker to publish the ledger "
                         "decides")
    ap.add_argument("--worker-id", metavar="ID", default=None,
                    help="default: <hostname>-<pid>; stable identity "
                         "for lease ownership and the events audit "
                         "log")
    ap.add_argument("--lease-s", type=float, default=30.0, metavar="S",
                    help="default: 30.0; shard lease duration — an "
                         "evicted worker's shard becomes stealable S "
                         "seconds after its last renewal (each "
                         "committed contig renews)")
    ap.add_argument("--autoscale", action="store_true",
                    help="supervise an elastic fleet against "
                         "--ledger-dir instead of polishing: spawn "
                         "worker subprocesses (this same command minus "
                         "--autoscale) up to --workers, replace sick "
                         "ones, retire surplus, and emit the merged "
                         "FASTA on stdout (RACON_TPU_AUTOSCALE_* "
                         "tunes the policy; see docs/DISTRIBUTED.md)")
    ap.add_argument("--version", action="store_true",
                    help="prints the version number")
    ap.add_argument("-h", "--help", action="store_true",
                    help="prints the usage")
    return ap


def main(argv: Optional[List[str]] = None) -> int:
    """Run racon_tpu with ``argv`` (default: the process's arguments);
    returns the exit code. A ``--trace`` tracer lives for this call
    alone: once it is finished, the process tracer from before the call
    is back, so a later in-process call is not traced by it."""
    from racon_tpu.obs import trace as obs_trace
    prev = obs_trace._tracer
    try:
        return _main(argv)
    finally:
        obs_trace._tracer = prev


def _main(argv: Optional[List[str]]) -> int:
    # The autoscaler re-executes this same command line per spawned
    # worker, so keep the unparsed form around.
    raw_argv = list(argv) if argv is not None else sys.argv[1:]
    ap = build_parser()
    args = ap.parse_args(argv)

    if args.version:
        print(f"v{__version__}")
        return 0
    if args.help:
        ap.print_help()
        return 0
    if len(args.paths) < 3:
        print("[racon_tpu::] error: missing input file(s)!", file=sys.stderr)
        ap.print_help(sys.stderr)
        return 1
    # Below every early return: --version/--help/usage errors should not
    # pay the jax import the cache setup triggers.
    import time as _time
    _wall_t0 = _time.perf_counter()
    from racon_tpu.obs.trace import configure as configure_trace
    tracer = configure_trace(args.trace)

    import os as _os
    from racon_tpu.utils import envspec as _envspec
    metrics_port = _envspec.read("RACON_TPU_METRICS_PORT")
    if metrics_port:
        # Live OpenMetrics pull endpoint (daemon thread, dies with the
        # process): serves this worker's registry; fleet-wide scrapes
        # aggregate the ledger dir via scripts/obs_export.py instead.
        from racon_tpu.obs.export import (fleet_health, render_registry,
                                          serve_metrics)
        from racon_tpu.obs.metrics import registry as _reg
        from racon_tpu.resilience.watchdog import health_snapshot
        if args.ledger_dir:
            # Fleet members (and the supervisor) answer /healthz with
            # the whole fleet's view — live/evicted/retired workers,
            # open shards, autoscaler heartbeat age; a dead supervisor
            # turns the probe 503 so orchestrators restart it.
            _ld = args.ledger_dir
            health = lambda: fleet_health(_ld, base=health_snapshot)
        else:
            health = health_snapshot
        try:
            serve_metrics(int(metrics_port),
                          lambda: render_registry(_reg().snapshot()),
                          health=health)
        except (ValueError, OSError) as exc:
            print(f"[racon_tpu::] error: cannot serve metrics on port "
                  f"{metrics_port!r}: {exc}", file=sys.stderr)
            return 1

    from racon_tpu.models.overlap import PolisherError
    from racon_tpu.io.parsers import ParseError
    from racon_tpu.pipeline import configure as configure_pipeline
    from racon_tpu.pipeline import pipeline_enabled
    from racon_tpu.server.engine import JobHooks, JobSpec, build_polisher
    from racon_tpu.server.engine import polish_job
    from racon_tpu.utils.logger import Logger

    try:
        configure_pipeline(args.pipeline_depth)
    except ValueError as exc:
        print(str(exc), file=sys.stderr)
        return 1

    out = sys.stdout.buffer
    store = None
    if args.resume and not args.checkpoint_dir:
        print("[racon_tpu::] error: --resume requires --checkpoint-dir!",
              file=sys.stderr)
        return 1
    if args.ledger_dir and (args.checkpoint_dir or args.resume):
        print("[racon_tpu::] error: --ledger-dir manages per-shard "
              "checkpoints itself; drop --checkpoint-dir/--resume!",
              file=sys.stderr)
        return 1
    if args.ledger_dir and args.cache_dir:
        print("[racon_tpu::] error: --cache-dir is a whole-run store; "
              "it does not compose with --ledger-dir's per-shard "
              "leases!", file=sys.stderr)
        return 1
    if args.ledger_dir and args.workers < 1:
        print(f"[racon_tpu::] error: invalid --workers {args.workers}!",
              file=sys.stderr)
        return 1
    if args.ledger_dir and args.lease_s <= 0:
        print(f"[racon_tpu::] error: invalid --lease-s {args.lease_s}!",
              file=sys.stderr)
        return 1
    if args.autoscale:
        if not args.ledger_dir:
            print("[racon_tpu::] error: --autoscale requires "
                  "--ledger-dir!", file=sys.stderr)
            return 1
        # Supervisor mode: no polishing in this process — spawn and
        # shepherd worker subprocesses (this same command line minus
        # --autoscale) until the merged FASTA lands, then emit it.
        from racon_tpu.distributed.autoscaler import run_supervisor
        from racon_tpu.distributed.ledger import LedgerError
        try:
            return run_supervisor(ledger_dir=args.ledger_dir,
                                  raw_argv=raw_argv,
                                  default_max=args.workers, out=out)
        except LedgerError as exc:
            print(str(exc), file=sys.stderr)
            return 1
        finally:
            tracer.finish()
    # Below the supervisor branch: a supervisor starts workers that
    # need the chip, so it must not touch a JAX backend itself.
    from racon_tpu.utils.jaxcache import enable_compile_cache
    enable_compile_cache()
    logger = Logger()
    mesh = None
    if args.distributed or args.dp:
        import numpy as _np
        import jax
        if args.dp < 0:
            print(f"[racon_tpu::] error: invalid --dp {args.dp}!",
                  file=sys.stderr)
            return 1
        if args.distributed:
            # Multi-host: every host runs this same command; coordinator
            # address / process count / process id come from the TPU pod
            # runtime environment (docs/DISTRIBUTED.md has the recipe).
            jax.distributed.initialize()
        devs = jax.devices()
        ndp = args.dp if args.dp > 0 else len(devs)
        if ndp > len(devs):
            print(f"[racon_tpu::] error: --dp {ndp} exceeds the "
                  f"{len(devs)} visible devices!", file=sys.stderr)
            return 1
        if args.distributed and ndp != len(devs):
            # A mesh over devs[:ndp] would exclude some hosts' local
            # devices, which the runtime rejects (or deadlocks on);
            # multi-host meshes must span the global device set.
            print(f"[racon_tpu::] error: --distributed requires --dp to "
                  f"match the global device count ({len(devs)}); shard "
                  "hosts with the wrapper instead (docs/DISTRIBUTED.md)",
                  file=sys.stderr)
            return 1
        if ndp > 1 or args.distributed:
            # One device is the unsharded path: a 1-device mesh would
            # only compile a second set of programs that do the same.
            from jax.sharding import Mesh
            mesh = Mesh(_np.asarray(devs[:ndp]), ("dp",))

    # Everything that changes emitted bytes goes into the run
    # fingerprint (checkpoint and ledger identity alike) — single
    # source: JobSpec.identity() (racon_tpu/server/engine.py), which
    # the daemon's job journal shares, so a daemon job and a solo CLI
    # run agree on what "the same run" means. Backend / mesh /
    # pipeline knobs are excluded because the execution paths are
    # bit-identical by design.
    spec = JobSpec(
        args.paths[0], args.paths[1], args.paths[2],
        include_unpolished=args.include_unpolished,
        fragment_correction=args.fragment_correction,
        window_length=args.window_length,
        quality_threshold=args.quality_threshold,
        error_threshold=args.error_threshold, match=args.match,
        mismatch=args.mismatch, gap=args.gap, backend=args.backend,
        threads=args.threads)
    ckpt_config = spec.identity()
    if args.checkpoint_dir:
        from racon_tpu.resilience.checkpoint import (CheckpointError,
                                                     CheckpointStore,
                                                     run_fingerprint)
        try:
            fp = run_fingerprint(ckpt_config, args.paths[:3])
            from racon_tpu.ava import seg_targets_for
            store = (CheckpointStore.resume(args.checkpoint_dir, fp)
                     if args.resume else
                     CheckpointStore.create(
                         args.checkpoint_dir, fp,
                         segment_targets=seg_targets_for(
                             args.fragment_correction)))
        except (CheckpointError, OSError) as exc:
            print(str(exc), file=sys.stderr)
            return 1
        if args.resume and store.committed:
            print(f"[racon_tpu::] resuming: {len(store.committed)} "
                  f"contig(s) already committed in "
                  f"{args.checkpoint_dir}", file=sys.stderr)

    # Serial-CLI Tier-1 cache: armed only by --cache-dir (the daemon
    # arms by default), globally killable via RACON_TPU_CACHE=0.
    result_cache = None
    if args.cache_dir:
        from racon_tpu.cache import cache_enabled
        if cache_enabled():
            from racon_tpu.cache import ResultCache
            try:
                result_cache = ResultCache(args.cache_dir)
            except Exception as exc:
                print(str(exc), file=sys.stderr)
                return 1

    import signal
    import threading
    old_handlers = {}
    if threading.current_thread() is threading.main_thread():
        def _on_signal(signum, frame):
            raise _Interrupted(signum)
        for s in (signal.SIGINT, signal.SIGTERM):
            old_handlers[s] = signal.signal(s, _on_signal)

    from racon_tpu.obs import fleet
    from racon_tpu.obs.metrics import registry as obs_registry
    rc = 0

    obs_dir = _envspec.read(fleet.ENV_OBS_DIR)
    if obs_dir and not args.ledger_dir:
        # Serial runs join the fleet observability plane on request:
        # the same metric shard a ledger worker writes (workers install
        # their own writer under <ledger-dir>/obs at join time).
        from racon_tpu.resilience.checkpoint import run_fingerprint
        fp = run_fingerprint(ckpt_config, args.paths[:3])
        wid = args.worker_id or f"serial-{_os.getpid()}"
        fleet.install_writer(obs_dir, wid, fp)
        tracer.set_context(worker_id=wid, run_fp=fp)

    if not args.ledger_dir:
        # Serial runs spawned by another process (tests, orchestration)
        # adopt its trace context from RACON_TPU_TRACE_CTX; ledger
        # workers adopt inside run_worker (env first, then ledger meta).
        from racon_tpu.obs.trace import adopt_trace_context
        adopt_trace_context(tracer=tracer)

    def make_polisher():
        return build_polisher(spec, logger=logger, mesh=mesh)

    try:
        with tracer.span("run", "racon_tpu"):
            if args.ledger_dir:
                from racon_tpu.distributed.worker import run_worker
                from racon_tpu.io.parsers import scan_sequence_index
                from racon_tpu.resilience.checkpoint import \
                    run_fingerprint
                fp = run_fingerprint(ckpt_config, args.paths[:3])
                # Deferred target count: only the worker that publishes
                # the ledger meta scans the target file; later joiners
                # adopt the published count + offsets (satellite of
                # ROADMAP item 2 — per-worker full parses were pure
                # duplicated I/O).
                rc = run_worker(
                    ledger_dir=args.ledger_dir, fingerprint=fp,
                    scan_targets=lambda: scan_sequence_index(
                        args.paths[2]),
                    worker_id=args.worker_id,
                    workers=args.workers, lease_s=args.lease_s,
                    make_polisher=make_polisher,
                    drop_unpolished=not args.include_unpolished,
                    fragment_correction=args.fragment_correction,
                    window_length=args.window_length,
                    out=out)
            else:
                # The serial frontend is now a thin call into the
                # shared engine loop (racon_tpu/server/engine.py):
                # resume pruning, stored-blob re-emission interleaved
                # with fresh records in input order, and durable
                # per-contig commits all live there — one
                # implementation for CLI, ledger worker, and daemon.
                def _resume_log(n_committed: int, n_skip: int) -> None:
                    if n_skip:
                        print("[racon_tpu::] resume: skipping "
                              f"recompute of {n_skip} window(s)",
                              file=sys.stderr)

                # Cache probe/store only applies to runs starting from
                # scratch — a resumed run's committed prefix already
                # owns the output interleaving.
                fresh = store is None or not store.committed
                hit = None
                if result_cache is not None and fresh:
                    hit = result_cache.load(spec.fingerprint())
                if hit is not None:
                    from racon_tpu.cache import replay_records
                    n = replay_records(hit, emit=out.write, store=store)
                    print(f"[racon_tpu::] cache: re-emitted {n} "
                          f"contig(s) from {args.cache_dir} (zero "
                          f"consensus dispatches)", file=sys.stderr)
                else:
                    captured = [] if (result_cache is not None and
                                      fresh) else None

                    def _capture(tid, rec):
                        if rec is None:
                            captured.append((tid, None, b""))
                        else:
                            captured.append((tid, rec.name.encode(),
                                             rec.data))

                    polish_job(
                        make_polisher,
                        drop_unpolished=not args.include_unpolished,
                        store=store, emit=out.write,
                        hooks=JobHooks(
                            on_resume=_resume_log,
                            after_commit=_capture
                            if captured is not None else None))
                    if captured is not None:
                        result_cache.store(spec.fingerprint(), captured)
    except (PolisherError, ParseError, ValueError) as exc:
        print(str(exc), file=sys.stderr)
        return 1
    except _Interrupted as exc:
        out.flush()
        if args.ledger_dir:
            print(f"[racon_tpu::] interrupted (signal {exc.signum}); "
                  f"committed contigs are safe in {args.ledger_dir} — "
                  "this worker's lease will expire and a survivor (or "
                  "a rerun) will steal its shard", file=sys.stderr)
        elif store is not None:
            print(f"[racon_tpu::] interrupted (signal {exc.signum}); "
                  f"{len(store.committed)} contig(s) committed in "
                  f"{args.checkpoint_dir} — rerun with --resume",
                  file=sys.stderr)
        else:
            print(f"[racon_tpu::] interrupted (signal {exc.signum})",
                  file=sys.stderr)
        # The eviction contract: a SIGTERM'd worker leaves a *final*
        # metric snapshot (and a flight-recorder dump) for the fleet
        # aggregator before dying.
        fleet.flush_final(reason=f"signal-{exc.signum}")
        tracer.finish(metrics=obs_registry().snapshot())
        return 128 + exc.signum
    except Exception as exc:
        # Terminal watchdog breach on the SERIAL path (the ledger loop
        # handles its own self-eviction before returning): this host is
        # wedged — flush what we have and exit the distinct self-evict
        # code so supervisors reschedule elsewhere instead of retrying
        # here. Anything non-terminal propagates unchanged.
        from racon_tpu.resilience.watchdog import (EXIT_SELF_EVICT,
                                                   is_terminal)
        if not is_terminal(exc):
            raise
        out.flush()
        print(f"[racon_tpu::] terminal watchdog breach — {exc}",
              file=sys.stderr)
        fleet.flush_final(reason="watchdog-terminal")
        tracer.finish(metrics=obs_registry().snapshot())
        return EXIT_SELF_EVICT
    finally:
        for s, h in old_handlers.items():
            signal.signal(s, h)
        if store is not None:
            store.close()
    out.flush()
    logger.total("[racon_tpu::Polisher::] total =")
    from racon_tpu.obs.metrics import (pipeline_extras,
                                       set_ingest_fraction, walk_extras)
    from racon_tpu.utils.jaxcache import cache_extras
    from racon_tpu.io.ingest import ingest_enabled
    reg = obs_registry()
    for k, v in cache_extras(reg).items():
        reg.set(k, v)
    for k, v in pipeline_extras(reg).items():
        reg.set(k, v)
    for k, v in walk_extras(reg).items():
        reg.set(k, v)
    if int(reg.get("ingest_records", 0)):
        reg.set("ingest_enabled", int(ingest_enabled()))
        set_ingest_fraction(_time.perf_counter() - _wall_t0, reg)
    fleet.flush_final()
    tracer.finish(metrics=reg.snapshot())
    return rc


if __name__ == "__main__":
    sys.exit(main())
