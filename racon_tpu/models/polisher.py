"""Polisher: end-to-end orchestration from input files to polished contigs.

TPU-first re-design of the reference's Polisher (src/polisher.{hpp,cpp}).
The preprocessing pipeline keeps the reference's semantics step for step
(citations inline); the execution model changes where the reference uses a
thread pool:

- per-overlap edlib alignments (src/polisher.cpp:351-364) become one
  batched native/banded-NW call (racon_tpu/native) or a device batch;
- per-window spoa tasks (src/polisher.cpp:457-469) become PoaEngine
  batches with windows as the batch dimension (racon_tpu/ops/poa.py).
"""

from __future__ import annotations

import enum
import os
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

from racon_tpu.io import parsers as iop
from racon_tpu.models.overlap import Overlap, PolisherError
from racon_tpu.models.sequence import Sequence
from racon_tpu.models.window import Window, WindowType
from racon_tpu.ops.poa import PoaEngine
from racon_tpu.utils.logger import Logger, NullLogger

# Streaming chunk size for reads/overlaps (src/polisher.cpp:22) — single
# source of truth lives with the parsers.
CHUNK_SIZE = iop.CHUNK_SIZE


class PolisherType(enum.Enum):
    kC = 0  # contig polishing (default)
    kF = 1  # fragment error-correction (-f)


class PolishedSequence:
    """Output record: polished contig with its FASTA header tags."""
    __slots__ = ("name", "data")

    def __init__(self, name: str, data: bytes):
        self.name = name
        self.data = data


def create_polisher(sequences_path: str, overlaps_path: str,
                    target_path: str, type_: PolisherType = PolisherType.kC,
                    window_length: int = 500, quality_threshold: float = 10.0,
                    error_threshold: float = 0.3, match: int = 5,
                    mismatch: int = -4, gap: int = -8,
                    backend: str = "auto", logger: Optional[Logger] = None,
                    threads: int = 1, mesh=None) -> "Polisher":
    """Validate options and dispatch parsers (src/polisher.cpp:51-130).

    ``mesh``: optional jax.sharding.Mesh with a "dp" axis — the consensus
    engine shards every chunk's job axis over it (see
    docs/DISTRIBUTED.md for single-host v5e-8 and multi-host recipes).
    """
    if not isinstance(type_, PolisherType):
        raise PolisherError(
            "[racon_tpu::create_polisher] error: invalid polisher type!")
    if window_length <= 0:
        raise PolisherError(
            "[racon_tpu::create_polisher] error: invalid window length!")
    sparser = iop.create_sequence_parser(sequences_path)
    oparser = iop.create_overlap_parser(overlaps_path)
    tparser = iop.create_sequence_parser(target_path)
    return Polisher(sparser, oparser, tparser, type_, window_length,
                    quality_threshold, error_threshold, match, mismatch,
                    gap, backend=backend, logger=logger, threads=threads,
                    mesh=mesh)


class Polisher:
    def __init__(self, sparser, oparser, tparser, type_: PolisherType,
                 window_length: int, quality_threshold: float,
                 error_threshold: float, match: int, mismatch: int,
                 gap: int, backend: str = "auto",
                 logger: Optional[Logger] = None,
                 window_chunk: int = 8192, threads: int = 1, mesh=None):
        self.sparser = sparser
        self.oparser = oparser
        self.tparser = tparser
        self.type = type_
        self.window_length = window_length
        self.quality_threshold = quality_threshold
        self.error_threshold = error_threshold
        # Host-side OS-thread fan-out for the native aligner (reference
        # -t, src/polisher.cpp:341-364); device batching is unaffected.
        self.threads = threads
        self.engine = PoaEngine(match, mismatch, gap, backend=backend,
                                threads=threads, mesh=mesh)
        self.logger = logger if logger is not None else NullLogger()
        self.window_chunk = window_chunk

        self.sequences: List[Sequence] = []
        self.windows: List[Window] = []
        self.targets_coverages: List[int] = []
        self._targets_size = 0
        self._window_type = WindowType.TGS

    # ------------------------------------------------------------ initialize

    def initialize(self) -> None:
        """Preprocess inputs into windows (src/polisher.cpp:162-449)."""
        if self.windows:
            print("[racon_tpu::Polisher::initialize] warning: "
                  "object already initialized!", file=sys.stderr)
            return
        log = self.logger
        log.begin()

        # Ingest stage (ISSUE 12): with the RACON_TPU_INGEST gate on
        # (and no io/* fault drill armed, which needs single-threaded
        # determinism), all three input files parse on background
        # threads concurrently — reads and overlaps inflate+parse while
        # targets are consumed, so phases 1-3 wait only on the slowest
        # file instead of the sum. Chunk protocol and error contract
        # are identical to the serial loops; bounded queues cap the
        # parsed-ahead memory at pipeline depth.
        from racon_tpu.io.ingest import prefetch_ok
        from racon_tpu.pipeline.streaming import (IngestPrefetcher,
                                                  serial_chunks)
        # kF single-parse (docs/AVA.md): a fragment-correction
        # invocation passes the SAME file as reads and targets
        # (``racon reads paf reads -f``), so parsing it twice doubles
        # the dominant I/O of an assembly-scale run for records the
        # dedup phase immediately discards. When the two paths are one
        # file, phase 2 feeds from the already-loaded targets instead
        # of a second parse — every lookup, comparison, and counter
        # runs as before, so the result is byte-identical.
        s_path = getattr(self.sparser, "path", None)
        t_path = getattr(self.tparser, "path", None)
        shared = (s_path is not None and t_path is not None
                  and os.path.realpath(s_path)
                  == os.path.realpath(t_path))
        prefetchers: List[IngestPrefetcher] = []
        src_s = None
        if prefetch_ok():
            pf_t = IngestPrefetcher(self.tparser, CHUNK_SIZE, "targets")
            pf_o = IngestPrefetcher(self.oparser, CHUNK_SIZE, "overlaps")
            prefetchers = [pf_t, pf_o]
            if not shared:
                pf_s = IngestPrefetcher(self.sparser, CHUNK_SIZE,
                                        "reads")
                prefetchers.append(pf_s)
                src_s = pf_s.chunks()
            src_t = pf_t.chunks()
            src_o = pf_o.chunks()
        else:
            src_t = serial_chunks(self.tparser, CHUNK_SIZE)
            if not shared:
                src_s = serial_chunks(self.sparser, CHUNK_SIZE)
            src_o = serial_chunks(self.oparser, CHUNK_SIZE)
        try:
            self._load_inputs(src_t, src_s, src_o, log)
        finally:
            for pf in prefetchers:
                pf.close()

    def _load_inputs(self, src_t, src_s, src_o, log) -> None:
        """Phases 1-7 of initialize(), consuming the three ingest chunk
        streams (prefetched or serial — same protocol). ``src_s`` may
        be None — the reads ARE the targets (kF single-parse above) —
        and phase 2 then replays the loaded target records through the
        identical dedup/bookkeeping path without touching the file."""
        # 1. Targets (src/polisher.cpp:172-187).
        self.sequences = []
        for chunk, _more in src_t:
            self.sequences.extend(chunk)
        targets_size = len(self.sequences)
        if targets_size == 0:
            raise PolisherError(
                "[racon_tpu::Polisher::initialize] error: "
                "empty target sequences set!")
        self._targets_size = targets_size

        name_to_id: Dict[str, int] = {}
        id_to_id: Dict[int, int] = {}
        for i, seq in enumerate(self.sequences):
            name_to_id[seq.name + "t"] = i
            id_to_id[i << 1 | 1] = i

        has_name = [True] * targets_size
        has_data = [True] * targets_size
        has_reverse = [False] * targets_size

        log.phase("[racon_tpu::Polisher::initialize] loaded target sequences")
        log.begin()

        # 2. Reads, streamed and deduplicated against targets
        # (src/polisher.cpp:196-234).
        if src_s is None:
            # The slice is a copy, so the loop below never iterates a
            # list it is appending to (it won't append here — every
            # "read" dedups against itself — but the invariant should
            # not depend on that).
            src_s = [(self.sequences[:targets_size], False)]
        sequences_size = 0
        total_len = 0
        for chunk, _more in src_s:
            for seq in chunk:
                total_len += len(seq.data)
                tid = name_to_id.get(seq.name + "t")
                if tid is not None:
                    tgt = self.sequences[tid]
                    if len(seq.data) != len(tgt.data) or \
                            len(seq.quality or b"") != len(tgt.quality or b""):
                        raise PolisherError(
                            "[racon_tpu::Polisher::initialize] error: "
                            f"duplicate sequence {seq.name} with unequal data")
                    name_to_id[seq.name + "q"] = tid
                    id_to_id[sequences_size << 1 | 0] = tid
                else:
                    idx = len(self.sequences)
                    self.sequences.append(seq)
                    name_to_id[seq.name + "q"] = idx
                    id_to_id[sequences_size << 1 | 0] = idx
                sequences_size += 1
        if sequences_size == 0:
            raise PolisherError(
                "[racon_tpu::Polisher::initialize] error: "
                "empty sequences set!")

        n_seqs = len(self.sequences)
        has_name += [False] * (n_seqs - targets_size)
        has_data += [False] * (n_seqs - targets_size)
        has_reverse += [False] * (n_seqs - targets_size)

        # NGS/TGS heuristic: mean read length (src/polisher.cpp:246-247).
        self._window_type = WindowType.NGS \
            if total_len / sequences_size <= 1000 else WindowType.TGS

        log.phase("[racon_tpu::Polisher::initialize] loaded sequences")
        log.begin()

        # 3. Overlaps, streamed; per-q_id-group filtering
        # (src/polisher.cpp:252-325).
        overlaps: List[Overlap] = []
        group: List[Overlap] = []

        def flush_group():
            kept = _filter_overlap_group(group, self.error_threshold,
                                         self.type)
            for o in kept:
                if o.strand:
                    has_reverse[o.q_id] = True
                else:
                    has_data[o.q_id] = True
            overlaps.extend(kept)
            group.clear()

        for chunk, _more in src_o:
            for o in chunk:
                o.transmute(self.sequences, name_to_id, id_to_id)
                if not o.is_valid:
                    continue
                if group and group[-1].q_id != o.q_id:
                    flush_group()
                group.append(o)
        flush_group()
        del name_to_id, id_to_id

        if not overlaps:
            raise PolisherError(
                "[racon_tpu::Polisher::initialize] error: "
                "empty overlap set!")

        log.phase("[racon_tpu::Polisher::initialize] loaded overlaps")
        log.begin()

        # 4. Sequence transmute: build reverse complements where some
        # overlap needs them, free what nothing references
        # (src/polisher.cpp:339-348).
        from racon_tpu.obs import metrics as obs_metrics
        with obs_metrics.timed_span("align", "revcomp",
                                    overlaps=len(overlaps)):
            for i, seq in enumerate(self.sequences):
                seq.transmute(has_name[i], has_data[i], has_reverse[i])

        # 5. Breaking points; PAF/MHAP overlaps need a global alignment
        # first. With a device backend the whole phase runs as batched
        # banded NW on the TPU and the breaking points are reduced on
        # device (racon_tpu/ops/ovl_align.py — at genome scale this
        # phase dominated initialize on the host: 551 s of a 1325 s
        # 2 Mb/30x run on one core); over-budget or uncertified lanes
        # fall back to the batched native call, which also serves the
        # CPU backend outright (src/polisher.cpp:351-364,
        # overlap.cpp:194-213).
        # Each host stretch of the phase is an ``align`` span whose
        # seconds land in ``align_seconds_<name>`` (obs/metrics.py
        # ``timed_span``; the device ones in ops/ovl_align.py).
        reg = obs_metrics.registry()
        pending = [o for o in overlaps if len(o.cigar) == 0]
        reg.inc("align_bases_total",
                sum(o.q_end - o.q_begin + o.t_end - o.t_begin
                    for o in pending))
        if pending and self.engine.backend == "jax":
            from racon_tpu.ops.ovl_align import device_breaking_points
            # Edit-distance scoring (0, -1, -1): the reference derives
            # overlap CIGARs with edlib (src/overlap.cpp:198-200), and
            # the native fallback below uses the same NativeAligner
            # defaults — all three paths pick the same alignments.
            pending = device_breaking_points(
                pending, self.sequences, self.window_length,
                match=0, mismatch=-1, gap=-1, log=sys.stderr)
        if pending:
            from racon_tpu.native.aligner import NativeAligner
            from racon_tpu.ops.cigar import ops_to_cigar
            from racon_tpu.ops.encode import encode_bases
            with obs_metrics.timed_span("align", "native_redo",
                                        overlaps=len(pending),
                                        threads=self.threads) as sp:
                # Edit-distance scoring, like edlib
                # (src/overlap.cpp:198-200).
                aligner = NativeAligner(threads=self.threads)
                pairs = []
                for o in pending:
                    q, t = o.alignment_operands(self.sequences)
                    pairs.append((encode_bases(bytes(q)),
                                  encode_bases(bytes(t))))
                bases = sum(len(q) + len(t) for q, t in pairs)
                reg.inc("align_native_bases", bases)
                # Band cells of every pass, and those the vector pass
                # computed (racon_tpu/native/nw.cpp).
                cells = np.zeros((len(pairs), 2), np.int64)
                for o, ops in zip(pending,
                                  aligner.align_batch(pairs, cells=cells)):
                    o.cigar = ops_to_cigar(ops)
                n_cells, n_vector = (int(c) for c in cells.sum(axis=0))
                sp.add(bases=bases, cells=n_cells, vector_cells=n_vector)
                reg.inc("align_native_cells", n_cells)
                reg.inc("align_native_vector_cells", n_vector)
        step = len(overlaps) // 20
        with obs_metrics.timed_span("align", "bp_walk",
                                    overlaps=len(overlaps)) as sp:
            walked = 0
            for i, o in enumerate(overlaps):
                # Overlaps the device handled already hold their
                # breaking points; the rest walk a CIGAR.
                walked += o.breaking_points is None
                o.find_breaking_points(self.sequences, self.window_length)
                # 20-tick cap as in the reference
                # (src/polisher.cpp:359-364).
                if step and (i + 1) % step == 0 and (i + 1) // step <= 20:
                    log.tick("[racon_tpu::Polisher::initialize] "
                             "aligning overlaps")
            sp.add(walked=walked)
        reg.inc("align_walk_overlaps", walked)
        log.phase("[racon_tpu::Polisher::initialize] aligned overlaps")
        log.begin()

        # 6. Cut targets into windows (src/polisher.cpp:373-388).
        w_len = self.window_length
        id_to_first_window = [0] * (targets_size + 1)
        for i in range(targets_size):
            tgt = self.sequences[i]
            data = memoryview(tgt.data)
            qual = memoryview(tgt.quality) if tgt.quality is not None else None
            k = 0
            for j in range(0, len(tgt.data), w_len):
                e = min(j + w_len, len(tgt.data))
                self.windows.append(Window(
                    i, k, self._window_type, data[j:e],
                    qual[j:e] if qual is not None else None))
                k += 1
            id_to_first_window[i + 1] = id_to_first_window[i] + k

        # 7. Route overlap segments into windows with the 2%-span and
        # mean-quality filters (src/polisher.cpp:390-446). Filters and
        # window arithmetic run vectorized over each overlap's breaking-
        # point rows (at genome scale this loop sees tens of millions of
        # rows — the per-row Python of earlier rounds dominated
        # initialize); only surviving rows pay Python list appends.
        self.targets_coverages = [0] * targets_size
        min_span = 0.02 * w_len
        for o in overlaps:
            self.targets_coverages[o.t_id] += 1
            seq = self.sequences[o.q_id]
            bps = o.breaking_points
            if bps is None or len(bps) == 0:
                o.breaking_points = None
                continue
            data = seq.reverse_complement if o.strand else seq.data
            qual = seq.reverse_quality if o.strand else seq.quality
            dmv = memoryview(data) if data is not None else None
            qmv = memoryview(qual) if qual is not None else None
            first_t = bps[:, 0]
            first_q = bps[:, 1]
            last_q1 = bps[:, 3]
            ok = (last_q1 - first_q) >= min_span
            if qual is not None:
                pref = seq.quality_prefix(o.strand)
                if pref is not None:
                    n_b = last_q1 - first_q
                    avg = (pref[last_q1] - pref[first_q]) / \
                        np.maximum(n_b, 1)
                    ok &= ~((avg < self.quality_threshold) & (n_b > 0))
            wslot = first_t // w_len
            wid = id_to_first_window[o.t_id] + wslot
            wstart = wslot * w_len
            b = first_t - wstart
            e = bps[:, 2] - wstart - 1
            for r in np.flatnonzero(ok):
                self.windows[wid[r]].add_layer(
                    dmv[first_q[r]:last_q1[r]],
                    qmv[first_q[r]:last_q1[r]] if qmv is not None
                    else None,
                    int(b[r]), int(e[r]))
            o.breaking_points = None  # freed (src/polisher.cpp:445)

        log.phase("[racon_tpu::Polisher::initialize] "
                  "transformed data into windows")

    # ----------------------------------------------------------------- polish

    def skip_targets(self, committed) -> int:
        """Drop every window of the given target ids before polishing —
        the checkpoint-resume path (racon_tpu/resilience/checkpoint.py):
        committed contigs re-emit from the shard, so their windows must
        not recompute. Pruning whole targets is safe for the assembler:
        each contig's windows restart at rank 0, so the remaining
        boundary structure is unchanged. Returns #windows dropped.
        """
        committed = set(committed)
        if not committed:
            return 0
        keep = [w for w in self.windows if w.id not in committed]
        n = len(self.windows) - len(keep)
        self.windows = keep
        return n

    def restrict_targets(self, keep) -> int:
        """Drop every window NOT belonging to the given target ids —
        the distributed-shard path (racon_tpu/distributed/): a worker
        holding a work-ledger shard polishes only that shard's contigs
        while parsing the same input files as everyone else. Pruning
        whole targets is safe for the assembler by the same argument as
        :meth:`skip_targets` (each contig's windows restart at rank 0).
        Returns #windows dropped.
        """
        keep = set(keep)
        kept = [w for w in self.windows if w.id in keep]
        n = len(self.windows) - len(kept)
        self.windows = kept
        return n

    def polish_records(self, drop_unpolished_sequences: bool = True):
        """The one polishing loop: yield ``(target_id, record-or-None)``
        as each target's last window finalizes, in target input order.

        ``record`` is None for a target dropped as unpolished — the
        completion event still yields so a checkpointing caller can
        commit the drop (resume must skip its compute too). polish()
        and polish_stream() are thin views over this; the serial and
        streaming executors feed the same assembler, so the two paths
        stay bit-identical by construction.
        """
        from racon_tpu.pipeline import pipeline_enabled
        log = self.logger
        log.begin()
        asm = _ContigAssembler(self, drop_unpolished_sequences)

        if pipeline_enabled():
            from racon_tpu.pipeline import pipeline_depth
            from racon_tpu.pipeline.streaming import stream_consensus

            def _tick():
                log.tick(
                    "[racon_tpu::Polisher::polish] generating consensus")

            for s, e in stream_consensus(self.engine, self.windows,
                                         chunk=self.window_chunk,
                                         depth=pipeline_depth(),
                                         tick=_tick):
                for i in range(s, e):
                    done = asm.feed(i, self.windows[i])
                    if done is not None:
                        yield done
            self._log_sched_summary()
        else:
            n_windows = len(self.windows)
            for s in range(0, n_windows, self.window_chunk):
                self.engine.consensus_windows(
                    self.windows[s:s + self.window_chunk])
                log.tick(
                    "[racon_tpu::Polisher::polish] generating consensus")
            self._log_sched_summary()
            for i, w in enumerate(self.windows):
                done = asm.feed(i, w)
                if done is not None:
                    yield done

        log.phase("[racon_tpu::Polisher::polish] generated consensus")
        self.windows = []

    def polish(self, drop_unpolished_sequences: bool = True
               ) -> List[PolishedSequence]:
        """Batch windows through the engine, stitch contigs in order, tag
        and emit (src/polisher.cpp:451-513).

        With the streaming pipeline enabled (RACON_TPU_PIPELINE /
        --pipeline-depth; racon_tpu/pipeline/) the underlying
        :meth:`polish_records` loop runs the overlapped executor — same
        records, bit-identical.
        """
        return [rec for _tid, rec
                in self.polish_records(drop_unpolished_sequences)
                if rec is not None]

    def polish_stream(self, drop_unpolished_sequences: bool = True):
        """Streaming polish: yield each PolishedSequence as soon as all
        of its windows finalize, while later windows are still being
        packed/computed (racon_tpu/pipeline/streaming.py).

        The pipeline retires window slices out of order (host-path items
        overtake device chunks), but stream_consensus releases ranges in
        input order, so records come out exactly as polish() would list
        them — the two are differentially tested bit-identical.
        """
        for _tid, rec in self.polish_records(drop_unpolished_sequences):
            if rec is not None:
                yield rec

    def _log_sched_summary(self) -> None:
        telem = getattr(self.engine, "sched_telemetry", None)
        if telem is not None and telem.windows:
            # One source of truth: the counters go into the process
            # metrics registry, and the stderr line is formatted from
            # the same registry keys the trace snapshot carries.
            from racon_tpu.obs.metrics import (publish_sched, registry,
                                               sched_summary_line)
            publish_sched(telem, registry())
            self.logger.line("[racon_tpu::Polisher::polish] scheduler " +
                             sched_summary_line(registry()))


class _ContigAssembler:
    """Incremental contig stitching: feed finalized windows in input
    order; the last window of each target returns ``(target_id,
    PolishedSequence-or-None)`` — None when the target is dropped as
    unpolished, so completion is still observable (the checkpoint store
    commits drops too). One implementation serves every polish path so
    the record format cannot drift between the serial and streaming
    executors (src/polisher.cpp:478-508)."""

    __slots__ = ("p", "drop", "n_windows", "_data", "_num_polished")

    def __init__(self, polisher: Polisher, drop_unpolished: bool):
        self.p = polisher
        self.drop = drop_unpolished
        self.n_windows = len(polisher.windows)
        self._data: List[bytes] = []
        self._num_polished = 0

    def feed(self, i: int, w: Window
             ) -> Optional[Tuple[int, Optional[PolishedSequence]]]:
        p = self.p
        self._num_polished += 1 if w.polished else 0
        self._data.append(w.consensus or b"")
        last = (i == self.n_windows - 1) or (p.windows[i + 1].rank == 0)
        if not last:
            return None
        ratio = self._num_polished / (w.rank + 1)
        rec: Optional[PolishedSequence] = None
        if not self.drop or ratio > 0:
            data = b"".join(self._data)
            tags = "r" if p.type == PolisherType.kF else ""
            tags += f" LN:i:{len(data)}"
            tags += f" RC:i:{p.targets_coverages[w.id]}"
            tags += f" XC:f:{ratio:.6f}"
            rec = PolishedSequence(p.sequences[w.id].name + tags, data)
        self._num_polished = 0
        self._data = []
        return (w.id, rec)


def _filter_overlap_group(group: List[Overlap], error_threshold: float,
                          type_: PolisherType) -> List[Overlap]:
    """Drop high-error and self overlaps; in kC keep only the longest
    overlap per query (src/polisher.cpp:254-278 — the reference's pairwise
    elimination keeps the last occurrence of the maximum length)."""
    kept = [o for o in group
            if o.error <= error_threshold and o.q_id != o.t_id]
    if not kept or type_ != PolisherType.kC:
        return kept
    best = kept[0]
    for o in kept[1:]:
        if o.length >= best.length:
            best = o
    return [best]
