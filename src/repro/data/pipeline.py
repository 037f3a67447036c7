"""Chunked streaming data pipeline (the paper's batch-of-10K-sets loop).

Responsibilities:
  * on-disk shard format(s): LibSVM-style text and binary .npz -- the paper
    notes binary loading is ~5x faster than text (§3.7 Table 2 caption, §6.1);
    both are implemented so benchmarks can reproduce that ratio,
  * chunked iteration: yield ``SegmentedBatch`` chunks of ``chunk_size``
    sets.  A shard travels as flat CSR ``(flat, offsets, labels)`` from
    the file to the chunk: a binary shard whose members are stored is
    read with one ``readinto`` (into a buffer reused within a pass) and
    its members viewed in place, not decoded, and each id is then copied
    once, into the chunk's fixed-width segments
    (``repro.data.sparse.segment_csr_parts``), so a heavy-tailed chunk
    costs about its real ids, not its longest row times its rows,
  * double-buffered background prefetch (overlap load with compute),
  * worker shard assignment + straggler mitigation: a shard read that
    exceeds its deadline is retried and, on repeated failure, reassigned to
    the next healthy worker (bookkeeping mirrors what a real multi-host
    data service does; on one host the "workers" are reader threads),
  * load-time accounting consumed by the online-learning benchmarks,
  * segment buffers recycled within a pass: a chunk's ids are laid out
    in the host buffer of an earlier chunk of the same shape once the
    upload from it has completed (``_SegmentRing``), so a chunk does not
    fault in tens of MB of fresh pages,
  * spans on the loader thread, one per shard read (``prep.read``: the
    whole file's ``readinto``, or the decode of a shard whose members are
    not stored) and one per chunk for the segmented layout (``prep.pad``:
    the one copy of the ids and the per-segment counts) and its hand-off
    to the device (``prep.upload``), on the ``repro.obs`` tracer.

The prefetch (``prefetch_iter``) and retry (``read_with_retries``)
machinery is shared with the signature-cache replay path in
``repro.train.online``, so hashed-shard epochs get the same straggler
story as raw-shard epochs.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import queue
import random
import struct
import tempfile
import threading
import time
import zipfile
from typing import Iterator, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.sparse import SegmentedBatch, SparseBatch, segment_csr_parts
from repro.obs.trace import Tracer, get_tracer


# ---------------------------------------------------------------------------
# Shard I/O
# ---------------------------------------------------------------------------

def write_shard_libsvm(path: str, sets: Sequence[np.ndarray], labels: np.ndarray) -> None:
    """LibSVM text: ``<label> <idx>:1 <idx>:1 ...`` (binary features)."""
    with open(path, "w") as f:
        for s, y in zip(sets, labels):
            feats = " ".join(f"{int(t)}:1" for t in s)
            f.write(f"{int(y)} {feats}\n")


def read_shard_libsvm(path: str):
    sets, labels = [], []
    with open(path) as f:
        for line in f:
            parts = line.split()
            labels.append(float(parts[0]))
            sets.append(np.array([int(p.split(":")[0]) for p in parts[1:]],
                                 np.int64))
    return sets, np.asarray(labels, np.float32)


def write_shard_binary(path: str, sets: Sequence[np.ndarray], labels: np.ndarray) -> None:
    """Binary .npz: concatenated indices + row offsets (true CSR)."""
    lens = np.array([len(s) for s in sets], np.int64)
    offsets = np.concatenate([[0], np.cumsum(lens)])
    flat = (np.concatenate(sets) if len(sets) else np.zeros((0,), np.int64))
    np.savez(path, indices=flat.astype(np.int64), offsets=offsets,
             labels=np.asarray(labels, np.float32))


def read_shard_binary(path: str):
    flat, offsets, labels = _decode_npz(path)[:3]
    sets = [flat[offsets[i]:offsets[i + 1]] for i in range(len(labels))]
    return sets, labels


class CsrShard(NamedTuple):
    """One shard as flat CSR: row ``i`` is
    ``flat[offsets[i]:offsets[i + 1]]``.  ``mapped`` says the arrays are
    views of the members in place, in ``buffer``, one host buffer that
    holds the whole file, rather than decoded copies."""

    flat: np.ndarray
    offsets: np.ndarray
    labels: np.ndarray
    mapped: bool
    buffer: Optional[np.ndarray] = None


_CSR_MEMBERS = ("indices", "offsets", "labels")
_ZIP_LOCAL_HEADER = struct.Struct("<4sHHHHHIIIHH")
_ALIGN = 64


def _decode_npz(path: str) -> CsrShard:
    with np.load(path) as z:
        return CsrShard(*(z[m] for m in _CSR_MEMBERS), mapped=False)


def _stored_npy(f, info: zipfile.ZipInfo):
    """(dtype, shape, data offset in the file) of an ``.npz`` member
    that can be viewed in place: stored, not compressed or encrypted,
    and a C-order ``.npy`` (format 1.0 or 2.0) of little-endian items
    whose data fills the member.  None otherwise."""
    if info.compress_type != zipfile.ZIP_STORED or info.flag_bits & 0x1:
        return None
    f.seek(info.header_offset)
    head = _ZIP_LOCAL_HEADER.unpack(f.read(_ZIP_LOCAL_HEADER.size))
    if head[0] != b"PK\x03\x04":
        return None
    start = info.header_offset + _ZIP_LOCAL_HEADER.size + head[9] + head[10]
    f.seek(start)
    fmt = np.lib.format
    try:
        header = {(1, 0): fmt.read_array_header_1_0,
                  (2, 0): fmt.read_array_header_2_0}.get(fmt.read_magic(f))
        if header is None:
            return None
        shape, fortran, dtype = header(f)
    except ValueError:
        return None
    if fortran or dtype.hasobject or dtype.byteorder == ">":
        return None
    data = f.tell()
    if data - start + dtype.itemsize * int(np.prod(shape)) != info.file_size:
        return None
    return dtype, shape, data


def _read_buffer(nbytes: int, spare: Optional[List[np.ndarray]]):
    """A uint8 buffer of at least ``nbytes``: the first big enough one
    popped from ``spare`` (smaller ones are dropped), else a new one."""
    while spare:
        try:
            buf = spare.pop()
        except IndexError:      # emptied by a concurrent pass
            break
        if buf.size >= nbytes:
            return buf
    return np.empty(nbytes, np.uint8)


def read_shard_csr(path: str,
                   spare: Optional[List[np.ndarray]] = None) -> CsrShard:
    """A binary shard as flat CSR.

    Where every member is stored (``np.savez``, ``write_shard_binary``)
    the whole file is read with one ``readinto`` into one buffer, placed
    so that the ids start on a 64-byte boundary, and the arrays are
    views of their members there: no per-member copy, no CRC pass.  The
    buffer comes from ``spare``, a free list of buffers whose views the
    caller no longer reads, when one is big enough (a fresh buffer costs
    a page fault per page), else it is new.  All of the shard's I/O
    happens inside this call.  Any other ``.npz``
    (``np.savez_compressed``, a big-endian or Fortran-order member) is
    decoded through ``np.load``, which gives the same arrays."""
    with open(path, "rb") as f:
        try:
            with zipfile.ZipFile(f) as zf:
                infos = {i.filename: i for i in zf.infolist()}
        except zipfile.BadZipFile:
            return _decode_npz(path)
        layout = [infos.get(m + ".npy") for m in _CSR_MEMBERS]
        layout = [None if i is None else _stored_npy(f, i) for i in layout]
        if None in layout:
            return _decode_npz(path)
        size = os.fstat(f.fileno()).st_size
        raw = _read_buffer(size + _ALIGN, spare)
        shift = -(raw.ctypes.data + layout[0][2]) % _ALIGN
        buf = raw[shift:shift + size]
        f.seek(0)
        got = f.readinto(buf)
    if got != size:
        raise OSError(f"short read of {path}: {got} of {size} bytes")
    arrays = [buf[off:off + dtype.itemsize * int(np.prod(shape))]
              .view(dtype).reshape(shape) for dtype, shape, off in layout]
    return CsrShard(*arrays, mapped=True, buffer=raw)


def read_shard_csr_libsvm(path: str) -> CsrShard:
    """A LibSVM text shard as flat CSR (decoded; ids int64)."""
    sets, labels = read_shard_libsvm(path)
    offsets = np.zeros(len(sets) + 1, np.int64)
    np.cumsum([len(s) for s in sets], out=offsets[1:])
    flat = np.concatenate(sets) if sets else np.zeros((0,), np.int64)
    return CsrShard(flat, offsets, labels, mapped=False)


def write_shards(batch_sets: Sequence[np.ndarray], labels: np.ndarray,
                 out_dir: str, n_shards: int, fmt: str = "binary") -> List[str]:
    os.makedirs(out_dir, exist_ok=True)
    paths = []
    per = (len(batch_sets) + n_shards - 1) // n_shards
    for i in range(n_shards):
        lo, hi = i * per, min((i + 1) * per, len(batch_sets))
        suffix = "npz" if fmt == "binary" else "txt"
        path = os.path.join(out_dir, f"shard_{i:05d}.{suffix}")
        writer = write_shard_binary if fmt == "binary" else write_shard_libsvm
        writer(path, batch_sets[lo:hi], labels[lo:hi])
        paths.append(path)
    return paths


# ---------------------------------------------------------------------------
# Streaming loader with prefetch + straggler handling
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LoaderStats:
    load_seconds: float = 0.0
    chunks: int = 0
    bytes_read: int = 0
    straggler_retries: int = 0
    shard_reassignments: int = 0
    io_errors: int = 0
    mapped_reads: int = 0
    decoded_reads: int = 0
    nonzeros: int = 0
    slots: int = 0
    segment_buffers_reused: int = 0
    segment_buffers_fresh: int = 0


# LoaderStats field -> (metric name, help); every field is monotone, so
# they all export as counters through ``loader_collector``
_LOADER_METRICS = {
    "load_seconds": ("data_loader_seconds_total",
                     "wall clock spent reading shards"),
    "chunks": ("data_loader_chunks_total", "chunks yielded"),
    "bytes_read": ("data_loader_bytes_read_total", "shard bytes read"),
    "straggler_retries": ("data_loader_straggler_retries_total",
                          "reads retried for exceeding the deadline"),
    "shard_reassignments": ("data_loader_shard_reassignments_total",
                            "slow reads kept after exhausted retries"),
    "io_errors": ("data_loader_io_errors_total",
                  "OSErrors absorbed by the retry loop"),
    "mapped_reads": ("data_loader_mapped_reads_total",
                     "shards read whole, members viewed in place"),
    "decoded_reads": ("data_loader_decoded_reads_total",
                      "shards decoded into fresh arrays"),
    "nonzeros": ("data_loader_nonzeros_total",
                 "real ids laid out in chunks"),
    "slots": ("data_loader_slots_total",
              "index slots laid out in chunks (segments x width), "
              "what the signature kernels hash"),
    "segment_buffers_reused": ("data_loader_segment_buffers_reused_total",
                               "chunks laid out in the segment buffer of "
                               "an earlier chunk of the pass"),
    "segment_buffers_fresh": ("data_loader_segment_buffers_fresh_total",
                              "chunks laid out in a new segment buffer"),
}


def loader_collector(role: str):
    """Registry collector factory over one ``LoaderStats`` holder.

    ``role`` labels which pipeline the stats belong to (``"load"`` = raw
    shard reads, ``"replay"`` = cached signature-shard replay); several
    live loaders with the same role sum into one process total.  Used as
    ``get_registry().register_object(stats, loader_collector("load"))``.
    """
    from repro.obs.metrics import Sample
    labels = (("role", role),)

    def collect(stats: LoaderStats):
        for field, (name, help) in _LOADER_METRICS.items():
            yield Sample(name, "counter", help, labels,
                         float(getattr(stats, field)))
    return collect


# process-wide jitter source for I/O retry backoff (callers needing
# determinism inject their own seeded ``random.Random``)
_default_backoff_rng = random.Random()


def read_with_retries(reader, path: str, stats: LoaderStats, *,
                      deadline: float, max_retries: int,
                      backoff_base_s: float = 0.05,
                      backoff_cap_s: float = 1.0,
                      rng=None, sleep=time.sleep):
    """Straggler/IO-aware shard read, shared by ``ChunkedLoader`` and the
    signature-cache replay path (``repro.train.online.SignatureCache``).

    Every attempt is accounted: an ``OSError`` bumps ``stats.io_errors``
    and is retried after an exponential backoff with jitter -- attempt
    ``i`` sleeps ``min(backoff_cap_s, backoff_base_s * 2**i)`` scaled by
    a uniform [0.5, 1.0) jitter factor, so a flapping filesystem is not
    hammered in a tight loop and concurrent readers decorrelate.  A read
    slower than ``deadline`` bumps ``stats.straggler_retries`` and
    retries *immediately* (slow is not broken; the last slow attempt is
    kept and counted as a ``shard_reassignment``).  If all
    ``max_retries + 1`` attempts raise, the last ``OSError`` propagates
    after the final attempt with no trailing sleep -- there is no silent
    unaccounted re-read.  ``rng`` (a ``random.Random``) and ``sleep``
    are injectable so tests can pin the exact sleep schedule with a
    fake clock.
    """
    if rng is None:
        rng = _default_backoff_rng
    # no exception is kept in a local: its traceback holds this frame,
    # and the cycle would keep ``stats`` (and a loader) alive until gc
    for attempt in range(max_retries + 1):
        t0 = time.perf_counter()
        try:
            out = reader(path)
        except OSError:
            stats.io_errors += 1
            if attempt == max_retries:
                raise
            delay = min(backoff_cap_s, backoff_base_s * (2.0 ** attempt))
            sleep(delay * (0.5 + 0.5 * rng.random()))
            continue
        dt = time.perf_counter() - t0
        if dt > deadline:
            if attempt < max_retries:
                # too slow: count as straggler, retry (a real service
                # would hedge the read against a replica)
                stats.straggler_retries += 1
                continue
            # retries exhausted: shard is handed to the next worker
            stats.shard_reassignments += 1
        stats.load_seconds += dt
        stats.bytes_read += os.path.getsize(path)
        return out


def prefetch_iter(make_iter, prefetch: int):
    """Double-buffered background prefetch over any chunk iterator.

    Runs ``make_iter()`` in a daemon thread, keeping up to ``prefetch``
    items ahead of the consumer (overlap load with compute).  Exceptions
    in the producer propagate to the consumer; abandoning the consumer
    mid-iteration (generator close) stops the producer thread instead of
    leaving it blocked on a full queue.  ``prefetch <= 0`` iterates
    inline.
    """
    if prefetch <= 0:
        yield from make_iter()
        return
    q: "queue.Queue" = queue.Queue(maxsize=prefetch)
    sentinel = object()
    stop = threading.Event()
    err: List[BaseException] = []

    def put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def producer():
        try:
            for item in make_iter():
                if not put(item):
                    return
        except BaseException as e:   # propagate into consumer
            err.append(e)
        finally:
            put(sentinel)

    t = threading.Thread(target=producer, daemon=True,
                         name="prefetch-producer")
    t.start()
    try:
        while True:
            item = q.get()
            if item is sentinel:
                break
            yield item
        t.join()
        if err:
            # popped, so no frame of its traceback keeps it (and them) alive
            raise err.pop()
    finally:
        # also runs on generator close (abandoned consumer): joining here
        # guarantees the producer no longer touches shared loader stats
        stop.set()
        t.join()


def device_put_iter(make_host_iter, prefetch: int = 2):
    """Double-buffered host->device upload pipeline.

    Wraps ``prefetch_iter`` with a ``jax.device_put`` applied in the
    producer thread, so the H2D copy of item i+1 overlaps the consumer's
    compute on item i (jax transfers are asynchronous; the producer only
    *enqueues* them).  Items are arbitrary pytrees of numpy arrays /
    scalars -- the out-of-core index scan streams
    ``(window_offset, window_words)`` pairs through this.
    """
    import jax

    def produce():
        for item in make_host_iter():
            yield jax.tree_util.tree_map(jax.device_put, item)

    yield from prefetch_iter(produce, prefetch)


class _SegmentRing:
    """The host buffers one pass lays its chunks' segments out in.

    ``take(shape)`` hands out the oldest held buffer of that shape, else
    a new one; ``give(buffer, uploaded)`` holds a buffer with the device
    arrays uploaded from it, at most ``size`` of them (the oldest go
    first).  The upload is asynchronous and reads the buffer, so a
    buffer is handed out again only after ``jax.block_until_ready`` on
    those arrays.  The upload copies (``jnp.array``), so what a consumer
    holds never changes when the buffer is written again.
    """

    def __init__(self, size: int, stats: LoaderStats):
        self.size = size
        self.stats = stats
        self.held: list = []

    def take(self, shape) -> np.ndarray:
        for i, (buf, uploaded) in enumerate(self.held):
            if buf.shape == shape:
                del self.held[i]
                jax.block_until_ready(uploaded)
                self.stats.segment_buffers_reused += 1
                return buf
        self.stats.segment_buffers_fresh += 1
        return np.empty(shape, np.int32)

    def give(self, buf: np.ndarray, uploaded) -> None:
        self.held.append((buf, uploaded))
        del self.held[:-self.size]


class ChunkedLoader:
    """Iterate ``SegmentedBatch`` chunks over a list of shard files.

    ``n_workers`` reader threads each own a disjoint round-robin slice of
    shards.  A read exceeding ``straggler_deadline_s`` is retried
    (``max_retries``); persistent failure reassigns the shard to the next
    worker -- the multi-host straggler story, modeled faithfully enough to
    test the control logic.  Spans go to ``tracer`` (default: the
    process-wide ``get_tracer()``).
    """

    def __init__(self, shard_paths: Sequence[str], chunk_size: int = 10_000,
                 fmt: str = "binary",
                 prefetch: int = 2, n_workers: int = 1,
                 straggler_deadline_s: float = 30.0, max_retries: int = 2,
                 io_backoff_base_s: float = 0.05,
                 io_backoff_cap_s: float = 1.0,
                 tracer: Optional[Tracer] = None):
        self.shard_paths = list(shard_paths)
        self.chunk_size = chunk_size
        self.fmt = fmt
        self.prefetch = prefetch
        self.n_workers = n_workers
        self.deadline = straggler_deadline_s
        self.max_retries = max_retries
        self.io_backoff_base_s = io_backoff_base_s
        self.io_backoff_cap_s = io_backoff_cap_s
        self.tracer = tracer if tracer is not None else get_tracer()
        self.stats = LoaderStats()
        from repro.obs.metrics import get_registry
        get_registry().register_object(self.stats, loader_collector("load"))
        # examples per shard index, recorded as shards are read; lets a
        # consumer resume mid-stream (``resume_point`` + ``iter_from``)
        self.shard_examples: dict = {}
        # read buffers no pending chunk views any more, reused by the
        # next binary read of the same pass
        self._spare: List[np.ndarray] = []
        self._reader = (functools.partial(read_shard_csr, spare=self._spare)
                        if fmt == "binary" else read_shard_csr_libsvm)

    # -- straggler-aware shard read ------------------------------------
    def _read_shard(self, path: str, worker: int):
        return read_with_retries(self._reader, path, self.stats,
                                 deadline=self.deadline,
                                 max_retries=self.max_retries,
                                 backoff_base_s=self.io_backoff_base_s,
                                 backoff_cap_s=self.io_backoff_cap_s)

    def _chunk_iter(self, start_shard: int = 0,
                    skip_examples: int = 0) -> Iterator[SegmentedBatch]:
        # the pending chunk is a list of CSR pieces (flat, row offsets,
        # labels), so a chunk may span shards; no row is split out.  A
        # shard's read buffer goes back to ``_spare`` once no pending
        # piece views it (``held``: buffers of earlier shards still in
        # ``parts``).  Segment buffers are recycled through ``ring``, one
        # per pass, bounded by what the prefetch keeps in flight
        parts: list = []
        held: list = []
        ring = _SegmentRing(max(1, self.prefetch), self.stats)
        pending = 0
        skip = skip_examples
        for i in range(start_shard, len(self.shard_paths)):
            worker = i % self.n_workers
            with self.tracer.span("prep.read"):
                shard = self._read_shard(self.shard_paths[i], worker)
            if shard.mapped:
                self.stats.mapped_reads += 1
            else:
                self.stats.decoded_reads += 1
            n = len(shard.labels)
            self.shard_examples[i] = n
            lo = min(skip, n)
            skip -= lo
            while lo < n:
                take = min(n - lo, self.chunk_size - pending)
                parts.append((shard.flat, shard.offsets[lo:lo + take + 1],
                              shard.labels[lo:lo + take]))
                pending += take
                lo += take
                if pending == self.chunk_size:
                    yield self._make_batch(parts, ring)
                    parts, pending = [], 0
                    self._spare.extend(held)
                    held.clear()
            if shard.buffer is not None:
                in_parts = bool(parts) and parts[-1][0] is shard.flat
                (held if in_parts else self._spare).append(shard.buffer)
        if parts:
            yield self._make_batch(parts, ring)
        self._spare.clear()

    def _make_batch(self, parts, ring: _SegmentRing) -> SegmentedBatch:
        """Lay the chunk's CSR pieces out as fixed-width segments in host
        arrays (the segments in a buffer from ``ring``; no view of a shard
        survives into the batch), then hand them to the device, which
        only enqueues the copies."""
        self.stats.chunks += 1
        with self.tracer.span("prep.pad"):
            idx, counts, rows = segment_csr_parts(
                [(f, o) for f, o, _ in parts], ring.take)
            lab = np.concatenate([y for _, _, y in parts]
                                 ).astype(np.float32, copy=False)
        self.stats.nonzeros += sum(int(o[-1] - o[0]) for _, o, _ in parts)
        self.stats.slots += idx.size
        with self.tracer.span("prep.upload"):
            # ``jnp.array`` copies: ``jnp.asarray`` of a numpy array may
            # alias it (the CPU backend does for 64-byte aligned ones),
            # and ``ring`` lays a later chunk out in ``idx``
            batch = SegmentedBatch(
                indices=jnp.array(idx), counts=jnp.asarray(counts),
                rows=None if rows is None else jnp.asarray(rows),
                labels=jnp.asarray(lab), n=lab.size)
        ring.give(idx, batch.indices)
        return batch

    def resume_point(self, example_offset: int):
        """Map a stream example offset -> (shard index, in-shard skip).

        Needs per-shard example counts, i.e. a completed prior pass
        (``shard_examples``).  This is how the signature cache starts a
        budget-truncated replay at the first *uncached* chunk instead of
        re-reading the cached prefix's raw shards.
        """
        cum = 0
        for i in range(len(self.shard_paths)):
            n_i = self.shard_examples.get(i)
            if n_i is None:
                raise ValueError(
                    f"resume_point({example_offset}) needs shard {i}'s "
                    "example count; complete a full pass first")
            if cum + n_i > example_offset:
                return i, example_offset - cum
            cum += n_i
        return len(self.shard_paths), 0

    def iter_from(self, start_shard: int = 0,
                  skip_examples: int = 0) -> Iterator[SegmentedBatch]:
        """Iterate chunks starting at ``start_shard``, dropping the first
        ``skip_examples`` examples (same prefetch machinery as iteration
        from the top).  Chunk boundaries line up with a full pass when
        (start_shard, skip_examples) came from ``resume_point`` of a
        chunk-aligned offset."""
        yield from prefetch_iter(
            lambda: self._chunk_iter(start_shard, skip_examples),
            self.prefetch)

    def __iter__(self) -> Iterator[SegmentedBatch]:
        yield from self.iter_from()


class SignatureStream:
    """Stream (signatures, labels) chunks: loader -> hash kernel -> b bits.

    The online-learning front half of the §3 pipeline with pluggable
    hashing scheme: ``family`` is a Hash2U/Hash4U (k-pass minwise
    hashing) or a ``repro.core.oph.OPH`` scheme (single-pass
    one-permutation hashing), executed through the
    ``repro.kernels.SignatureEngine`` (``backend`` selects interpret /
    compiled TPU / jnp reference execution).  With ``packed=True`` chunks
    are ``PackedSignatures`` -- the k*b-bit wire format, packed inside
    the kernel jit, so only packed words cross the host boundary.
    ``stats`` aggregates load/kernel accounting like ``preprocess_shards``
    does for the batch path.
    """

    def __init__(self, shard_paths: Sequence[str], family, *, b: int = 8,
                 chunk_size: int = 10_000, use_pallas: bool = True,
                 backend: Optional[str] = None, packed: bool = False,
                 loader_kwargs: Optional[dict] = None):
        from repro.kernels import SignatureEngine
        self.loader = ChunkedLoader(shard_paths, chunk_size=chunk_size,
                                    **(loader_kwargs or {}))
        self.family = family
        self.b = b
        self.use_pallas = use_pallas
        self.packed = packed
        self.engine = SignatureEngine(
            family, b=b, packed=packed,
            backend="ref" if not use_pallas else backend)
        self.kernel_seconds = 0.0
        self.examples = 0

    @property
    def cumulative_stats(self) -> dict:
        """Monotone counters for per-epoch delta accounting (the protocol
        ``repro.train.online.OnlineTrainer`` reads from any chunk source)."""
        return {"kernel_s": self.kernel_seconds,
                "bytes_read": self.loader.stats.bytes_read,
                "source": "hash"}

    def hash_chunk(self, chunk: SegmentedBatch):
        """Hash one loader chunk (with kernel-time accounting)."""
        import jax
        t0 = time.perf_counter()
        sig = self.engine(chunk)
        jax.block_until_ready(sig.data if self.packed else sig)
        self.kernel_seconds += time.perf_counter() - t0
        self.examples += chunk.n
        return sig, chunk.labels

    def __iter__(self):
        for chunk in self.loader:
            yield self.hash_chunk(chunk)


def batch_to_shards(batch: SparseBatch, out_dir: str, n_shards: int = 4,
                    fmt: str = "binary") -> List[str]:
    """Write a SparseBatch back out as raw disk shards; returns paths."""
    idx = np.asarray(batch.indices)
    msk = np.asarray(batch.mask)
    sets = [idx[i][msk[i]].astype(np.int64) for i in range(batch.n)]
    return write_shards(sets, np.asarray(batch.labels), out_dir, n_shards, fmt)


def make_sharded_dataset(spec, tmpdir: Optional[str] = None, n_shards: int = 4,
                         fmt: str = "binary", n: Optional[int] = None) -> List[str]:
    """Generate a synthetic dataset and write it as shards; returns paths."""
    from repro.data.synthetic import generate
    train, _ = generate(spec, n=n)
    out_dir = tmpdir or tempfile.mkdtemp(prefix=f"repro_{spec.name}_")
    return batch_to_shards(train, out_dir, n_shards, fmt)
