"""Vectorized window -> pprof bytes, paired with a DictAggregator.

The "into pprof" half of the north star: after close_window() lands exact
per-stack counts on the host, every pid with samples needs a serialized
profile.proto. Done naively (per-sample scalar encode, builder.build_pprof)
that is minutes per window at 50k-pid scale — far slower than the
aggregation it follows. This encoder exploits the same stationarity the
dict aggregator exploits for counts:

  * Per-stack sample bytes are FIXED once the stack id exists: the packed
    location-id field (tag + len + varints) never changes, because per-pid
    location ids are registry-stable and append-only. They are encoded ONCE
    at id sync (vectorized) and cached as one ragged uint8 buffer; a window
    encode gathers the live ids' prefixes with a single fancy index and
    splices in only the per-window count varints.
  * Per-pid static sections (sample_type, mappings, locations, string
    table, period) change only when that pid's registry grows; they are
    cached as bytes and rebuilt incrementally (location growth appends to
    the cached location section without touching the rest).
  * Static sections are additionally CONTENT-ADDRESSED (_ContentCache):
    built blobs are interned under a digest of their build inputs, so a
    registry rotation or an encoder reset — which wipe the per-pid map —
    rebuilds by lookup instead of re-encoding, pids with identical inputs
    (forks, same-image containers) share one blob, and a restart warmed
    through pprof/statics_store.py adopts blobs straight into the cache.

Steady state — stationary stack population — therefore costs one ragged
byte gather plus one varint pass over the live ids, independent of how the
counts moved. And because a stationary population usually has the SAME
live set window after window, the encoder goes one level further: count
and time fields are serialized as fixed-width (non-minimal, legal) varints
so the whole multi-hundred-MB window serialization has a value-independent
layout, is cached as one buffer, and a repeat window is a vectorized patch
of count varints — no re-serialization at all.

Output matches builder.build_pprof for an unsymbolized profile (the
reference agent also ships unsymbolized profiles and lets the server
symbolize, pkg/profiler/pprof.go:24-72): same fields, same ids, same
string-table construction; builder.parse_pprof round-trips it, and the
differential tests assert sample-for-sample equality.

Labels are NOT embedded per sample: they ride the write request beside the
profile, exactly as the reference's batch writer carries them.

Thread-ownership contract (the encode pipeline, profiler/encode_pipeline.py):

  * The encoder instance is single-threaded BY SECTION, not by object: a
    window is split into prepare() — runs on the PROFILER thread at window
    close, sequenced with every aggregator mutation, and is the only place
    the id mirrors (_pre_flat/_pre_off/_order) are written — and
    encode_prepared(), which runs on the ENCODER thread and touches only
    the template plus the registry rows frozen into the prepared window's
    caps. The pipeline guarantees prepare() never overlaps encoder-thread
    work (it parks the worker first).
  * What the encoder carries from window to window follows the same
    split. The caps of the window before (_caps, with the pids they were
    keyed from and the aggregator's touched-pid token) are prepare()'s
    alone: it reads them, and a window that changes any of them gets a
    NEW dictionary, because the one before is still the worker's to read
    (a prepared window's caps are never mutated after the hand-off; a
    window that changes nothing is handed the same object). The order
    arrays are replaced, never written in place, since an all-live
    window's idx/pids_live ARE those arrays. The template's kept emit
    state (_Template.kept: the row look-up, the live groups, the time
    byte positions, the views list) is the encoder thread's alone:
    encode_prepared() builds, reuses and drops it, and a views list it
    hands out again is the list of the window before, valid, as that one
    was, until the next encode.
  * build_statics() may run on the encoder thread concurrently with the
    profiler thread FEEDING the next window. That is safe because the
    aggregator's registries are append-only and published behind a
    watermark (_published): a registry's location columns are read below
    a length observed under the GIL (rows under a published length never
    change, and a column that grew keeps an equal prefix), its mapping
    list likewise, id-mirror reads by the watermark, and a rotation
    observed mid-read at worst caches state that the next prepare() (which
    always sees the bumped rotation epoch, being sequenced after it)
    throws away wholesale.
"""

from __future__ import annotations

import gzip as _gzip
import hashlib as _hashlib

import numpy as np

from parca_agent_tpu.pprof import proto
from parca_agent_tpu.pprof.builder import (
    LOC_ADDRESS,
    LOC_ID,
    LOC_MAPPING_ID,
    M_BUILDID,
    M_FILENAME,
    M_ID,
    M_LIMIT,
    M_OFFSET,
    M_START,
    P_DURATION_NANOS,
    P_LOCATION,
    P_MAPPING,
    P_PERIOD,
    P_PERIOD_TYPE,
    P_SAMPLE_TYPE,
    P_STRING_TABLE,
    P_TIME_NANOS,
    VT_TYPE,
    VT_UNIT,
    _Strings,
)
from parca_agent_tpu.pprof.vec import (
    put_varints,
    put_varints_padded,
    ragged_gather,
    varint_len,
)
from parca_agent_tpu.runtime import trace as window_trace

_TAG_SAMPLE = 0x12       # field 2 (Profile.sample), wire 2
_TAG_S_LOCID = 0x0A      # field 1 (Sample.location_id), wire 2 (packed)
_TAG_S_VALUE = 0x12      # field 2 (Sample.value), wire 2 (packed)
_TAG_LOCATION = 0x22     # field 4 (Profile.location), wire 2


def _encode_location_stream(ids: np.ndarray, mids: np.ndarray,
                            addrs: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Vectorized Profile.location messages for a flat stream of
    (1-based id, mapping id, normalized address) rows (possibly many pids'
    tables concatenated). Returns (uint8 buffer, int64 per-row offsets
    [N+1]) so the caller can slice per-pid ranges."""
    n = len(ids)
    ids = np.ascontiguousarray(ids, np.uint64)
    mids = np.ascontiguousarray(mids, np.uint64)
    addrs = np.ascontiguousarray(addrs, np.uint64)
    l_id = varint_len(ids)
    l_mid = varint_len(mids)
    l_addr = varint_len(addrs)
    has_mid = mids > 0  # proto3 zero elision, as put_tag_varint does
    body = (1 + l_id) + np.where(has_mid, 1 + l_mid, 0) + (1 + l_addr)
    l_body = varint_len(body.astype(np.uint64))
    msg = 1 + l_body + body
    offs = np.zeros(n + 1, np.int64)
    np.cumsum(msg, out=offs[1:])
    out = np.empty(int(offs[-1]), np.uint8)
    p = offs[:-1]
    out[p] = _TAG_LOCATION
    put_varints(out, p + 1, body.astype(np.uint64), l_body)
    p = p + 1 + l_body
    out[p] = (LOC_ID << 3)
    put_varints(out, p + 1, ids, l_id)
    p = p + 1 + l_id
    pm = p[has_mid]
    out[pm] = (LOC_MAPPING_ID << 3)
    put_varints(out, pm + 1, mids[has_mid], l_mid[has_mid])
    p = p + np.where(has_mid, 1 + l_mid, 0)
    out[p] = (LOC_ADDRESS << 3)
    put_varints(out, p + 1, addrs, l_addr)
    return out, offs


# Profile.sample_type for every profile is the same two-entry message
# over string indices 1 ("samples") and 2 ("count") — constant bytes.
_SAMPLE_TYPE_SEC = bytes([
    (P_SAMPLE_TYPE << 3) | 2, 4,
    (VT_TYPE << 3), 1, (VT_UNIT << 3), 2,
])


def _encode_mapping_stream(mids, starts, limits, offsets, fidx, bidx):
    """Vectorized Profile.mapping messages for a flat stream of rows
    (many pids' tables concatenated; string indices are per-pid values the
    caller computed while interning). Zero-valued fields are elided,
    matching proto.put_tag_varint. Returns (uint8 buffer, int64 per-row
    offsets [N+1])."""
    cols = [np.ascontiguousarray(c, np.uint64)
            for c in (mids, starts, limits, offsets, fidx, bidx)]
    n = len(cols[0])
    lens = [varint_len(c) for c in cols]
    present = [c > 0 for c in cols]
    body = np.zeros(n, np.int64)
    for c_len, c_has in zip(lens, present):
        body += np.where(c_has, 1 + c_len, 0)
    l_body = varint_len(body.astype(np.uint64))
    msg = 1 + l_body + body
    offs = np.zeros(n + 1, np.int64)
    np.cumsum(msg, out=offs[1:])
    out = np.empty(int(offs[-1]), np.uint8)
    p = offs[:-1].copy()
    out[p] = (P_MAPPING << 3) | 2
    put_varints(out, p + 1, body.astype(np.uint64), l_body)
    p += 1 + l_body
    for field, (col, c_len, c_has) in enumerate(
            zip(cols, lens, present), start=1):
        sel = p[c_has]
        out[sel] = (field << 3)
        put_varints(out, sel + 1, col[c_has], c_len[c_has])
        p += np.where(c_has, 1 + c_len, 0)
    return out, offs


class _PidStatic:
    """Cached per-pid static sections of the profile message.

    loc_bytes is `bytes` while the section is a pure content-cache value
    (possibly SHARED across pids — cross-pid dedup) and is promoted to a
    private bytearray by _loc_extend the first time this pid appends a
    delta past the shared prefix."""

    __slots__ = ("head", "loc_bytes", "tail", "n_mappings", "n_locs",
                 "period_ns", "reg")

    def __init__(self):
        self.head = b""          # sample_type + mapping messages
        self.loc_bytes = b""     # location messages (append-only)
        self.tail = b""          # string table + period_type + period
        self.n_mappings = -1
        self.n_locs = 0
        self.period_ns = -1      # period embedded in tail (staleness guard)
        self.reg = None          # registry these sections were built from
        #                          (identity guard for the rotation-time
        #                          cache rescue: a reused pid number with
        #                          a FRESH registry must not intern the
        #                          old pid's bytes under new-content keys)


def _loc_extend(st: _PidStatic, data) -> None:
    """Append location bytes, promoting a shared cached blob to a private
    bytearray first (cache values are immutable and may be aliased by
    other pids)."""
    if not isinstance(st.loc_bytes, bytearray):
        st.loc_bytes = bytearray(st.loc_bytes)
    st.loc_bytes.extend(data)


def _ht_key(reg, n_mappings: int, period_ns: int) -> bytes:
    """Content digest of the head/tail build inputs: the first n_mappings
    registry mappings plus the period. Everything the built bytes depend
    on — and nothing else — so equal keys mean byte-equal sections."""
    h = _hashlib.blake2b(digest_size=16)
    h.update(b"H%d,%d;" % (period_ns, n_mappings))
    for m in reg.mappings[:n_mappings]:
        h.update(("%d,%d,%d,%d,%s\0%s\0" % (
            m.id, m.start, m.end, m.offset, m.path, m.build_id)).encode())
    return b"H" + h.digest()


def _loc_key(reg, n_locs: int) -> bytes:
    """Content digest of a FULL location blob's build inputs: rows
    [0, n_locs) of (mapping id, normalized address) — ids are always the
    dense 1-based numbering, so they are implied by n_locs."""
    h = _hashlib.blake2b(digest_size=16)
    h.update(n_locs.to_bytes(8, "little"))
    h.update(reg.loc_mapping_id[:n_locs].astype(np.uint64))
    h.update(reg.loc_normalized[:n_locs])
    return b"L" + h.digest()


class _ContentCache:
    """Content-addressed interning of built statics sections.

    Keys digest the build INPUTS (_ht_key/_loc_key); values are the built
    bytes. Because keys name content — not pids — the cache survives the
    events that wipe the per-pid statics map wholesale (registry
    rotation, encoder reset, a restart warmed through the statics store),
    turning those rebuild storms into lookups, and pids with identical
    inputs (forks, same-image containers) share one value (cross-pid
    dedup). Insertion-order LRU, bounded by value bytes."""

    __slots__ = ("_map", "max_bytes", "bytes", "evictions")

    def __init__(self, max_bytes: int):
        self._map: dict[bytes, tuple[object, int]] = {}
        self.max_bytes = max_bytes
        self.bytes = 0
        self.evictions = 0

    def get(self, key: bytes):
        got = self._map.pop(key, None)
        if got is None:
            return None
        self._map[key] = got  # re-insert: recency order
        return got[0]

    def put(self, key: bytes, value, nbytes: int) -> None:
        if key in self._map or nbytes > self.max_bytes:
            return
        self._map[key] = (value, nbytes)
        self.bytes += nbytes
        while self.bytes > self.max_bytes and self._map:
            # dict order = insertion/recency order (get re-inserts), so
            # the first key is the least recently used.
            _, sz = self._map.pop(next(iter(self._map)))
            self.bytes -= sz
            self.evictions += 1


class _PieceCache:
    """One slot per template group for the COMPRESSED form of the group's
    static span, kept for the ship path (agent/writer.py splices it into
    every window's gzip member instead of deflating the span again). A
    slot holds (revision, piece); the revision is the one the span's
    bytes were written under (_Template.span_rev), so a piece made from
    bytes since rewritten is never handed out. The cache is an attribute
    of the template's layout: a relayout replaces it, taking over the
    pieces of the spans it lays down again byte for byte
    (WindowEncoder._held_pieces); a reset starts an empty one, and
    nothing else ever evicts."""

    __slots__ = ("slots", "nbytes")

    def __init__(self, n_groups: int):
        self.slots: list = [None] * n_groups
        self.nbytes = 0  # compressed bytes held


class _SpanTable:
    """One encoded window's static spans by group, as plain lists (the
    template's arrays at emit time), with the layout's piece cache: what
    every _SpanBlob of the window shares, so a blob itself is three
    references."""

    __slots__ = ("off", "length", "rev", "pieces")

    def __init__(self, tmpl: "_Template"):
        self.off = tmpl.span_off.tolist()
        self.length = tmpl.span_len.tolist()
        self.rev = tmpl.span_rev.tolist()
        self.pieces = tmpl.pieces


class _SpanBlob:
    """One pid's profile as the writer receives it from a pipelined
    window: a zero-copy bytes-like over the blob in the template buffer
    (buffer protocol, len()) that also says where the blob's
    static span [head][locations][tail] lies (`static_span`: offset and
    length inside the blob) and reaches the group's slot for the span's
    compressed piece. Valid, like the view, until the next encode."""

    __slots__ = ("_view", "_spans", "_g")

    def __init__(self, view: memoryview, spans: _SpanTable, g: int):
        self._view = view
        self._spans = spans
        self._g = g

    def __buffer__(self, flags: int) -> memoryview:
        return self._view

    def __len__(self) -> int:
        return self._view.nbytes

    @property
    def static_span(self) -> tuple[int, int]:
        t, g = self._spans, self._g
        return t.off[g], t.length[g]

    def static_piece(self) -> bytes | None:
        """The span's compressed piece, if one made from the bytes now
        in the span is held."""
        t, g = self._spans, self._g
        slot = t.pieces.slots[g]
        if slot is not None and slot[0] == t.rev[g]:
            return slot[1]
        return None

    def keep_static_piece(self, piece: bytes) -> None:
        t, g = self._spans, self._g
        cache = t.pieces
        old = cache.slots[g]
        cache.slots[g] = (t.rev[g], piece)
        cache.nbytes += len(piece) - (len(old[1]) if old is not None else 0)


class _SpanViews(list):
    """What a views=True encode returns: [(pid, memoryview)] as it always
    was, to every consumer that iterates, indexes or measures it, plus
    the window's span table. The ship path asks for `span_blobs()`: the
    same pairs with each view wrapped, one at a time as it is written,
    into a _SpanBlob (so the wrapping costs the encode nothing and no
    window's worth of wrappers is ever alive at once)."""

    __slots__ = ("_spans", "_groups")

    def __init__(self, pairs, spans: _SpanTable, groups: list):
        super().__init__(pairs)
        self._spans = spans
        self._groups = groups    # group index of each pair

    def span_blobs(self):
        spans = self._spans
        for (pid, view), g in zip(self, self._groups):
            yield pid, _SpanBlob(view, spans, g)


class _Kept:
    """What one window's encode leaves with the template for the next:
    everything a window computes from the layout and from WHICH ids are
    live, never from their counts. It stands while the layout stands:
    _build_layout and _append_rows (with the relocations and rewrites
    under it) drop it, so do a reset and a rotation (the template goes),
    and a window whose ids are another array than `idx` reuses only what
    depends on the layout alone."""

    __slots__ = ("time_idx", "dur_idx", "idx", "row", "live_g", "views")

    def __init__(self, tmpl: "_Template", time_w: int):
        w = np.arange(time_w, dtype=np.int64)
        # Byte positions of every group's time and duration varints.
        self.time_idx = tmpl.time_pos[:, None] + 1 + w[None, :]
        self.dur_idx = self.time_idx + 1 + time_w
        self.idx = None      # the window's id array (identity is the test)
        self.row = None      # tmpl.row_of[idx]
        self.live_g = None   # bool [G]: groups with a live row
        self.views = None    # _SpanViews over the live groups, if built


class _Template:
    """Cached whole-window serialization: every pid's profile bytes laid
    out in one uint8 buffer, one independent blob slice per pid, with the
    positions of the per-window-variable bytes (fixed-width count varints
    and the shared time/duration fields) recorded.

    The template survives WINDOW CHURN, not just identical windows:

      * a template row whose stack got no samples this window is patched
        to count 0 (legal protobuf, same profile semantics) instead of
        forcing a relayout;
      * new stacks append sample rows into per-pid slack reserved at
        build time (protobuf field order is free, so appended rows after
        the time fields are legal), and new location messages append the
        registry's append-only delta the same way;
      * a pid whose slack is exhausted (or whose head/tail statics
        changed) relocates its blob to the end of the buffer — blobs are
        independent slices, their order in the buffer is meaningless —
        leaving a hole that is accounted as waste;
      * a full rebuild happens only when dead rows, waste, or the append
        volume cross thresholds (see encode()).

    Without this, every real window (where SOME stack goes cold or new
    stacks appear — i.e. all of them) would pay the full relayout; the
    patch path would only ever serve the bench's repeated identical
    window."""

    __slots__ = ("buf", "n_rows", "row_of", "row_id", "row_group",
                 "val_pos", "pids", "blob_start", "blob_end", "cap_end",
                 "time_pos", "group_of", "g_head_len", "g_tail_len",
                 "g_loc_len", "g_static", "span_off", "span_len", "span_rev",
                 "pieces", "alloc_end", "waste", "rotations", "period_ns",
                 "kept")

    def __init__(self):
        self.buf = None          # np.uint8 big buffer
        self.n_rows = 0          # sample rows currently in the template
        self.row_of = None       # int64 [>=synced] id -> row (-1 absent)
        self.row_id = None       # int64 [n_rows] row -> id
        self.row_group = None    # int32 [n_rows] row -> group
        self.val_pos = None      # int64 [n_rows] count-varint positions
        self.pids = None         # int32 [G]
        self.blob_start = None   # int64 [G] blob slice starts
        self.blob_end = None     # int64 [G] blob slice ends (exclusive)
        self.cap_end = None      # int64 [G] region capacity limits
        self.time_pos = None     # int64 [G] per-pid time-field positions
        self.group_of = None     # dict pid -> group index
        self.g_head_len = None   # int64 [G] static head bytes in blob
        self.g_tail_len = None   # int64 [G] static tail bytes in blob
        self.g_loc_len = None    # int64 [G] location bytes in blob
        self.g_static = None     # list [G]: the _PidStatic each group's
        #                          span was last written from
        # The static span: the contiguous run [head][locations][tail] as
        # it was LAID DOWN, blob-relative (a relocation moves a blob, not
        # the span inside it). Not g_head_len + g_loc_len + g_tail_len:
        # _append_rows adds a location delta behind the time tail and
        # raises g_loc_len by it, so that sum outgrows the run.
        self.span_off = None     # int64 [G] span start inside the blob
        self.span_len = None     # int64 [G] span length
        self.span_rev = None     # int64 [G] bumped when the span's bytes
        #                          are rewritten (never by a move)
        self.pieces = None       # _PieceCache, one slot per group
        self.alloc_end = 0       # buffer high-water mark
        self.waste = 0           # relocation holes, bytes
        self.rotations = -1      # aggregator rotation epoch at build
        self.period_ns = -1      # period the cached statics embed
        self.kept = None         # _Kept: the last encode's, while it stands


class _PreparedWindow:
    """One closed window, frozen on the profiler thread for hand-off to the
    encoder thread: the live ids/counts (copies — the aggregator's counts
    buffer is only valid for one close) plus per-pid registry caps
    (registry object, mapping count, location count) captured while no
    mutation could be in flight. encode_prepared() reads registries only
    through these caps, so the next window's inserts can never tear the
    bytes of this one."""

    __slots__ = ("idx", "vals", "pids_live", "time_ns", "duration_ns",
                 "period_ns", "rotations", "caps", "sink_ctx")

    def __init__(self, idx, vals, pids_live, time_ns, duration_ns,
                 period_ns, rotations, caps):
        self.idx = idx
        self.vals = vals
        self.pids_live = pids_live
        self.time_ns = time_ns
        self.duration_ns = duration_ns
        self.period_ns = period_ns
        self.rotations = rotations
        self.caps = caps
        # Output-backend context (sinks/): a rotation-consistent
        # RegistryView captured on the profiler thread at hand-off, so
        # secondary sinks can read per-id frame mirrors on the encode
        # worker without racing cold-stack rotation. None until (and
        # unless) a sink capture hook fills it.
        self.sink_ctx = None


def _reg_cap(reg) -> tuple:
    """(registry, safe mapping count, safe location count) for concurrent
    readers: the location columns have one published length, set after
    the rows it covers are written, and mappings are appended BEFORE any
    location row references them — which is only a guarantee if the
    LOCATION length is read first (reading the mapping count first could
    miss a mapping that location rows read a moment later already
    reference)."""
    n_locs = reg.n_locs
    return (reg, len(reg.mappings), n_locs)


def _distinct_sorted(a: np.ndarray) -> np.ndarray:
    """The distinct values of a sorted array: those that differ from
    the neighbour before them (np.unique would sort them again)."""
    head = np.ones(len(a), bool)
    np.not_equal(a[1:], a[:-1], out=head[1:])
    return a[head]


_WTAIL_LEN = 22  # [tag][10B time][tag][10B duration], fixed-width


def _padded_bytes(v: int, width: int) -> np.ndarray:
    """Fixed-width varint of one value as a uint8 array (see
    vec.put_varints_padded for why non-minimal encodings are used)."""
    out = np.empty(width, np.uint8)
    vv = v & ((1 << 64) - 1)
    for k in range(width):
        b = (vv >> (7 * k)) & 0x7F
        if k < width - 1:
            b |= 0x80
        out[k] = b
    return out


class WindowEncoder:
    """Stateful encoder; reuse one instance per DictAggregator.

    compress=True gzips each profile (local-store mode): the template is
    still built and patched the same way, but every window pays a gzip
    pass over the full output. The remote-write path ships raw protobuf
    (the channel compresses) and skips that per-window cost."""

    _VAL_W = 5    # fixed-width count varint: covers the int32 window bound
    _TIME_W = 10  # fixed-width time/duration varint: covers any uint64

    def __init__(self, agg, compress: bool = False,
                 statics_cache_bytes: int = 256 << 20):
        self._agg = agg
        self._compress = compress
        # Content-addressed statics interning (digest of build inputs ->
        # built bytes): survives rotation/reset/adoption, dedups across
        # pids. Sized generously — values alias the per-pid sections, so
        # the marginal footprint is only the cross-content variety.
        self._cache = _ContentCache(statics_cache_bytes)
        self._synced = 0                 # ids with cached sample prefixes
        self._rotations = -1             # aggregator rotation epoch mirror
        self._pre_flat = np.empty(4096, np.uint8)
        # _pre_off[0.._synced] are valid; capacity grows by doubling (a
        # per-sync concatenate would re-copy ~8 MB of offsets per window
        # at 1M ids just to append a trickle of new stacks).
        self._pre_off = np.zeros(1024, np.int64)
        # Both order arrays are replaced, never written in place: a
        # window whose every id is live hands them to the worker as its
        # idx/pids_live (prepare), so they are read-only.
        self._order = None               # ids sorted by pid (int64)
        self._order_pid = None           # pid per sorted slot (int32)
        # The caps of the window before, carried to the next prepare
        # (_window_caps): the dictionary that window was handed (never
        # mutated: a window that changes anything gets a new one), the
        # pids_live array it was keyed from (identity says "the same
        # pids" without a pass) with its distinct pids, and the token of
        # the aggregator's touched-pid report as of that prepare.
        self._caps: dict | None = None
        self._caps_src = None
        self._caps_pids = None
        self._touch_token = None
        self._static: dict[int, _PidStatic] = {}
        # (registry version, period) after a scan that found NOTHING
        # dirty: while the aggregator reports the same version, the
        # O(pids) staleness scan in build_statics/statics_backlog is
        # provably a no-op and is skipped (it used to run per drain).
        self._statics_clean: tuple | None = None
        self._tmpl = _Template()
        # Static-span revisions: one counter for the encoder's life, so
        # a revision is never handed out twice (reset() keeps it).
        self._span_rev = 0
        self.timings: dict[str, float] = {}
        # Per-encode observability (ADVICE round 5): the churn-tolerant
        # template ships dead rows as count-0 samples — legal protobuf,
        # same profile semantics, but wire bytes the reference never
        # emits. The fraction makes that bloat monitorable (docs/parity.md
        # records the deviation).
        self.stats: dict[str, float | int] = {
            "windows_encoded": 0,
            # Windows that laid every template out again (the first, one
            # after the id space was compacted, one with more than half
            # its rows dead or new) instead of patching counts.
            "layouts_built": 0,
            "template_rows": 0,
            "dead_rows": 0,
            "dead_row_fraction": 0.0,
            # Content-addressed statics accounting: hits/misses count
            # cache lookups; built/reused count the section BYTES that
            # were vectorized-encoded vs served from the cache (the dedup
            # ratio is reused / (built + reused)); append_fast/slow count
            # churn-append pid groups by path.
            "statics_cache_hits": 0,
            "statics_cache_misses": 0,
            "statics_cache_bytes": 0,
            "statics_cache_evictions": 0,
            "statics_bytes_built": 0,
            "statics_bytes_reused": 0,
            "statics_dedup_ratio": 0.0,
            "statics_adopted_pids": 0,
            "append_fast_groups": 0,
            "append_slow_groups": 0,
            # The state carried from window to window (docs/perf.md
            # "what a window costs the encoder"): registry caps read in
            # prepare (one a pid: 0 a steady window) and windows that
            # read every live pid's (the first, an epoch change, an
            # aggregator without a touched-pid report, a short counts
            # buffer); ids merged into the pid order and full argsorts
            # of it; encodes that handed out the views of the one before.
            "caps_refreshed_total": 0,
            "caps_rebuilds_total": 0,
            "order_merged_ids_total": 0,
            "order_rebuilds_total": 0,
            "views_reused_total": 0,
            # Changes of the aggregator's registry_epoch this encoder
            # met with mirrors to lose, and the per-pid statics it kept
            # (it followed the compaction's remap and the pid's registry
            # is the object it held) or dropped across them.
            "epoch_changes_total": 0,
            "epoch_statics_kept_total": 0,
            "epoch_statics_dropped_total": 0,
            # Statics build clock: per-call duration (the gauge) and the
            # monotone accumulator the pipeline worker diffs to span the
            # statics work that ran INSIDE one window's encode. The same
            # per-call number feeds the "statics" stage histogram
            # (runtime/trace.py), so gauge and histogram cannot disagree.
            "last_statics_build_s": 0.0,
            "statics_build_s_total": 0.0,
        }
        # Last inline-encoded prepared window, stashed by encode() ONLY
        # when a consumer opted in (track_prep — the profiler sets it
        # when secondary sinks are bound): the prepared arrays are
        # MB-scale at large row counts and must not outlive the window
        # for callers with no sink fan-out. Pipelined windows travel as
        # preps directly and never ride this.
        self.track_prep = False
        self.last_prep = None

    # -- content cache -------------------------------------------------------

    def _cache_get(self, key: bytes):
        got = self._cache.get(key)
        if got is None:
            self.stats["statics_cache_misses"] += 1
            return None
        self.stats["statics_cache_hits"] += 1
        return got

    def _cache_put(self, key: bytes, value, nbytes: int) -> None:
        self._cache.put(key, value, nbytes)
        self.stats["statics_cache_bytes"] = self._cache.bytes
        self.stats["statics_cache_evictions"] = self._cache.evictions

    def _count_statics_bytes(self, built: int = 0, reused: int = 0) -> None:
        self.stats["statics_bytes_built"] += built
        self.stats["statics_bytes_reused"] += reused
        total = (self.stats["statics_bytes_built"]
                 + self.stats["statics_bytes_reused"])
        self.stats["statics_dedup_ratio"] = (
            self.stats["statics_bytes_reused"] / total if total else 0.0)

    # -- mirrors -------------------------------------------------------------

    def _sync(self) -> None:
        """Bring the per-id sample-prefix cache and the pid sort order up to
        the aggregator's current registry (cheap when nothing changed).
        Paces itself by the aggregator's PUBLISHED watermark, not _next_id:
        a concurrent feed assigns ids before their metadata lands, and the
        watermark only advances once the rows are complete."""
        agg = self._agg
        # The id space's epoch: a rotation, a pid invalidation or a
        # reclaim compacted it (aggregators without one never remap).
        rot = getattr(agg, "registry_epoch", 0)
        if rot != self._rotations:
            if self._rotations < 0:
                self._drop_mirrors()    # never synced: nothing to lose
            else:
                with window_trace.child("epoch_remap"):
                    self._change_epoch()
            self._rotations = rot
        n = getattr(agg, "_published", None)
        if n is None:
            n = agg._next_id
        if n > self._synced:
            # The pid order lags until _ensure_order merges the new ids
            # in (its length is its own watermark).
            self._extend_prefixes(self._synced, n)
            self._synced = n

    def _change_epoch(self) -> None:
        """The id space was compacted under synced mirrors. Where the
        aggregator says where the ids went (id_remap: one compaction
        back, over exactly the ids synced) the mirrors follow: a
        compaction never edits a surviving pid's registry, so its
        sample prefixes, its place in the pid order, its static
        sections and its caps are what they were, under new ids. The
        template is laid out again by the window's encode (its rows
        are keyed by id), from these kept parts. Otherwise every mirror
        goes, as it always did."""
        agg = self._agg
        take = getattr(agg, "id_remap", None)
        remap = take(self._rotations) if take is not None else None
        self.stats["epoch_changes_total"] += 1
        if remap is None or len(remap) != self._synced:
            # Rescue the location blobs into the content cache first:
            # the blobs are still exact and the imminent rebuild can be
            # lookups instead of re-encodes. (Head/tail pairs were
            # cached at build time; delta-extended loc blobs were not.)
            for pid, st in self._static.items():
                reg = agg._pids.get(pid)
                if (reg is None or reg is not st.reg
                        or st.n_locs == 0
                        or reg.n_locs < st.n_locs):
                    continue
                self._cache_put(_loc_key(reg, st.n_locs),
                                bytes(st.loc_bytes), len(st.loc_bytes))
            self.stats["epoch_statics_dropped_total"] += len(self._static)
            self._drop_mirrors()
            return
        kept = np.flatnonzero(remap >= 0)
        off = self._pre_off
        flat, new_off = ragged_gather(self._pre_flat, off[kept],
                                      off[kept + 1] - off[kept])
        self._pre_flat = flat
        self._pre_off = np.empty(max(len(off), len(new_off)), np.int64)
        self._pre_off[: len(new_off)] = new_off
        self._synced = len(kept)
        if self._order is not None:
            # Survivors keep their order, so the order by pid stands.
            order = remap[self._order]
            live = order >= 0
            order, order_pid = order[live], self._order_pid[live]
            order.flags.writeable = False
            order_pid.flags.writeable = False
            self._order, self._order_pid = order, order_pid
        n_before = len(self._static)
        pids = agg._pids
        self._static = {pid: st for pid, st in self._static.items()
                        if pids.get(pid) is st.reg}
        self.stats["epoch_statics_kept_total"] += len(self._static)
        self.stats["epoch_statics_dropped_total"] += \
            n_before - len(self._static)
        self._statics_clean = None

    def _drop_mirrors(self) -> None:
        self._synced = 0
        self._pre_off[0] = 0
        self._static.clear()
        self._statics_clean = None
        self._order = None
        self._order_pid = None
        self._caps = None

    def reset(self) -> None:
        """Drop every mirror, cached static, and the template; the next
        encode rebuilds from the aggregator's registry. For recovery after
        an encode aborted mid-flight (encoder-thread exception) left the
        template state inconsistent. The CONTENT cache deliberately
        survives: its values are immutable bytes keyed by input digests —
        an aborted encode cannot have corrupted them, and they are what
        makes the post-reset rebuild cheap."""
        self._drop_mirrors()
        self._rotations = -1
        self._tmpl = _Template()
        self.last_prep = None

    def static_piece_bytes(self) -> int:
        """Compressed bytes the template's piece cache holds now."""
        pieces = self._tmpl.pieces
        return 0 if pieces is None else pieces.nbytes

    def _ensure_order(self) -> None:
        """Bring the id-by-pid sort order up to the synced id space.
        Lazy and separate from _sync: encode() is the only consumer, and
        the per-drain statics prebuild syncs on the polling thread every
        second — paying for the order there during population growth
        would be for nothing.

        Ids the order has not seen are sorted by pid among themselves
        and merged in: after every known id of their pid and in id
        order, which is where the stable argsort of the whole id space
        puts them. Only a lost order (the first window, an epoch change,
        a reset) is sorted whole."""
        n = self._synced
        if self._order is None:
            pids = self._agg._id_pid[:n].astype(np.int32, copy=False)
            order = np.argsort(pids, kind="stable").astype(np.int64)
            order_pid = pids[order]
            self.stats["order_rebuilds_total"] += 1
            window_trace.count(encode_order_rebuilds=1)
        elif len(self._order) < n:
            s = len(self._order)
            pids = self._agg._id_pid[s:n].astype(np.int32, copy=False)
            by = np.argsort(pids, kind="stable")
            at = np.searchsorted(self._order_pid, pids[by], side="right")
            order = np.insert(self._order, at, by + s)
            order_pid = np.insert(self._order_pid, at, pids[by])
            self.stats["order_merged_ids_total"] += n - s
            window_trace.count(encode_order_merged_ids=n - s)
        else:
            return
        order.flags.writeable = False
        order_pid.flags.writeable = False
        self._order, self._order_pid = order, order_pid

    def _extend_prefixes(self, s: int, n: int) -> None:
        """Encode the fixed Sample prefix (location_id field) for ids
        [s, n): one vectorized pass over all their frames."""
        agg = self._agg
        off = agg._loc_off
        base = int(off[s])
        frames = agg._loc_flat[base: int(off[n])].astype(np.uint64)
        rel = (off[s: n + 1] - base).astype(np.int64)  # per-id frame offsets

        fl = varint_len(frames)
        cs = np.zeros(len(frames) + 1, np.int64)
        np.cumsum(fl, out=cs[1:])
        pb = cs[rel[1:]] - cs[rel[:-1]]          # packed body bytes per id
        l_pb = varint_len(pb.astype(np.uint64))
        pre = 1 + l_pb + pb                      # tag + len + packed ids
        if n + 1 > len(self._pre_off):
            grown = np.empty(max(n + 1, 2 * len(self._pre_off)), np.int64)
            grown[: s + 1] = self._pre_off[: s + 1]
            self._pre_off = grown
        new_off = self._pre_off[s: n + 1]        # continue the cache tail
        tail0 = int(new_off[0])
        np.cumsum(pre, out=new_off[1:])
        new_off[1:] += tail0

        need = int(new_off[-1])
        if need > len(self._pre_flat):
            grown = np.empty(max(need, 2 * len(self._pre_flat)), np.uint8)
            grown[:tail0] = self._pre_flat[:tail0]
            self._pre_flat = grown
        out = self._pre_flat
        p = new_off[:-1]
        out[p] = _TAG_S_LOCID
        put_varints(out, p + 1, pb.astype(np.uint64), l_pb)
        # Frame varints: frame k of id i lands at that id's body start plus
        # the within-id byte cumsum.
        depths = rel[1:] - rel[:-1]
        body_start = p + 1 + l_pb
        fpos = cs[:-1] + np.repeat(body_start - cs[rel[:-1]], depths)
        put_varints(out, fpos, frames, fl)

    # -- static sections -----------------------------------------------------

    def _build_head_tail(self, st: _PidStatic, reg, period_ns: int,
                         n_mappings: int | None = None) -> None:
        """Rebuild the string-bearing sections (sample_type + mappings +
        string table + period). Location ids/addresses carry no strings, so
        the cached location section survives a mapping change (mapping ids
        are registry-stable and append-only). n_mappings bounds the read
        for encoder-thread callers (a concurrent feed may be appending)."""
        if n_mappings is None:
            n_mappings = len(reg.mappings)
        key = _ht_key(reg, n_mappings, period_ns)
        got = self._cache_get(key)
        if got is not None:
            st.head, st.tail = got
            st.n_mappings = n_mappings
            st.period_ns = period_ns
            self._count_statics_bytes(reused=len(st.head) + len(st.tail))
            return
        strings = _Strings()
        w = proto.Writer()
        vt = proto.Writer().varint(VT_TYPE, strings("samples")) \
            .varint(VT_UNIT, strings("count"))
        w.message(P_SAMPLE_TYPE, vt.buf)
        for m in reg.mappings[:n_mappings]:
            mw = (
                proto.Writer()
                .varint(M_ID, m.id)
                .varint(M_START, m.start)
                .varint(M_LIMIT, m.end)
                .varint(M_OFFSET, m.offset)
                .varint(M_FILENAME, strings(m.path))
                .varint(M_BUILDID, strings(m.build_id))
            )
            w.message(P_MAPPING, mw.buf)
        st.head = bytes(w.buf)
        pt = proto.Writer().varint(VT_TYPE, strings("cpu")) \
            .varint(VT_UNIT, strings("nanoseconds"))
        tail = bytearray()
        for s_ in strings.table:
            proto.put_tag_bytes(tail, P_STRING_TABLE, s_.encode())
        proto.put_tag_bytes(tail, P_PERIOD_TYPE, bytes(pt.buf))
        proto.put_tag_varint(tail, P_PERIOD, period_ns)
        st.tail = bytes(tail)
        st.n_mappings = n_mappings
        st.period_ns = period_ns
        self._cache_put(key, (st.head, st.tail), len(st.head) + len(st.tail))
        self._count_statics_bytes(built=len(st.head) + len(st.tail))

    def _ensure_static(self, pid: int, period_ns: int,
                       cap: tuple | None = None) -> _PidStatic:
        """Per-pid static sections, built to at least `cap` (registry,
        n_mappings, n_locs). Without a cap — same-thread callers only —
        the registry's current lengths are the target. A static built
        FURTHER than the cap (a prebuild raced ahead) is kept: extra
        unreferenced locations are legal pprof."""
        if cap is None:
            cap = _reg_cap(self._agg._pids[pid])
        reg, n_mappings, n_locs = cap
        st = self._static.get(pid)
        if st is None:
            st = self._static[pid] = _PidStatic()
        st.reg = reg
        if st.n_mappings < n_mappings or st.period_ns != period_ns:
            self._build_head_tail(st, reg, period_ns,
                                  max(n_mappings, st.n_mappings))
        if st.n_locs < n_locs:
            key = None
            if st.n_locs == 0:
                # Full blob: content-addressable (post-rotation rebuilds
                # and restart adoption land here with a warm cache).
                key = _loc_key(reg, n_locs)
                got = self._cache_get(key)
                if got is not None:
                    st.loc_bytes = got
                    st.n_locs = n_locs
                    self._count_statics_bytes(reused=len(got))
                    return st
            ids = np.arange(st.n_locs + 1, n_locs + 1, dtype=np.uint64)
            mids = reg.loc_mapping_id[st.n_locs:n_locs].astype(np.uint64)
            addrs = reg.loc_normalized[st.n_locs:n_locs]
            buf, _ = _encode_location_stream(ids, mids, addrs)
            data = buf.tobytes()
            self._count_statics_bytes(built=len(data))
            if key is not None:
                st.loc_bytes = data
                self._cache_put(key, data, len(data))
            else:
                _loc_extend(st, data)
            st.n_locs = n_locs
        return st

    def _build_tails_batch(self, tables, cpu_idx, nano_idx,
                           period_ns: int) -> list[bytes]:
        """Vectorized per-pid tail sections (string table + period_type +
        period): the scalar loop paid ~3 put_varint calls per string —
        hundreds of thousands of Python calls on a cold 10k-pid build —
        here every tag, length varint, and payload byte across the whole
        batch lands in a handful of whole-array passes."""
        n_pids = len(tables)
        blobs = [s.encode() for tbl in tables for s in tbl]
        joined = np.frombuffer(b"".join(blobs), np.uint8)
        slen = np.fromiter(map(len, blobs), np.int64, len(blobs))
        l_slen = varint_len(slen.astype(np.uint64))
        smsg = 1 + l_slen + slen                 # tag + len varint + bytes
        counts = np.fromiter(map(len, tables), np.int64, n_pids)
        sbounds = np.zeros(n_pids + 1, np.int64)
        np.cumsum(counts, out=sbounds[1:])
        csum = np.zeros(len(blobs) + 1, np.int64)
        np.cumsum(smsg, out=csum[1:])
        sec_len = csum[sbounds[1:]] - csum[sbounds[:-1]]

        cpu_v = np.asarray(cpu_idx, np.uint64)
        nano_v = np.asarray(nano_idx, np.uint64)
        l_cpu = varint_len(cpu_v)
        l_nano = varint_len(nano_v)
        pt_body = (1 + l_cpu + 1 + l_nano).astype(np.int64)
        l_ptb = varint_len(pt_body.astype(np.uint64))
        pt_len = 1 + l_ptb + pt_body
        pconst_b = bytearray()
        proto.put_tag_varint(pconst_b, P_PERIOD, period_ns)
        pconst = np.frombuffer(bytes(pconst_b), np.uint8)

        tail_len = sec_len + pt_len + len(pconst)
        tb = np.zeros(n_pids + 1, np.int64)
        np.cumsum(tail_len, out=tb[1:])
        out = np.empty(int(tb[-1]), np.uint8)

        pid_of_str = np.repeat(np.arange(n_pids), counts)
        sstart = tb[:-1][pid_of_str] + (csum[:-1] - csum[sbounds[:-1]][pid_of_str])
        out[sstart] = (P_STRING_TABLE << 3) | 2
        put_varints(out, sstart + 1, slen.astype(np.uint64), l_slen)
        joff = np.zeros(len(blobs) + 1, np.int64)
        np.cumsum(slen, out=joff[1:])
        ragged_gather(joined, joff[:-1], slen,
                      out=out, out_starts=sstart + 1 + l_slen)

        p = tb[:-1] + sec_len
        out[p] = (P_PERIOD_TYPE << 3) | 2
        put_varints(out, p + 1, pt_body.astype(np.uint64), l_ptb)
        p2 = p + 1 + l_ptb
        out[p2] = (VT_TYPE << 3)
        put_varints(out, p2 + 1, cpu_v, l_cpu)
        p3 = p2 + 1 + l_cpu
        out[p3] = (VT_UNIT << 3)
        put_varints(out, p3 + 1, nano_v, l_nano)
        pp = (p + pt_len)[:, None] + np.arange(len(pconst))[None, :]
        out[pp] = pconst[None, :]

        mv = out.data
        return [bytes(mv[int(tb[k]): int(tb[k + 1])])
                for k in range(n_pids)]

    def _build_head_tail_batch(self, items, period_ns: int) -> None:
        """Batch head/tail build: Python only interns the (few) mapping
        strings per pid; ALL mapping messages AND all tail sections across
        the batch encode in vectorized passes (the scalar path's
        per-message Writer varints dominated the 50k-pid first build).
        Items are (static, registry, n_mappings) with the mapping count
        frozen by the caller (encoder-thread safety).

        Cache-aware: items whose build inputs digest to a cached pair are
        served directly (a rotation or restart rebuilds thousands of pids
        whose content did not change; pids sharing a layout dedup to one
        build); only the residue pays the vectorized encode."""
        keyed = [(it, _ht_key(it[1], it[2], period_ns)) for it in items]
        items = []
        dups: dict[bytes, list] = {}  # within-batch identical layouts
        for it, key in keyed:
            if key in dups:
                dups[key].append(it)
                continue
            got = self._cache_get(key)
            if got is None:
                items.append((it, key))
                dups[key] = []
                continue
            st = it[0]
            st.head, st.tail = got
            st.n_mappings = it[2]
            st.period_ns = period_ns
            self._count_statics_bytes(reused=len(st.head) + len(st.tail))
        if not items:
            return
        keys = [key for _, key in items]
        items = [it for it, _ in items]
        mid: list[int] = []
        start: list[int] = []
        limit: list[int] = []
        off: list[int] = []
        fidx: list[int] = []
        bidx: list[int] = []
        bounds = [0]
        tables: list[list[str]] = []
        cpu_i: list[int] = []
        nano_i: list[int] = []
        for _st, reg, nm in items:
            strings = _Strings()
            strings("samples")
            strings("count")
            for m in reg.mappings[:nm]:
                mid.append(m.id)
                start.append(m.start)
                limit.append(m.end)
                off.append(m.offset)
                fidx.append(strings(m.path))
                bidx.append(strings(m.build_id))
            bounds.append(len(mid))
            cpu_i.append(strings("cpu"))
            nano_i.append(strings("nanoseconds"))
            tables.append(strings.table)
        tails = self._build_tails_batch(tables, cpu_i, nano_i, period_ns)
        if mid:
            buf, offs = _encode_mapping_stream(mid, start, limit, off,
                                               fidx, bidx)
            mv = buf.data
        # Mark pids clean only now, with head AND tail in hand: a raise
        # above (e.g. MemoryError in the stream encode) must leave every
        # staleness guard still tripping so a retry rebuilds fully.
        for k, (st, _reg, nm) in enumerate(items):
            if mid:
                a, b = int(offs[bounds[k]]), int(offs[bounds[k + 1]])
                st.head = _SAMPLE_TYPE_SEC + bytes(mv[a:b])
            else:
                st.head = _SAMPLE_TYPE_SEC
            st.tail = tails[k]
            st.period_ns = period_ns
            st.n_mappings = nm
            self._cache_put(keys[k], (st.head, st.tail),
                            len(st.head) + len(st.tail))
            self._count_statics_bytes(built=len(st.head) + len(st.tail))
            for st2, _reg2, nm2 in dups.get(keys[k], ()):
                # Same inputs elsewhere in this batch: share the blobs.
                st2.head, st2.tail = st.head, st.tail
                st2.period_ns = period_ns
                st2.n_mappings = nm2
                self._count_statics_bytes(reused=len(st.head)
                                          + len(st.tail))

    def _build_locs_batch(self, dirty) -> None:
        """One vectorized location pass over a batch of (static, registry,
        n_locs) triples whose cached location sections are behind.

        Full blobs (n_locs building from 0 — the rotation-rebuild and
        restart-adoption shape) are content-addressed: a cache hit skips
        the varint encode entirely and aliases the shared bytes; only
        misses and true deltas ride the batch encode below."""
        rest: list[tuple] = []  # (st, reg, n, full_blob_key_or_None)
        dups: dict[bytes, list] = {}  # within-batch identical blobs
        for st, reg, n in dirty:
            if st.n_locs == 0 and n > 0:
                key = _loc_key(reg, n)
                if key in dups:
                    dups[key].append((st, n))
                    continue
                got = self._cache_get(key)
                if got is not None:
                    st.loc_bytes = got
                    st.n_locs = n
                    self._count_statics_bytes(reused=len(got))
                    continue
                dups[key] = []
                rest.append((st, reg, n, key))
            else:
                rest.append((st, reg, n, None))
        if not rest:
            return
        lens = np.array([n - st.n_locs for st, reg, n, _ in rest], np.int64)
        total = int(lens.sum())
        bounds = np.zeros(len(rest) + 1, np.int64)
        np.cumsum(lens, out=bounds[1:])
        # Flat streams without 10k+ intermediate per-pid arrays: ids are
        # each pid's 1-based location numbering continued from its cache.
        first = np.array([st.n_locs + 1 for st, reg, n, _ in rest],
                         np.uint64)
        ids = np.repeat(first, lens) + (
            np.arange(total, dtype=np.uint64)
            - np.repeat(bounds[:-1], lens).astype(np.uint64))
        mids = np.concatenate(
            [reg.loc_mapping_id[st.n_locs:n] for st, reg, n, _ in rest]
        ).astype(np.uint64)
        addrs = np.concatenate(
            [reg.loc_normalized[st.n_locs:n] for st, reg, n, _ in rest])
        buf, offs = _encode_location_stream(ids, mids, addrs)
        mv = buf.data
        for k, (st, reg, n, key) in enumerate(rest):
            data = mv[int(offs[bounds[k]]): int(offs[bounds[k + 1]])]
            self._count_statics_bytes(built=len(data))
            if key is not None:
                st.loc_bytes = bytes(data)
                self._cache_put(key, st.loc_bytes, len(st.loc_bytes))
                for st2, n2 in dups.get(key, ()):
                    st2.loc_bytes = st.loc_bytes
                    st2.n_locs = n2
                    self._count_statics_bytes(reused=len(st.loc_bytes))
            else:
                _loc_extend(st, data)
            st.n_locs = n

    def build_statics(self, period_ns: int, budget_s: float | None = None,
                      chunk: int = 4096, loc_chunk: int = 1 << 18,
                      caps: dict | None = None, stop=None,
                      prepare_order: bool = False) -> int:
        """Pre-build known pids' static sections in vectorized location and
        mapping/tail passes (the per-pid _ensure_static path pays a
        vectorization fixed cost per pid — ruinous for the 50k-pid first
        window). Returns the number of pids now fully cached.

        budget_s bounds one call's wall time: dirty pids are processed in
        vectorized batches — at most `chunk` pids AND (for the location
        pass, whose cost tracks rows not pids) at most `loc_chunk` dirty
        locations per batch — and the call returns between batches once
        the budget is spent, leaving the rest dirty for the next call.
        This is the amortization hook — the streaming feeder drives it
        from its drain tick (directly, or through the encode pipeline's
        worker thread), so by window close the population discovered
        during the window is already warm and the close-time statics
        transient is bounded by roughly one batch past the budget, not by
        the whole window's pid population.

        caps restricts (and freezes) the build targets to a prepared
        window's pids: {pid: (registry, n_mappings, n_locs)}; without it
        every registry pid is targeted at its current published lengths.
        stop, a threading.Event, aborts between batches regardless of
        budget — the pipeline sets it to park the worker for a window
        hand-off."""
        import time as _time

        t0 = _time.perf_counter()
        if caps is None:
            # A prepared window (caps) was synced where it was prepared,
            # on the thread that owns the aggregator. Its encode runs on
            # the worker while that thread may be compacting the id
            # space for the next window: adopting the new epoch from
            # here would lay this window's old ids out against the new
            # mirrors.
            self._sync()
        agg = self._agg
        version = (getattr(agg, "_reg_version", None), period_ns)
        if version[0] is not None and self._statics_clean == version:
            # Nothing can be dirty: no registry mutated since a scan
            # that found everything clean at this period. Skips the
            # O(pids) staleness walk this method otherwise pays on
            # every drain-tick prebuild and every encode.
            if prepare_order:
                self._ensure_order()
            return len(agg._pids) if caps is None else len(caps)
        if prepare_order:
            # Pipeline prebuilds run on the WORKER thread: rebuilding the
            # stale pid sort order here moves the O(n log n) argsort over
            # the full id space off the window-close hand-off (prepare()
            # then finds it warm unless ids arrived after the last drain
            # tick). Inline callers keep the lazy default — on the
            # polling thread that argsort per drain would be pure loss.
            self._ensure_order()
        if caps is not None:
            targets = [(pid, cap) for pid, cap in caps.items()]
        else:
            # list(...) snapshots atomically under the GIL; a pid inserted
            # by a concurrent feed is simply next call's work.
            targets = [(pid, _reg_cap(reg))
                       for pid, reg in list(agg._pids.items())]
        dirty: list[tuple[_PidStatic, object, int]] = []
        dirty_ht: list[tuple[_PidStatic, object, int]] = []
        for pid, (reg, nm, nl) in targets:
            st = self._static.get(pid)
            if st is None:
                st = self._static[pid] = _PidStatic()
            st.reg = reg
            if st.n_mappings < nm or st.period_ns != period_ns:
                dirty_ht.append((st, reg, max(nm, st.n_mappings)))
            if st.n_locs < nl:
                dirty.append((st, reg, nl))
        left: set[int] = set()  # ids of statics still dirty in any pass
        did_work = False        # every call makes >=1 chunk of progress

        def _spent() -> bool:
            if stop is not None and stop.is_set():
                return True
            return (did_work and budget_s is not None
                    and _time.perf_counter() - t0 > budget_s)

        for k in range(0, len(dirty_ht), chunk):
            if _spent():
                left.update(id(st) for st, _, _ in dirty_ht[k:])
                break
            self._build_head_tail_batch(dirty_ht[k: k + chunk], period_ns)
            did_work = True
        k = 0
        while k < len(dirty):
            if _spent():
                left.update(id(st) for st, _, _ in dirty[k:])
                break
            # Batch bounded by dirty-LOCATION count, not pid count: one
            # pid can carry a deep backlog, and the budget is only
            # honest if a batch's work is bounded.
            end, locs = k, 0
            while end < len(dirty) and end - k < chunk and locs < loc_chunk:
                st, reg, n = dirty[end]
                locs += n - st.n_locs
                end += 1
            self._build_locs_batch(dirty[k: end])
            did_work = True
            k = end
        if caps is None and not left and version[0] is not None:
            # Full-target scan came back (or was built) clean: the next
            # call at this (version, period) can skip the walk. The
            # version was read BEFORE the scan, so a concurrent insert
            # landing mid-walk re-arms the scan on the next call.
            self._statics_clean = version
        if did_work:
            dt = _time.perf_counter() - t0
            self.stats["last_statics_build_s"] = dt
            self.stats["statics_build_s_total"] += dt
            window_trace.observe("statics", dt)
        return len(targets) - len(left)

    def statics_backlog(self, period_ns: int) -> int:
        """Number of pids whose static sections are still stale (what the
        next build_statics call would work on) — the amortization driver's
        progress gauge. Call only from a thread that owns the encoder
        (same contract as prepare)."""
        self._sync()
        if self._statics_clean == (getattr(self._agg, "_reg_version",
                                           None), period_ns):
            return 0
        n = 0
        for _pid, reg in list(self._agg._pids.items()):
            st = self._static.get(_pid)
            _reg, nm, nl = _reg_cap(reg)
            if st is None or st.n_mappings < nm \
                    or st.period_ns != period_ns or st.n_locs < nl:
                n += 1
        return n

    def adopt_statics(self, pid: int, head: bytes, tail: bytes,
                      loc_bytes: bytes, n_mappings: int, n_locs: int,
                      period_ns: int) -> None:
        """Install snapshot-restored static sections for one pid (the
        statics store's warm-restart path, pprof/statics_store.py). The
        caller has already validated the blobs against the pid's adopted
        registry content and installed that registry in the aggregator.
        Must run before any encode/prebuild touches the pid — i.e. at
        startup, on the thread that owns the encoder.

        The head/tail pair is also interned into the content cache under
        its input digest (cheap: a handful of mapping rows). Location
        blobs are NOT digested here — adoption is on the startup path
        and already pays one content digest per record for validation;
        the rotation-time rescue in _sync interns them lazily, exactly
        when a rebuild could want them."""
        self._sync()  # pin the rotation epoch so the next sync keeps these
        st = self._static.get(pid)
        if st is None:
            st = self._static[pid] = _PidStatic()
        st.head = head
        st.tail = tail
        st.loc_bytes = loc_bytes
        st.n_mappings = n_mappings
        st.n_locs = n_locs
        st.period_ns = period_ns
        self.stats["statics_adopted_pids"] += 1
        reg = self._agg._pids.get(pid)
        st.reg = reg
        if reg is None:
            return
        self._cache_put(_ht_key(reg, n_mappings, period_ns), (head, tail),
                        len(head) + len(tail))

    # -- encode --------------------------------------------------------------

    def _held_pieces(self, period_ns: int) -> dict:
        """{pid: (static, head length, tail length, span length, piece)}
        of the standing template's groups whose static span has its
        compressed piece held, for the layout that replaces it."""
        tmpl = self._tmpl
        if tmpl.pieces is None or not tmpl.pieces.nbytes \
                or tmpl.period_ns != period_ns:
            return {}
        held = {}
        for pid, st, hl, tl, sl, rev, slot in zip(
                tmpl.pids.tolist(), tmpl.g_static,
                tmpl.g_head_len.tolist(), tmpl.g_tail_len.tolist(),
                tmpl.span_len.tolist(), tmpl.span_rev.tolist(),
                tmpl.pieces.slots):
            if slot is not None and slot[0] == rev:
                held[pid] = (st, hl, tl, sl, slot[1])
        return held

    def _build_layout(self, idx: np.ndarray, pids_live: np.ndarray,
                      period_ns: int, caps: dict | None = None) -> None:
        """Serialize the full window layout (everything except the count and
        time values, which are patched after) and record patch positions.
        Each pid's region is over-allocated with slack so later windows can
        APPEND new stacks' rows instead of relaying out (see _Template)."""
        tmpl = self._tmpl
        tmpl.kept = None
        held = self._held_pieces(period_ns)
        bounds = np.flatnonzero(np.diff(pids_live)) + 1
        gstarts = np.concatenate(([0], bounds))
        gends = np.concatenate((bounds, [len(idx)]))
        pids = pids_live[gstarts].astype(np.int32)
        # Batch-build whatever is still dirty before the per-pid walk: the
        # per-pid _ensure_static path pays a vectorization fixed cost per
        # pid, ruinous for a cold 50k-pid first window (the production
        # profiler lands here without ever calling build_statics itself).
        # After this, _ensure_static is a pure cache hit per pid.
        with window_trace.child("encode_statics"):
            self.build_statics(period_ns, caps=caps)
        statics = [self._ensure_static(int(p), period_ns,
                                       cap=None if caps is None
                                       else caps.get(int(p)))
                   for p in pids.tolist()]

        pre_lens = self._pre_off[idx + 1] - self._pre_off[idx]
        body_len = pre_lens + 2 + self._VAL_W
        l_body = varint_len(body_len.astype(np.uint64))
        samp_lens = 1 + l_body + body_len
        stream_off = np.zeros(len(idx) + 1, np.int64)
        np.cumsum(samp_lens, out=stream_off[1:])

        static_lens = np.array(
            [len(s.head) + len(s.loc_bytes) + len(s.tail) for s in statics],
            np.int64)
        gsizes = gends - gstarts
        samples_per_g = stream_off[gends] - stream_off[gstarts]
        blob_lens = samples_per_g + static_lens + _WTAIL_LEN
        # Append slack per pid (~12.5%, min 64 B): garbage bytes BETWEEN
        # blob slices cost nothing on the wire.
        caps = blob_lens + np.maximum(blob_lens >> 3, 64)
        cap_bounds = np.zeros(len(pids) + 1, np.int64)
        np.cumsum(caps, out=cap_bounds[1:])

        total = int(cap_bounds[-1])
        buf = tmpl.buf
        if buf is None or len(buf) < total:
            buf = np.empty(int(total * 1.05) + 64, np.uint8)
        blob_start = cap_bounds[:-1]
        # Each group's sample run starts at its blob start: shift the
        # packed stream offsets group-wise.
        shift = blob_start - stream_off[gstarts]
        p = stream_off[:-1] + np.repeat(shift, gsizes)
        buf[p] = _TAG_SAMPLE
        put_varints(buf, p + 1, body_len.astype(np.uint64), l_body)
        ragged_gather(self._pre_flat, self._pre_off[idx], pre_lens,
                      out=buf, out_starts=p + 1 + l_body)
        vp = p + 1 + l_body + pre_lens
        buf[vp] = _TAG_S_VALUE
        buf[vp + 1] = self._VAL_W

        time_pos = blob_start + samples_per_g + static_lens
        # Statics splice: one C-speed join into a flat buffer, then one
        # ragged scatter (native: a memcpy per pid) — the old path paid
        # 3 numpy slice copies per pid, tens of thousands of Python
        # iterations on the exact window the cold-start cliff hits.
        joined = np.frombuffer(
            b"".join(part for s in statics
                     for part in (s.head, s.loc_bytes, s.tail)), np.uint8)
        src_off = np.zeros(len(statics) + 1, np.int64)
        np.cumsum(static_lens, out=src_off[1:])
        if len(joined):
            ragged_gather(joined, src_off[:-1], static_lens, out=buf,
                          out_starts=blob_start + samples_per_g)
        buf[time_pos] = (P_TIME_NANOS << 3)
        buf[time_pos + 1 + self._TIME_W] = (P_DURATION_NANOS << 3)

        tmpl.buf = buf
        tmpl.n_rows = len(idx)
        row_of = np.full(max(self._synced, 1), -1, np.int64)
        row_of[idx] = np.arange(len(idx), dtype=np.int64)
        tmpl.row_of = row_of
        tmpl.row_id = idx.astype(np.int64, copy=True)
        tmpl.row_group = np.repeat(
            np.arange(len(pids), dtype=np.int32), gsizes)
        tmpl.val_pos = vp + 2
        tmpl.pids = pids
        tmpl.blob_start = blob_start.copy()
        tmpl.blob_end = blob_start + blob_lens
        tmpl.cap_end = cap_bounds[1:].copy()
        tmpl.time_pos = time_pos
        tmpl.group_of = {int(pid): g for g, pid in enumerate(pids.tolist())}
        tmpl.g_head_len = np.array([len(s.head) for s in statics], np.int64)
        tmpl.g_tail_len = np.array([len(s.tail) for s in statics], np.int64)
        tmpl.g_loc_len = np.array(
            [len(s.loc_bytes) for s in statics], np.int64)
        tmpl.g_static = statics
        tmpl.span_off = samples_per_g
        tmpl.span_len = static_lens
        self._span_rev += 1
        tmpl.span_rev = np.full(len(pids), self._span_rev, np.int64)
        tmpl.pieces = pieces = _PieceCache(len(pids))
        if held:
            # A span laid down again from the static it was laid from,
            # each of its three sections as long as it was, holds the
            # bytes it held (a pid's sections only ever grow by
            # appending): its compressed piece stands.
            for g, (pid, st, span) in enumerate(zip(
                    pids.tolist(), statics, static_lens.tolist())):
                got = held.get(pid)
                if got is not None and got[0] is st \
                        and got[1:4] == (len(st.head), len(st.tail), span):
                    pieces.slots[g] = (self._span_rev, got[4])
                    pieces.nbytes += len(got[4])
        tmpl.alloc_end = total
        tmpl.waste = 0
        tmpl.rotations = self._rotations

    # -- incremental append (the churn path) ---------------------------------

    def _ensure_buf(self, extra: int) -> None:
        """Grow the template buffer so `extra` bytes fit at alloc_end."""
        tmpl = self._tmpl
        need = tmpl.alloc_end + extra
        if need > len(tmpl.buf):
            grown = np.empty(int(need * 1.3) + 64, np.uint8)
            grown[: tmpl.alloc_end] = tmpl.buf[: tmpl.alloc_end]
            tmpl.buf = grown

    def _serialize_rows(self, ids: np.ndarray
                        ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Sample-row bytes for `ids`, packed back to back: returns
        (stream, row starts, value-varint positions), all stream-relative.
        Value bytes are left zeroed — encode() patches every row's count
        after any appends, so they never reach a parser unpatched."""
        pre_lens = self._pre_off[ids + 1] - self._pre_off[ids]
        body_len = pre_lens + 2 + self._VAL_W
        l_body = varint_len(body_len.astype(np.uint64))
        samp_lens = 1 + l_body + body_len
        s_off = np.zeros(len(ids) + 1, np.int64)
        np.cumsum(samp_lens, out=s_off[1:])
        stream = np.zeros(int(s_off[-1]), np.uint8)
        p = s_off[:-1]
        stream[p] = _TAG_SAMPLE
        put_varints(stream, p + 1, body_len.astype(np.uint64), l_body)
        ragged_gather(self._pre_flat, self._pre_off[ids], pre_lens,
                      out=stream, out_starts=p + 1 + l_body)
        vp = p + 1 + l_body + pre_lens
        stream[vp] = _TAG_S_VALUE
        stream[vp + 1] = self._VAL_W
        return stream, s_off, vp + 2

    def _append_rows(self, new_ids: np.ndarray, new_pids: np.ndarray,
                     period_ns: int, caps: dict | None = None) -> None:
        """Add sample rows for stacks the template has never seen, without
        touching any other pid's bytes: rows (and the location registry's
        append-only delta) go into the owning pid's slack; a pid without
        room — or whose head/tail statics changed — relocates its blob to
        the buffer's end (blob order is meaningless); a brand-new pid gets
        a fresh blob. encode() patches every count afterwards.

        The dominant churn shape — existing pid, statics unchanged, rows
        fit in slack — is handled for ALL such groups in one vectorized
        scatter (the per-group loop at 10k churning pids was most of the
        churn-encode penalty); only exceptional groups (statics drift,
        slack exhaustion, brand-new pids) take the scalar walk."""
        tmpl = self._tmpl
        tmpl.kept = None
        # Batch-build dirty statics first (new stacks usually mean new
        # locations for their pids); the per-pid _ensure_static below is
        # then a cache hit — the same reasoning as _build_layout's. Only
        # the APPENDING pids are targeted: freshening every registry pid
        # here cost an O(all pids) staleness walk per churn window.
        pids_u = [int(p) for p in np.unique(new_pids).tolist()]
        if caps is None:
            sub = {p: _reg_cap(self._agg._pids[p]) for p in pids_u
                   if p in self._agg._pids}
        else:
            sub = {p: caps[p] for p in pids_u if p in caps}
        with window_trace.child("encode_statics"):
            self.build_statics(period_ns, caps=sub)
        stream, s_off, vp_rel = self._serialize_rows(new_ids)
        bounds = np.flatnonzero(np.diff(new_pids)) + 1
        gstarts = np.concatenate(([0], bounds))
        gends = np.concatenate((bounds, [len(new_ids)]))
        n0 = tmpl.n_rows
        add_val_pos = np.empty(len(new_ids), np.int64)
        add_group = np.empty(len(new_ids), np.int32)
        n_g = len(gstarts)
        statics = [self._ensure_static(int(new_pids[gs]), period_ns,
                                       cap=None if caps is None
                                       else caps.get(int(new_pids[gs])))
                   for gs in gstarts.tolist()]
        g_idx = np.full(n_g, -1, np.int64)
        fast = np.zeros(n_g, bool)
        for k in range(n_g):
            st = statics[k]
            g = tmpl.group_of.get(int(new_pids[gstarts[k]]))
            if g is None:
                continue
            g_idx[k] = g
            fast[k] = (len(st.head) == int(tmpl.g_head_len[g])
                       and len(st.tail) == int(tmpl.g_tail_len[g])
                       and len(st.loc_bytes) == int(tmpl.g_loc_len[g]))
        need = s_off[gends] - s_off[gstarts]
        kf = np.flatnonzero(fast)
        if len(kf):
            gf = g_idx[kf]
            room = (tmpl.cap_end[gf] - tmpl.blob_end[gf]) >= need[kf]
            fast[kf[~room]] = False
            kf, gf = kf[room], gf[room]
        if len(kf):
            dest = tmpl.blob_end[gf].copy()
            ragged_gather(stream, s_off[gstarts[kf]], need[kf],
                          out=tmpl.buf, out_starts=dest)
            tmpl.blob_end[gf] = dest + need[kf]
            sizes = (gends - gstarts)[kf]
            tot = int(sizes.sum())
            off = np.zeros(len(kf) + 1, np.int64)
            np.cumsum(sizes, out=off[1:])
            rows_flat = np.repeat(gstarts[kf], sizes) + (
                np.arange(tot, dtype=np.int64) - np.repeat(off[:-1], sizes))
            shift = dest - s_off[gstarts[kf]]
            add_val_pos[rows_flat] = vp_rel[rows_flat] + np.repeat(shift,
                                                                  sizes)
            add_group[rows_flat] = np.repeat(gf, sizes).astype(np.int32)
        self.stats["append_fast_groups"] += len(kf)
        self.stats["append_slow_groups"] += n_g - len(kf)
        pend: list[tuple] = []  # deferred new-group records (pid, blob
        #                         geometry) — one concatenate per array
        #                         after the loop, not one np.append each
        for k in np.flatnonzero(~fast).tolist():
            gs, ge = int(gstarts[k]), int(gends[k])
            pid = int(new_pids[gs])
            st = statics[k]
            g = tmpl.group_of.get(pid)
            lo, hi = int(s_off[gs]), int(s_off[ge])
            if g is not None \
                    and len(st.head) == int(tmpl.g_head_len[g]) \
                    and len(st.tail) == int(tmpl.g_tail_len[g]):
                loc_delta = len(st.loc_bytes) - int(tmpl.g_loc_len[g])
                need_g = (hi - lo) + loc_delta
                if tmpl.cap_end[g] - tmpl.blob_end[g] < need_g:
                    self._relocate_blob(g, need_g)
                dest = int(tmpl.blob_end[g])
                buf = tmpl.buf
                buf[dest: dest + (hi - lo)] = stream[lo:hi]
                if loc_delta:
                    buf[dest + (hi - lo): dest + need_g] = np.frombuffer(
                        st.loc_bytes, np.uint8,
                        loc_delta, int(tmpl.g_loc_len[g]))
                    tmpl.g_loc_len[g] += loc_delta
                tmpl.blob_end[g] += need_g
                add_val_pos[gs:ge] = dest + (vp_rel[gs:ge] - lo)
            else:
                # Head/tail changed (mapping growth, comm change) or a
                # brand-new pid: (re)write the whole blob at the end.
                if g is not None:
                    rows_g = np.flatnonzero(
                        tmpl.row_group[:n0] == g).astype(np.int64)
                    ids_all = np.concatenate(
                        (tmpl.row_id[rows_g], new_ids[gs:ge]))
                else:
                    rows_g = np.empty(0, np.int64)
                    ids_all = new_ids[gs:ge].astype(np.int64)
                g, vp_abs = self._write_pid_blob(
                    g, pid, ids_all, rows_g, st,
                    pend=pend, next_g=len(tmpl.pids) + len(pend))
                # _write_pid_blob set val_pos for the existing rows; the
                # new rows' positions follow directly after them.
                add_val_pos[gs:ge] = vp_abs[len(rows_g):]
            add_group[gs:ge] = g
        if pend:
            # Register the deferred new groups: one concatenate per array
            # for the whole window, not one np.append per new pid.
            cols = list(zip(*pend))
            tmpl.pids = np.concatenate(
                (tmpl.pids, np.array(cols[0], np.int32)))
            for slot, col in zip(("blob_start", "blob_end", "cap_end",
                                  "time_pos", "g_head_len", "g_tail_len",
                                  "g_loc_len", "span_off", "span_len",
                                  "span_rev"), cols[1:]):
                setattr(tmpl, slot, np.concatenate(
                    (getattr(tmpl, slot), np.array(col, np.int64))))
            tmpl.g_static.extend(cols[11])
            tmpl.pieces.slots.extend([None] * len(pend))
        # Register the new rows (one concatenate per array per window).
        tmpl.row_id = np.concatenate((tmpl.row_id[:n0], new_ids))
        tmpl.row_group = np.concatenate((tmpl.row_group[:n0], add_group))
        tmpl.val_pos = np.concatenate((tmpl.val_pos[:n0], add_val_pos))
        tmpl.row_of[new_ids] = np.arange(n0, n0 + len(new_ids),
                                         dtype=np.int64)
        tmpl.n_rows = n0 + len(new_ids)

    def _relocate_blob(self, g: int, extra: int) -> None:
        """Move group g's blob to the end of the buffer with fresh slack
        sized for `extra` more bytes; the old region becomes waste. The
        static span moves with the blob, byte for byte: its offset inside
        the blob and its revision stand, and so does its cached piece."""
        tmpl = self._tmpl
        start, end = int(tmpl.blob_start[g]), int(tmpl.blob_end[g])
        blob_len = end - start
        cap = blob_len + extra + max((blob_len + extra) >> 3, 64)
        self._ensure_buf(cap)
        new_start = tmpl.alloc_end
        buf = tmpl.buf
        buf[new_start: new_start + blob_len] = buf[start:end]
        delta = new_start - start
        rows_g = tmpl.row_group[: tmpl.n_rows] == g
        tmpl.val_pos[: tmpl.n_rows][rows_g] += delta
        tmpl.time_pos[g] += delta
        tmpl.waste += int(tmpl.cap_end[g]) - start
        tmpl.blob_start[g] = new_start
        tmpl.blob_end[g] = new_start + blob_len
        tmpl.cap_end[g] = new_start + cap
        tmpl.alloc_end = new_start + cap

    def _write_pid_blob(self, g: int | None, pid: int, ids_all: np.ndarray,
                        rows_g: np.ndarray, st, pend: list | None = None,
                        next_g: int = -1) -> tuple[int, np.ndarray]:
        """Serialize pid's complete blob (samples + statics + time fields)
        at the buffer's end. Rewrites val_pos for the pid's existing rows
        (`rows_g`, in row order = the first len(rows_g) entries of
        `ids_all`); returns (group index, absolute value positions for
        every row of `ids_all`). A brand-new pid (g is None) is assigned
        `next_g` and its group arrays are DEFERRED onto `pend` — the
        caller registers all of a window's new groups in one concatenate
        per array."""
        tmpl = self._tmpl
        stream, s_off, vp_rel = self._serialize_rows(ids_all)
        static_len = len(st.head) + len(st.loc_bytes) + len(st.tail)
        blob_len = int(s_off[-1]) + static_len + _WTAIL_LEN
        cap = blob_len + max(blob_len >> 3, 64)
        self._ensure_buf(cap)
        base = tmpl.alloc_end
        buf = tmpl.buf
        buf[base: base + int(s_off[-1])] = stream
        a = base + int(s_off[-1])
        for part in (st.head, st.loc_bytes, st.tail):
            lp = len(part)
            if lp:
                buf[a: a + lp] = np.frombuffer(part, np.uint8)
                a += lp
        tpos = a
        buf[tpos] = (P_TIME_NANOS << 3)
        buf[tpos + 1 + self._TIME_W] = (P_DURATION_NANOS << 3)
        self._span_rev += 1  # the span's bytes were just (re)written
        if g is None:
            g = next_g
            pend.append((pid, base, base + blob_len, base + cap, tpos,
                         len(st.head), len(st.tail), len(st.loc_bytes),
                         int(s_off[-1]), static_len, self._span_rev, st))
            tmpl.group_of[pid] = g
        else:
            tmpl.waste += int(tmpl.cap_end[g]) - int(tmpl.blob_start[g])
            tmpl.blob_start[g] = base
            tmpl.blob_end[g] = base + blob_len
            tmpl.cap_end[g] = base + cap
            tmpl.time_pos[g] = tpos
            tmpl.g_head_len[g] = len(st.head)
            tmpl.g_tail_len[g] = len(st.tail)
            tmpl.g_loc_len[g] = len(st.loc_bytes)
            tmpl.g_static[g] = st
            tmpl.span_off[g] = int(s_off[-1])
            tmpl.span_len[g] = static_len
            tmpl.span_rev[g] = self._span_rev
            if len(rows_g):
                tmpl.val_pos[rows_g] = base + vp_rel[: len(rows_g)]
        tmpl.alloc_end = base + cap
        return g, base + vp_rel

    def prepare(self, counts: np.ndarray, time_ns: int, duration_ns: int,
                period_ns: int) -> _PreparedWindow:
        """Freeze one closed window for encoding: sync the id mirrors,
        filter to the live ids (copying them out of the aggregator's
        one-close counts buffer), and capture per-pid registry caps. Must
        run on the thread that owns aggregator mutation (the profiler
        thread) — this is the pipelined hand-off's entire critical
        section, and the only encoder-state write the profiler thread
        performs once a pipeline owns the encoder."""
        import time as _time

        t0 = _time.perf_counter()
        self._sync()
        self._ensure_order()
        n = len(counts)
        if n > self._synced:
            raise ValueError("counts longer than the synced id space")
        if n == self._synced:
            order, order_pid = self._order, self._order_pid
        else:
            # Ids are dense 0..next_id; a shorter counts buffer (an older
            # window) restricts to the ids it covers, keeping pid order.
            keep = self._order < n
            order, order_pid = self._order[keep], self._order_pid[keep]
        counts_o = np.asarray(counts)[order]
        live = counts_o > 0
        if live.all():
            # Every covered id is live: the window's ids are the order
            # itself (the arrays are never written in place), and the
            # encode knows a window of the same ids by that identity.
            idx, pids_live = order, order_pid
            vals = counts_o.astype(np.uint64)
        else:
            idx = order[live]
            vals = counts_o[live].astype(np.uint64)
            pids_live = order_pid[live]
        caps = self._window_caps(pids_live, carry=n == self._synced)
        self.timings["encode_sync"] = _time.perf_counter() - t0
        return _PreparedWindow(idx, vals, pids_live, time_ns, duration_ns,
                               period_ns, self._rotations, caps)

    def _window_caps(self, pids_live: np.ndarray, carry: bool) -> dict:
        """{pid: _reg_cap(registry)} for every live pid of the window,
        read from the registries only where the window before cannot
        vouch for it: the pids the aggregator registered stacks or
        mappings for since the last prepare (take_touched_pids) and the
        pids that were not live then; pids no longer live are dropped.
        A window that changes nothing gets the dictionary of the one
        before; any other a new one (a prepared window's caps are the
        worker's to read). Without something to go on — no window
        before, an aggregator that reports no touched pids, a window not
        carried (`carry`: its counts stop short of the id space) — every
        live pid's registry is read, as it always was."""
        agg = self._agg
        take = getattr(agg, "take_touched_pids", None)
        touched = None
        if take is not None:
            self._touch_token, touched = take(self._touch_token)
        old, old_pids = self._caps, self._caps_pids
        if old is not None and pids_live is self._caps_src:
            upids, same = old_pids, True
        else:
            upids = _distinct_sorted(pids_live)
            same = old is not None and np.array_equal(upids, old_pids)
        if old is None or touched is None or not carry:
            caps: dict[int, tuple] = {}
            refresh = upids
            self.stats["caps_rebuilds_total"] += 1
            window_trace.count(caps_rebuilds=1)
        else:
            refresh = np.intersect1d(
                upids, np.fromiter(touched, upids.dtype, len(touched)),
                assume_unique=True) if touched else upids[:0]
            if same and not len(refresh):
                self._caps_src = pids_live
                window_trace.count(caps_refreshed=0)
                return old
            caps = dict(old)
            if not same:
                for pid in np.setdiff1d(old_pids, upids,
                                        assume_unique=True).tolist():
                    caps.pop(pid, None)
                refresh = np.union1d(refresh, np.setdiff1d(
                    upids, old_pids, assume_unique=True))
        for pid in refresh.tolist():
            reg = agg._pids.get(pid)
            if reg is not None:
                caps[pid] = _reg_cap(reg)
            else:
                caps.pop(pid, None)
        self.stats["caps_refreshed_total"] += len(refresh)
        window_trace.count(caps_refreshed=len(refresh))
        self._caps = caps if carry else None
        self._caps_src, self._caps_pids = pids_live, upids
        return caps

    def encode(self, counts: np.ndarray, time_ns: int, duration_ns: int,
               period_ns: int, views: bool = False) -> list[tuple[int, bytes]]:
        """Serialize one closed window: per-stack-id counts (as returned by
        close_window/window_counts) -> [(pid, profile.proto bytes)].

        views=True returns zero-copy memoryviews into the template buffer
        (a _SpanViews list: it also knows where each blob's static span
        lies, for the ship path's gzip) — valid only until the next
        encode() call; for callers (bench, batch writer) that consume
        within the window.
        """
        prep = self.prepare(counts, time_ns, duration_ns, period_ns)
        if self.track_prep:
            # Stashed for the inline sink fan-out (profiler/cpu.py):
            # after a successful inline encode the secondary sinks
            # consume the same prepared rows the pprof bytes came from.
            # One window deep by construction — the next encode
            # replaces it.
            self.last_prep = prep
        return self.encode_prepared(prep, views=views)

    def encode_prepared(self, prep: _PreparedWindow,
                        views: bool = False) -> list[tuple[int, bytes]]:
        """Serialize a prepared window. Runs on the encoder thread under
        the pipeline; reads aggregator registries only through the caps
        frozen at prepare time."""
        import time as _time

        idx, vals, pids_live = prep.idx, prep.vals, prep.pids_live
        time_ns, duration_ns = prep.time_ns, prep.duration_ns
        period_ns, caps = prep.period_ns, prep.caps
        if not len(idx):
            return []
        if prep.rotations != self._rotations:
            # A registry rotation slid in between prepare and encode; the
            # prepared ids no longer name these mirrors. The pipeline's
            # sequencing makes this unreachable — fail loudly if not.
            raise ValueError("prepared window from a different registry "
                             "epoch")
        if int(vals.max()) >= 1 << (7 * self._VAL_W):
            raise ValueError("window count exceeds the fixed varint width")

        tmpl = self._tmpl
        t0 = _time.perf_counter()
        hit = (tmpl.buf is not None
               and tmpl.period_ns == period_ns
               and tmpl.rotations == self._rotations)
        kept = tmpl.kept
        same_ids = hit and kept is not None and kept.idx is idx
        if same_ids:
            # The ids of the window before (an all-live window's idx is
            # the order array itself) on the layout it left: their rows
            # are what they were, none is new, and the relayout test
            # needs no pass over them.
            row, n_new = kept.row, 0
            hit = (tmpl.n_rows - len(idx) <= tmpl.n_rows // 2
                   and tmpl.waste <= tmpl.alloc_end // 3)
        elif hit:
            # Churn analysis against the template's row set. row_of may
            # lag the id space (population grew since the build).
            row = tmpl.row_of[idx] if int(idx.max()) < len(tmpl.row_of) \
                else None
            if row is None:
                known = np.zeros(len(idx), bool)
                known_ok = tmpl.row_of[idx[idx < len(tmpl.row_of)]]
                n_new = len(idx) - int((known_ok >= 0).sum())
            else:
                known = row >= 0
                n_new = len(idx) - int(known.sum())
            dead = tmpl.n_rows - (len(idx) - n_new)
            # Rebuild when the patch path stops paying: mostly-dead
            # template (wire bloat from zero rows), append volume near a
            # relayout's, or relocation holes dominating the buffer.
            hit = (dead <= tmpl.n_rows // 2
                   and n_new <= max(tmpl.n_rows // 2, 1024)
                   and tmpl.waste <= tmpl.alloc_end // 3)
        if not hit:
            self._build_layout(idx, pids_live, period_ns, caps=caps)
            self.stats["layouts_built"] += 1
            tmpl.period_ns = period_ns
            row = tmpl.row_of[idx]
        elif not same_ids:
            if row is None or (n_new and len(tmpl.row_of) < self._synced):
                grown = np.full(max(self._synced, 1), -1, np.int64)
                grown[: len(tmpl.row_of)] = tmpl.row_of
                tmpl.row_of = grown
                row = tmpl.row_of[idx]
                known = row >= 0
            if n_new:
                self._append_rows(idx[~known], pids_live[~known], period_ns,
                                  caps=caps)
                row = tmpl.row_of[idx]
        buf = tmpl.buf
        # Patch the per-window values (on a template hit this IS the
        # encode). Template rows with no samples this window are patched
        # to zero — semantically the same profile, no relayout.
        vals_full = np.zeros(tmpl.n_rows, np.uint64)
        vals_full[row] = vals
        put_varints_padded(buf, tmpl.val_pos, vals_full, self._VAL_W)
        # Dead-row accounting: rows patched to count 0 are wire bytes the
        # reference never ships (docs/parity.md) — keep the bloat visible.
        dead = int(tmpl.n_rows - len(row))
        self.stats["windows_encoded"] += 1
        self.stats["template_rows"] = int(tmpl.n_rows)
        self.stats["dead_rows"] = dead
        self.stats["dead_row_fraction"] = (
            dead / tmpl.n_rows if tmpl.n_rows else 0.0)
        kept = tmpl.kept  # dropped by a layout or an append above
        if kept is None:
            kept = tmpl.kept = _Kept(tmpl, self._TIME_W)
        buf[kept.time_idx] = _padded_bytes(time_ns, self._TIME_W)[None, :]
        buf[kept.dur_idx] = _padded_bytes(duration_ns,
                                          self._TIME_W)[None, :]
        self.timings["encode_patch" if hit else "encode_build"] = \
            _time.perf_counter() - t0

        t0 = _time.perf_counter()
        bs, be = tmpl.blob_start, tmpl.blob_end
        # A pid whose every template row is dead this window would emit an
        # all-zero profile — the reference never writes a sample-less
        # profile, so skip those groups (their blobs stay for the next
        # window they wake up in).
        if kept.idx is idx:
            live_g = kept.live_g
        else:
            live_g = np.zeros(len(tmpl.pids), bool)
            live_g[tmpl.row_group[row]] = True
            if kept.live_g is None or not np.array_equal(live_g,
                                                         kept.live_g):
                kept.views = None  # other blobs go out than were kept
            kept.idx, kept.row, kept.live_g = idx, row, live_g
        out: list[tuple[int, bytes]] = []
        if self._compress:
            mv = buf.data
            for g, pid in enumerate(tmpl.pids.tolist()):
                if live_g[g]:
                    out.append((pid, _gzip.compress(
                        bytes(mv[int(bs[g]): int(be[g])]), 1)))
        elif views and kept.views is not None:
            # The layout and the live groups of the window before: its
            # views are this window's, over the bytes just patched (the
            # list was only ever valid until the next encode).
            out = kept.views
            self.stats["views_reused_total"] += 1
        elif views:
            # The views go out with the window's span table, for the
            # ship path's gzip (_SpanViews.span_blobs).
            mv = buf.data
            live = np.flatnonzero(live_g)
            out = kept.views = _SpanViews(
                [(pid, mv[a:b]) for pid, a, b in zip(
                    tmpl.pids[live].tolist(), bs[live].tolist(),
                    be[live].tolist())],
                _SpanTable(tmpl), live.tolist())
        else:
            for g, pid in enumerate(tmpl.pids.tolist()):
                if live_g[g]:
                    out.append((pid, buf[int(bs[g]): int(be[g])].tobytes()))
        self.timings["encode_emit"] = _time.perf_counter() - t0
        return out
