"""On-device cascaded compression: RLE + zigzag-delta + FoR bitpack.

TPU-native rebuild of the reference's nvcomp-cascaded wire compression
(/root/reference/src/compression.{hpp,cpp}): each shuffle partition is
compressed before the collective and decompressed after, with a
sampling selector choosing the cascade per column and the same
per-column recursive options tree (string columns carry child options
for the size and char sub-buffers; policy compresses fixed-width data
and string sizes, never chars — compression.cpp:44-60).

TPU-first twist (SURVEY.md §7): XLA collectives need static shapes, so
"compressed" buckets have a static capacity = wire_factor x the raw
bucket bytes, chosen by the selector from the sampled ratio with slack.
The collective then moves wire_factor of the raw bytes — that static
shrink is the bandwidth win, the analogue of the reference's dynamic
compressed sizes riding its tag-addressed transports. A block whose
compressed stream exceeds its static capacity raises the overflow flag
(never silent corruption).

Codec layout per block (uint64 words):
  [0] valid value/run count r     [1] bits_v | bits_l<<8
  [2] FoR base of values          [3] delta base (pre-delta first value)
  [4] FoR base of run lengths     [5] packed value words nw_v
  [6] packed length words nw_l    [7] block element count (sanity)
  [8 ... 8+nw_v) packed values    [8+nw_v ... 8+nw_v+nw_l) packed lengths
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.search import count_leq_arange
from ..core.table import Column, StringColumn, Table
from ..obs import recorder as obs
from ..resilience import faults

HEADER_WORDS = 8

METHOD_NONE = "none"
METHOD_CASCADED = "cascaded"

_U64 = jnp.uint64
_UINT_BY_SIZE = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}


@dataclasses.dataclass(frozen=True)
class CascadedOptions:
    """Cascade shape: RLE passes, delta passes, bitpacking.

    Mirror of nvcompCascadedFormatOpts {num_RLEs, num_deltas, use_bp}
    (/root/reference/src/compression.hpp:42-58); this codec supports at
    most one RLE and one delta pass (the configurations the reference's
    selector chooses in practice).
    """

    num_rles: int = 1
    num_deltas: int = 0
    use_bp: bool = True

    def __post_init__(self):
        assert 0 <= self.num_rles <= 1, "at most one RLE pass supported"
        assert 0 <= self.num_deltas <= 1, "at most one delta pass supported"


@dataclasses.dataclass(frozen=True)
class ColumnCompressionOptions:
    """Per-column compression plan, recursive for string sub-buffers.

    The analogue of the reference's ColumnCompressionOptions tree
    (/root/reference/src/compression.hpp:45-58). ``wire_factor`` is the
    static compressed-bucket capacity as a fraction of raw bucket bytes
    (TPU-specific: the collective's shape must be chosen at trace time).
    children = (sizes_options, chars_options) for string columns.
    """

    method: str = METHOD_NONE
    cascaded: CascadedOptions = CascadedOptions()
    wire_factor: float = 1.0
    children: tuple["ColumnCompressionOptions", ...] = ()


TableCompressionOptions = tuple[ColumnCompressionOptions, ...]


# ---------------------------------------------------------------------------
# Block codec primitives (all static shapes; run under vmap over peers).
# ---------------------------------------------------------------------------


def _bits_needed(maxdiff: jax.Array) -> jax.Array:
    """Smallest b with maxdiff < 2**b (0..64), as uint64 scalar."""
    k = jnp.arange(64, dtype=_U64)
    return jnp.sum((maxdiff >> k) > 0).astype(_U64)


def _rle(x: jax.Array, count: jax.Array):
    """Run-length encode x[:count] -> (values[B], lengths[B], run_count)."""
    B = x.shape[0]
    i = jnp.arange(B, dtype=jnp.int32)
    in_prefix = i < count
    boundary = jnp.concatenate(
        [count > 0, x[1:] != x[:-1]], axis=None
    ) & in_prefix
    r = jnp.sum(boundary).astype(_U64)
    starts = jnp.sort(jnp.where(boundary, i, B))  # run k starts at starts[k]
    vals = x.at[jnp.clip(starts, 0, B - 1)].get()
    ends = jnp.concatenate([starts[1:], jnp.full((1,), B, jnp.int32)])
    lens = jnp.maximum(
        jnp.minimum(ends, count) - starts, 0
    ).astype(_U64)
    valid = i.astype(_U64) < r
    return jnp.where(valid, vals, 0), jnp.where(valid, lens, 0), r


def _rle_decode(vals, lens, B: int) -> jax.Array:
    ends = jnp.cumsum(lens.astype(jnp.int32))
    run = count_leq_arange(ends, B)
    return vals.at[jnp.clip(run, 0, B - 1)].get()


def _zigzag(x: jax.Array) -> jax.Array:
    s = x.astype(jnp.int64)
    return ((s << 1) ^ (s >> 63)).astype(_U64)


def _unzigzag(z: jax.Array) -> jax.Array:
    z = z.astype(_U64)
    return ((z >> 1) ^ (-(z & 1).astype(jnp.int64)).astype(_U64)).astype(_U64)


def _pack(vals: jax.Array, r: jax.Array, b: jax.Array, cap_words: int):
    """Pack vals[:r] (b bits each) into uint64 words; returns (words, nw)."""
    B = vals.shape[0]
    i = jnp.arange(B, dtype=_U64)
    valid = i < r
    vals = jnp.where(valid, vals, 0)
    bitpos = i * b
    w0 = (bitpos >> _U64(6)).astype(jnp.int32)
    sh = bitpos & _U64(63)
    lo = vals << sh
    hi = jnp.where(sh > 0, vals >> (_U64(64) - sh), _U64(0))
    w0 = jnp.where(valid, w0, cap_words)
    words = jnp.zeros((cap_words,), _U64)
    # Contributions occupy disjoint bit ranges, so add == bitwise-or.
    words = words.at[w0].add(lo, mode="drop")
    words = words.at[w0 + 1].add(hi, mode="drop")
    nw = (r * b + _U64(63)) >> _U64(6)
    return words, nw


def _unpack(words: jax.Array, r: jax.Array, b: jax.Array, B: int):
    """Inverse of _pack: words -> B values (zeros beyond r)."""
    W = words.shape[0]
    i = jnp.arange(B, dtype=_U64)
    bitpos = i * b
    w0 = (bitpos >> _U64(6)).astype(jnp.int32)
    sh = bitpos & _U64(63)
    lo = words.at[w0].get(mode="fill", fill_value=0) >> sh
    hi = jnp.where(
        sh > 0,
        words.at[w0 + 1].get(mode="fill", fill_value=0) << (_U64(64) - sh),
        _U64(0),
    )
    mask = jnp.where(b >= 64, ~_U64(0), (_U64(1) << b) - _U64(1))
    v = (lo | hi) & mask
    return jnp.where(i < r, v, 0)


def _for_encode(vals: jax.Array, r: jax.Array):
    """Frame-of-reference: subtract the valid-prefix min; returns
    (rebased values, base, bit width)."""
    i = jnp.arange(vals.shape[0], dtype=_U64)
    valid = i < r
    vmin = jnp.min(jnp.where(valid, vals, ~_U64(0)))
    vmax = jnp.max(jnp.where(valid, vals, _U64(0)))
    vmin = jnp.minimum(vmin, vmax)  # r == 0 guard
    b = _bits_needed(vmax - vmin)
    return jnp.where(valid, vals - vmin, 0), vmin, b


def compressed_capacity_words(
    raw_bytes: int, wire_factor: float
) -> int:
    """Static uint64-word capacity of a compressed block."""
    return HEADER_WORDS + max(1, int(np.ceil(raw_bytes * wire_factor / 8)))


def compress_block(
    x: jax.Array,
    opts: CascadedOptions,
    cap_words: int,
    count: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Compress x[:count] (a uint64 block) into a static [cap_words] stream.

    Only the valid prefix is encoded — like the reference, which
    compresses exactly the partition's bytes, never bucket padding
    (/root/reference/src/all_to_all_comm.cpp:379-406). Elements beyond
    ``count`` decompress as zeros. Returns (words[cap_words],
    total_words, overflow). The equivalent of one compression_functor
    partition launch (/root/reference/src/compression.hpp:73-150).
    """
    B = x.shape[0]
    assert x.dtype == _U64
    count = jnp.int32(B) if count is None else count.astype(jnp.int32)
    r = count.astype(_U64)
    vals, lens = x, None
    if opts.num_rles:
        vals, lens, r = _rle(vals, count)
    else:
        i = jnp.arange(B, dtype=_U64)
        vals = jnp.where(i < r, vals, 0)
    base = vals[0]
    if opts.num_deltas:
        prev = jnp.concatenate([vals[:1], vals[:-1]])
        d = _zigzag((vals - prev).astype(_U64))
        i = jnp.arange(B, dtype=_U64)
        vals = jnp.where((i > 0) & (i < r), d, 0)  # slot 0 -> header base
    if opts.use_bp:
        vals, vmin, b_v = _for_encode(vals, r)
    else:
        vmin, b_v = _U64(0), _U64(64)
    pv, nw_v = _pack(vals, r, b_v, cap_words)
    if lens is not None:
        if opts.use_bp:
            lens, lmin, b_l = _for_encode(lens, r)
        else:
            lmin, b_l = _U64(0), _U64(64)
        pl, nw_l = _pack(lens, r, b_l, cap_words)
    else:
        pl = jnp.zeros((cap_words,), _U64)
        lmin, b_l, nw_l = _U64(0), _U64(0), _U64(0)

    header = jnp.stack(
        [
            r,
            b_v | (b_l << _U64(8)),
            vmin,
            base,
            lmin,
            nw_v,
            nw_l,
            count.astype(_U64),
        ]
    )
    out = jnp.zeros((cap_words,), _U64)
    out = out.at[:HEADER_WORDS].set(header)
    k = jnp.arange(cap_words, dtype=jnp.int32)
    # Value words at fixed offset; length words behind the (dynamic)
    # value region. Words beyond each region are zero, so unconditional
    # or-scatter with drop semantics is exact.
    out = out.at[HEADER_WORDS + k].add(pv, mode="drop")
    out = out.at[HEADER_WORDS + nw_v.astype(jnp.int32) + k].add(
        pl, mode="drop"
    )
    total = _U64(HEADER_WORDS) + nw_v + nw_l
    return out, total, total > cap_words


def decompress_block(
    words: jax.Array, opts: CascadedOptions, out_elems: int
) -> jax.Array:
    """Inverse of compress_block -> uint64[out_elems]."""
    B = out_elems
    r = words[0]
    b_v = words[1] & _U64(0xFF)
    b_l = (words[1] >> _U64(8)) & _U64(0xFF)
    vmin, base, lmin = words[2], words[3], words[4]
    nw_v = words[5]
    count = words[7]
    k = jnp.arange(B, dtype=jnp.int32)
    region_v = words.at[HEADER_WORDS + k].get(mode="fill", fill_value=0)
    vals = _unpack(region_v, r, b_v, B)
    i = jnp.arange(B, dtype=_U64)
    valid = i < r
    if opts.use_bp:
        vals = jnp.where(valid, vals + vmin, 0)
    if opts.num_deltas:
        d = _unzigzag(vals)
        d = jnp.where((i > 0) & valid, d, 0)
        vals = jnp.where(valid, base + jnp.cumsum(d), 0)
    if opts.num_rles:
        region_l = words.at[
            HEADER_WORDS + nw_v.astype(jnp.int32) + k
        ].get(mode="fill", fill_value=0)
        lens = _unpack(region_l, r, b_l, B)
        if opts.use_bp:
            lens = jnp.where(valid, lens + lmin, 0)
        vals = _rle_decode(vals, lens, B)
    return jnp.where(i < count, vals, 0)


def compress_buckets(
    buckets: jax.Array,
    itemsize: int,
    opts: CascadedOptions,
    cap_words: int,
    counts: Optional[jax.Array] = None,
):
    """Compress [n, B] physical-dtype buckets -> ([n, cap_words] u64,
    total_words[n], overflow[n]). ``counts[n]`` bounds each bucket's
    valid prefix (padding is never encoded). Peers map over vmap like
    the reference's per-peer compression streams
    (/root/reference/src/all_to_all_comm.cpp:326-332)."""
    # Deterministic fault site "codec" (resilience.faults): a failing
    # wire codec at build/trace time — the degradation ladder pins the
    # raw-wire baseline and retries. No-op when unarmed.
    faults.check("codec")
    u = _UINT_BY_SIZE[itemsize]
    as_u64 = jax.lax.bitcast_convert_type(buckets, u).astype(_U64)
    if counts is None:
        counts = jnp.full((buckets.shape[0],), buckets.shape[1], jnp.int32)
    return jax.vmap(
        lambda x, c: compress_block(x, opts, cap_words, c)
    )(as_u64, counts)


def decompress_buckets(
    received: jax.Array, itemsize: int, opts: CascadedOptions, out_elems: int,
    physical,
):
    """Inverse of compress_buckets -> [n, out_elems] physical buckets."""
    u = _UINT_BY_SIZE[itemsize]
    dec = jax.vmap(lambda w: decompress_block(w, opts, out_elems))(received)
    return jax.lax.bitcast_convert_type(dec.astype(u), jnp.dtype(physical))


# ---------------------------------------------------------------------------
# Option generation: selector, policy, distributed agreement.
# ---------------------------------------------------------------------------

_CANDIDATES = (
    CascadedOptions(num_rles=0, num_deltas=0, use_bp=True),
    CascadedOptions(num_rles=1, num_deltas=0, use_bp=True),
    CascadedOptions(num_rles=0, num_deltas=1, use_bp=True),
    CascadedOptions(num_rles=1, num_deltas=1, use_bp=True),
)


def _simulate_compressed_words(x: np.ndarray, opts: CascadedOptions) -> int:
    """Host-side exact size model of compress_block on a sample."""
    x = x.astype(np.uint64)
    r = x.size
    vals, lens = x, None
    if opts.num_rles and x.size:
        boundary = np.concatenate([[True], x[1:] != x[:-1]])
        vals = x[boundary]
        idx = np.flatnonzero(boundary)
        lens = np.diff(np.concatenate([idx, [x.size]])).astype(np.uint64)
        r = vals.size
    if opts.num_deltas and vals.size:
        d = np.zeros_like(vals)
        s = vals.astype(np.int64)
        d[1:] = ((s[1:] - s[:-1]) << 1 ^ (s[1:] - s[:-1]) >> 63).astype(
            np.uint64
        )
        vals = d

    def bits(a):
        if a.size == 0:
            return 0
        diff = int(a.max() - a.min())
        return max(0, diff.bit_length())

    total = HEADER_WORDS + -(-r * bits(vals) // 64)
    if lens is not None:
        total += -(-r * bits(lens) // 64)
    return total


def select_cascaded_options(
    data: np.ndarray,
    sample_chunks: int = 100,
    chunk_elems: int = 1024,
    slack: float = 2.0,
) -> tuple[CascadedOptions, float]:
    """Pick the cascade by measuring candidates on a sample.

    The analogue of nvcomp's CascadedSelector sampling 100x1024
    (/root/reference/src/compression.hpp:253-292), with one deliberate
    difference: the sample is randomly permuted before measuring,
    because the shuffle compresses hash-partitioned buckets whose rows
    are permuted relative to the input — a delta win on globally sorted
    input would not survive partitioning (and with static wire sizing a
    wrong pick means overflow, not just a worse ratio). Returns
    (options, wire_factor) where wire_factor is the sampled compressed
    fraction with ``slack`` headroom, clamped to [1/64, 1].
    """
    data = np.asarray(data)
    # View as unsigned of the same width: matches the device path's
    # bitcast-then-zero-extend, so sampled bit widths are exact.
    data = data.view(
        {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}[
            data.dtype.itemsize
        ]
    )
    n = data.size
    if n > sample_chunks * chunk_elems:
        stride = n // sample_chunks
        sample = np.concatenate(
            [data[k * stride : k * stride + chunk_elems] for k in range(sample_chunks)]
        )
    else:
        sample = data
    sample = np.random.default_rng(0).permutation(sample)
    raw_words = max(1, sample.size * data.dtype.itemsize // 8)
    best, best_words = _CANDIDATES[0], None
    for cand in _CANDIDATES:
        w = _simulate_compressed_words(sample, cand)
        if best_words is None or w < best_words:
            best, best_words = cand, w
    ratio = best_words / raw_words
    wire_factor = float(np.clip(ratio * slack, 1 / 64, 1.0))
    return best, wire_factor


def selector_sample(
    data, sample_chunks: int = 100, chunk_elems: int = 1024
) -> np.ndarray:
    """Strided selector sample with the device->host transfer bounded.

    The selector's sampling used to start with ``np.asarray(full
    column)`` — an 800 MB host pull per column at bench scale. The
    reference samples
    100x1024 chunks ON DEVICE (/root/reference/src/compression.hpp:
    253-292); this mirrors it: the strided chunks (identical positions
    to `select_cascaded_options`'s own host-side stride, so the picked
    cascade is unchanged) are sliced on device and ONLY the sample —
    at the default geometry <= 100 * 1024 * 8 B = 800 KB — crosses to
    the host. Small columns (<= the sample size) transfer whole.
    """
    n = int(data.shape[0])
    budget = sample_chunks * chunk_elems
    if n <= budget:
        return np.asarray(data)
    stride = n // sample_chunks
    if isinstance(data, np.ndarray):
        return np.concatenate(
            [
                data[k * stride : k * stride + chunk_elems]
                for k in range(sample_chunks)
            ]
        )
    idx = (
        np.arange(sample_chunks, dtype=np.int64)[:, None] * stride
        + np.arange(chunk_elems, dtype=np.int64)[None, :]
    ).reshape(-1)
    sample = np.asarray(jnp.take(data, jnp.asarray(idx), axis=0))
    assert sample.size <= budget
    return sample


def _record_select(kind: str, method: str, wire_factor=None, opts=None):
    """Flight-recorder trail of the sampling selector's per-column
    verdicts (host-side; the selector already runs on the host): which
    columns ride the codec, at what static wire_factor, and why the
    rest stayed raw — the reference prints the same decision per column
    (compression.cpp:36-73), we make it a structured event."""
    obs.inc("dj_compress_select_total", kind=kind, method=method)
    fields = dict(kind=kind, method=method)
    if wire_factor is not None:
        fields["wire_factor"] = round(float(wire_factor), 4)
    if opts is not None:
        fields["cascade"] = (
            f"rle={opts.num_rles},delta={opts.num_deltas},bp={int(opts.use_bp)}"
        )
    obs.record("compress_select", **fields)


def _auto_column_options(col: Column | StringColumn) -> ColumnCompressionOptions:
    if isinstance(col, StringColumn):
        # Policy from the reference (compression.cpp:44-60): compress the
        # size/offset sub-buffer, never the chars. Same incompressibility
        # fallback as fixed-width columns below.
        opts, wf = select_cascaded_options(selector_sample(col.sizes()))
        incompressible = wf >= 0.95
        _record_select(
            "string_sizes",
            METHOD_NONE if incompressible else METHOD_CASCADED,
            wf, None if incompressible else opts,
        )
        sizes_child = (
            ColumnCompressionOptions(METHOD_NONE)
            if incompressible
            else ColumnCompressionOptions(METHOD_CASCADED, opts, wf)
        )
        return ColumnCompressionOptions(
            METHOD_NONE,
            children=(sizes_child, ColumnCompressionOptions(METHOD_NONE)),
        )
    if col.dtype.kind == "float":
        # Cascaded is an integer codec (the reference's type dispatch
        # throws on unsupported types, compression.hpp:144-150); floats
        # ride uncompressed.
        _record_select("float", METHOD_NONE)
        return ColumnCompressionOptions(METHOD_NONE)
    opts, wf = select_cascaded_options(selector_sample(col.data))
    if wf >= 0.95:
        # Incompressible: the compressed path would move >= raw bytes
        # plus headers and pay codec compute — ride uncompressed.
        _record_select("column", METHOD_NONE, wf)
        return ColumnCompressionOptions(METHOD_NONE)
    _record_select("column", METHOD_CASCADED, wf, opts)
    return ColumnCompressionOptions(METHOD_CASCADED, opts, wf)


def generate_auto_select_compression_options(
    table: Table,
) -> TableCompressionOptions:
    """Sampling selector per column (host-side, on host or device data).

    Equivalent of generate_auto_select_compression_options
    (/root/reference/src/compression.cpp:36-73)."""
    return tuple(_auto_column_options(c) for c in table.columns)


def generate_none_compression_options(table: Table) -> TableCompressionOptions:
    """All-none options tree (strings get two none children), mirroring
    /root/reference/src/compression.cpp:76-96."""
    out = []
    for c in table.columns:
        if isinstance(c, StringColumn):
            out.append(
                ColumnCompressionOptions(
                    METHOD_NONE,
                    children=(
                        ColumnCompressionOptions(METHOD_NONE),
                        ColumnCompressionOptions(METHOD_NONE),
                    ),
                )
            )
        else:
            out.append(ColumnCompressionOptions(METHOD_NONE))
    return tuple(out)


def broadcast_compression_options(
    options: TableCompressionOptions,
) -> TableCompressionOptions:
    """Agree on process 0's options across a multi-host deployment.

    The jax.distributed analogue of the reference's recursive MPI_Bcast
    (/root/reference/src/compression.cpp:97-168). Compression options
    are static (they shape the compiled collective), so every process
    must trace with identical values; this broadcasts the root's choice.
    Single-process: identity.
    """
    if jax.process_count() == 1:
        return options
    from jax.experimental import multihost_utils

    def encode(o: ColumnCompressionOptions) -> list[float]:
        vec = [
            1.0 if o.method == METHOD_CASCADED else 0.0,
            float(o.cascaded.num_rles),
            float(o.cascaded.num_deltas),
            1.0 if o.cascaded.use_bp else 0.0,
            o.wire_factor,
            float(len(o.children)),
        ]
        for ch in o.children:
            vec.extend(encode(ch))
        return vec

    def decode(vec: list[float], pos: int) -> tuple[ColumnCompressionOptions, int]:
        method = METHOD_CASCADED if vec[pos] > 0.5 else METHOD_NONE
        casc = CascadedOptions(
            num_rles=int(vec[pos + 1]),
            num_deltas=int(vec[pos + 2]),
            use_bp=vec[pos + 3] > 0.5,
        )
        wf = float(vec[pos + 4])
        nchild = int(vec[pos + 5])
        pos += 6
        children = []
        for _ in range(nchild):
            ch, pos = decode(vec, pos)
            children.append(ch)
        return ColumnCompressionOptions(method, casc, wf, tuple(children)), pos

    flat: list[float] = []
    for o in options:
        flat.extend(encode(o))
    agreed = np.asarray(
        multihost_utils.broadcast_one_to_all(np.asarray(flat, np.float64))
    ).tolist()
    out, pos = [], 0
    for _ in options:
        o, pos = decode(agreed, pos)
        out.append(o)
    return tuple(out)
