"""Logical dtype model for columnar tables.

Covers the reference's cuDF type surface for join workloads — int32/int64
keys and payloads, timestamps and durations at four resolutions, floats,
and strings (reference sweep: /root/reference/test/compare_against_single_gpu.cu:237-268).

TPU-first storage choice: temporal types are *stored* as their integer
representation end to end (the reference reinterprets them to integers
only at the compression boundary, /root/reference/src/compression.hpp:96-118;
we make the integer rep the physical storage and keep the logical type as
column metadata, so every kernel — hash, sort, shuffle, codec — sees plain
integers and XLA never needs special temporal handling).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class DType:
    """A logical column dtype.

    Attributes:
      name: logical name ("int64", "timestamp_ns", "string", ...).
      physical: the numpy/jax dtype actually stored on device. For temporal
        types this is the integer tick count; for strings it is meaningless
        at column level (strings store chars uint8 + offsets int32).
      kind: one of {"int", "uint", "float", "timestamp", "duration", "string"}.
    """

    name: str
    physical: Any
    kind: str
    # Strings only: the most bytes a row holds (SQL CHAR(n)/VARCHAR(n)),
    # 0 for unbounded. A static promise about the data, which string
    # ops may plan by.
    max_len: int = 0

    @property
    def itemsize(self) -> int:
        return np.dtype(self.physical).itemsize

    def __repr__(self) -> str:
        return f"DType({self.name})"


int8 = DType("int8", np.int8, "int")
int16 = DType("int16", np.int16, "int")
int32 = DType("int32", np.int32, "int")
int64 = DType("int64", np.int64, "int")
uint8 = DType("uint8", np.uint8, "uint")
uint16 = DType("uint16", np.uint16, "uint")
uint32 = DType("uint32", np.uint32, "uint")
uint64 = DType("uint64", np.uint64, "uint")
float32 = DType("float32", np.float32, "float")
float64 = DType("float64", np.float64, "float")

# Temporal types: integer tick counts, resolution in the name. Matches the
# reference's coverage (cudf timestamp_{s,ms,us,ns}, duration_{s,ms,us,ns}).
timestamp_s = DType("timestamp_s", np.int64, "timestamp")
timestamp_ms = DType("timestamp_ms", np.int64, "timestamp")
timestamp_us = DType("timestamp_us", np.int64, "timestamp")
timestamp_ns = DType("timestamp_ns", np.int64, "timestamp")
duration_s = DType("duration_s", np.int64, "duration")
duration_ms = DType("duration_ms", np.int64, "duration")
duration_us = DType("duration_us", np.int64, "duration")
duration_ns = DType("duration_ns", np.int64, "duration")

string = DType("string", np.uint8, "string")

_BY_NAME = {
    d.name: d
    for d in [
        int8, int16, int32, int64,
        uint8, uint16, uint32, uint64,
        float32, float64,
        timestamp_s, timestamp_ms, timestamp_us, timestamp_ns,
        duration_s, duration_ms, duration_us, duration_ns,
        string,
    ]
}


def char(max_len: int) -> DType:
    """A string column whose rows hold at most ``max_len`` bytes, as SQL
    CHAR(n)/VARCHAR(n) declares (TPC-H's o_orderpriority is CHAR(15))."""
    assert max_len >= 1, max_len
    return DType(f"char{max_len}", np.uint8, "string", max_len)


def by_name(name: str) -> DType:
    return _BY_NAME[name]


def from_jnp(dtype) -> DType:
    """Best-effort logical dtype for a raw jax/numpy dtype."""
    return _BY_NAME[np.dtype(dtype).name]


def physical_jnp(dtype: DType):
    return jnp.dtype(dtype.physical)


# Canonical width -> unsigned dtype map for bitcast packing (shared by
# table.gather_rows, the join's u64 packing, and the shuffle's fused
# width groups).
UINT_BY_SIZE = {
    1: jnp.dtype(np.uint8),
    2: jnp.dtype(np.uint16),
    4: jnp.dtype(np.uint32),
    8: jnp.dtype(np.uint64),
}
