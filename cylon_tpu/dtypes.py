"""Data type system for cylon_tpu.

Mirrors the reference's stripped-down Arrow type system (reference:
cpp/src/cylon/data_types.hpp:25-175 — `Type::type` enum, `Layout`,
factory functions `Int64()`, `Double()`, ...), mapped onto device dtypes:

* fixed-width types map 1:1 to a ``jnp.dtype`` resident in HBM;
* STRING/BINARY are VARIABLE layout and are dictionary-encoded on device
  (int32 codes in HBM + host-side sorted vocabulary) because XLA has no
  variable-length array type — see data/column.py;
* temporal types carry their unit and are stored as int32/int64 lanes;
* an 8-byte lane on a backend without x64 is two 32-bit word planes
  (`to_word_planes` / `from_word_planes`; data/column.py says when).
"""
from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Optional

import numpy as np


class Type(enum.IntEnum):
    """Reference: cpp/src/cylon/data_types.hpp `Type::type` enum."""

    BOOL = 0
    UINT8 = 1
    INT8 = 2
    UINT16 = 3
    INT16 = 4
    UINT32 = 5
    INT32 = 6
    UINT64 = 7
    INT64 = 8
    HALF_FLOAT = 9
    FLOAT = 10
    DOUBLE = 11
    STRING = 12
    BINARY = 13
    FIXED_SIZE_BINARY = 14
    DATE32 = 15
    DATE64 = 16
    TIMESTAMP = 17
    TIME32 = 18
    TIME64 = 19
    INTERVAL = 20
    DECIMAL = 21
    LIST = 22
    EXTENSION = 23
    DURATION = 24


class Layout(enum.IntEnum):
    """Reference: data_types.hpp `Layout` (FIXED_WIDTH vs VARIABLE_WIDTH)."""

    FIXED_WIDTH = 1
    VARIABLE_WIDTH = 2


class TimeUnit(enum.IntEnum):
    SECOND = 0
    MILLI = 1
    MICRO = 2
    NANO = 3


_FIXED_NP: dict[Type, np.dtype] = {
    Type.BOOL: np.dtype(np.bool_),
    Type.UINT8: np.dtype(np.uint8),
    Type.INT8: np.dtype(np.int8),
    Type.UINT16: np.dtype(np.uint16),
    Type.INT16: np.dtype(np.int16),
    Type.UINT32: np.dtype(np.uint32),
    Type.INT32: np.dtype(np.int32),
    Type.UINT64: np.dtype(np.uint64),
    Type.INT64: np.dtype(np.int64),
    Type.HALF_FLOAT: np.dtype(np.float16),
    Type.FLOAT: np.dtype(np.float32),
    Type.DOUBLE: np.dtype(np.float64),
    # temporal lanes
    Type.DATE32: np.dtype(np.int32),
    Type.DATE64: np.dtype(np.int64),
    Type.TIMESTAMP: np.dtype(np.int64),
    Type.TIME32: np.dtype(np.int32),
    Type.TIME64: np.dtype(np.int64),
    Type.DURATION: np.dtype(np.int64),
}

_NP_TO_TYPE: dict[np.dtype, Type] = {
    np.dtype(np.bool_): Type.BOOL,
    np.dtype(np.uint8): Type.UINT8,
    np.dtype(np.int8): Type.INT8,
    np.dtype(np.uint16): Type.UINT16,
    np.dtype(np.int16): Type.INT16,
    np.dtype(np.uint32): Type.UINT32,
    np.dtype(np.int32): Type.INT32,
    np.dtype(np.uint64): Type.UINT64,
    np.dtype(np.int64): Type.INT64,
    np.dtype(np.float16): Type.HALF_FLOAT,
    np.dtype(np.float32): Type.FLOAT,
    np.dtype(np.float64): Type.DOUBLE,
}


@dataclass(frozen=True)
class DataType:
    """Reference: data_types.hpp `DataType::Make(type, layout)`."""

    type: Type
    layout: Layout = Layout.FIXED_WIDTH
    unit: Optional[TimeUnit] = field(default=None)  # temporal types only
    byte_width: int = -1  # FIXED_SIZE_BINARY only

    @staticmethod
    def Make(t: Type, layout: Layout = Layout.FIXED_WIDTH) -> "DataType":
        return DataType(t, layout)

    @property
    def np_dtype(self) -> np.dtype:
        """The numpy/jnp lane dtype backing this column on device."""
        if self.type in (Type.STRING, Type.BINARY):
            return np.dtype(np.int32)  # dictionary codes
        if self.type == Type.FIXED_SIZE_BINARY:
            return np.dtype(np.int32)  # dictionary codes
        try:
            return _FIXED_NP[self.type]
        except KeyError:
            raise TypeError(f"type {self.type.name} has no device lane dtype")

    def is_numeric(self) -> bool:
        return self.type in _FIXED_NP and self.type not in (
            Type.DATE32, Type.DATE64, Type.TIMESTAMP, Type.TIME32, Type.TIME64,
            Type.DURATION,
        )

    def is_temporal(self) -> bool:
        return self.type in (Type.DATE32, Type.DATE64, Type.TIMESTAMP,
                             Type.TIME32, Type.TIME64, Type.DURATION)

    def is_var_width(self) -> bool:
        return self.layout == Layout.VARIABLE_WIDTH


# Factory functions (reference: data_types.hpp TYPE_FACTORY macros).
def Bool() -> DataType: return DataType(Type.BOOL)
def UInt8() -> DataType: return DataType(Type.UINT8)
def Int8() -> DataType: return DataType(Type.INT8)
def UInt16() -> DataType: return DataType(Type.UINT16)
def Int16() -> DataType: return DataType(Type.INT16)
def UInt32() -> DataType: return DataType(Type.UINT32)
def Int32() -> DataType: return DataType(Type.INT32)
def UInt64() -> DataType: return DataType(Type.UINT64)
def Int64() -> DataType: return DataType(Type.INT64)
def HalfFloat() -> DataType: return DataType(Type.HALF_FLOAT)
def Float() -> DataType: return DataType(Type.FLOAT)
def Double() -> DataType: return DataType(Type.DOUBLE)
def String() -> DataType: return DataType(Type.STRING, Layout.VARIABLE_WIDTH)
def Binary() -> DataType: return DataType(Type.BINARY, Layout.VARIABLE_WIDTH)
def Date32() -> DataType: return DataType(Type.DATE32)
def Date64() -> DataType: return DataType(Type.DATE64)


def Timestamp(unit: TimeUnit = TimeUnit.MICRO) -> DataType:
    return DataType(Type.TIMESTAMP, Layout.FIXED_WIDTH, unit)


def Duration(unit: TimeUnit = TimeUnit.MICRO) -> DataType:
    return DataType(Type.DURATION, Layout.FIXED_WIDTH, unit)


def FixedSizeBinary(byte_width: int) -> DataType:
    return DataType(Type.FIXED_SIZE_BINARY, Layout.FIXED_WIDTH, None, byte_width)


def from_np_dtype(dt) -> DataType:
    """Infer a cylon DataType from a numpy dtype."""
    dt = np.dtype(dt)
    if dt in _NP_TO_TYPE:
        return DataType(_NP_TO_TYPE[dt])
    if dt.kind in ("U", "S", "O"):
        return String()
    if dt.kind == "M":
        return Timestamp(TimeUnit.NANO)
    if dt.kind == "m":
        return Duration(TimeUnit.NANO)
    raise TypeError(f"unsupported numpy dtype {dt}")


def to_word_planes(arr: np.ndarray) -> np.ndarray:
    """An 8-byte host array as ``uint32[2, n]``: plane 0 the high words,
    plane 1 the low, bit for bit (rows last: a minor dimension of 2 would
    be padded to a tile on the chip)."""
    words = np.ascontiguousarray(arr).view("<u4").reshape(-1, 2)
    return np.ascontiguousarray(words.T[::-1])


def from_word_planes(planes: np.ndarray, dt) -> np.ndarray:
    """The inverse of `to_word_planes`: ``n`` values of the 8-byte ``dt``."""
    planes = np.asarray(planes, dtype="<u4")
    words = np.stack([planes[1], planes[0]], axis=1)
    return words.view("<u8").reshape(-1).view(np.dtype(dt))
