"""Column — a typed, device-resident column with optional validity mask.

Mirrors the reference's Column (reference: cpp/src/cylon/column.hpp:31-113 —
id + DataType + arrow::ChunkedArray) with a TPU-native representation:

* fixed-width data is ONE dense jax array in HBM (the reference's
  CombineChunks "one chunk per column" invariant, table.cpp:374-379, is
  structural here);
* nullability is a separate boolean mask array (Arrow validity-bitmap
  analog) — absent mask means "all valid";
* STRING/BINARY columns are dictionary-encoded: a *sorted* host-side
  vocabulary (numpy object array) + int32 codes in HBM. Because the vocab is
  sorted, code order == lexicographic order, so device-side sort/join/
  group-by on strings are integer ops on the MXU-friendly codes. Cross-table
  ops unify vocabularies host-side and re-map codes with one device gather
  (`unify_dictionaries`).
* a 64-bit column (int64, uint64, float64, the 8-byte temporal types) on a
  backend without x64 (the chip) is held EXACTLY as two 32-bit word planes,
  ONE ``uint32[2, n]`` array (plane 0 the high words, plane 1 the low) under
  its logical 64-bit dtype: `Column.is_planes`. With x64 on it is the native
  array. Which form a column takes follows from ``jax_enable_x64`` alone; it
  is never narrowed. The local join takes plane-held columns; an operator
  that cannot yet raises (`refuse_planes`).

THE AXIS CONVENTION. Rows are the LAST axis of ``Column.data`` in both forms
(``[n]`` and ``[2, n]``): a minor dimension of 2 would be padded to a whole
tile on the chip. parallel/shuffle.py's 2-D leaves are the other way round,
rows FIRST (``x[:, j]``, ``shape[1:]`` is a row's trailing shape), so a
plane-held column never goes to the exchange as it is: it goes as its two
1-D planes (`split_planes`), two leaves, and is put together after
(`join_planes`); until that is written `parallel/shard.distribute` refuses
(ROADMAP.md 2a-a). What a word-plane array IS lives here and nowhere else:
`is_word_planes`, `split_planes`, `join_planes`, and on the host
`dtypes.to_word_planes` / `from_word_planes`.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes
from ..dtypes import DataType, Type
from ..status import Code, CylonError


class Column:
    def __init__(self, data, dtype: DataType, validity=None, dictionary=None,
                 name: str = "", varbytes=None):
        self.data = data              # jnp array [n] (codes for dict STRING,
        #                               byte lengths for varbytes STRING)
        self.dtype = dtype
        self.validity = validity      # jnp bool [n] (True=valid) or None
        self.dictionary = dictionary  # np.ndarray (sorted) for dict STRING
        self.varbytes = varbytes      # strings.VarBytes for varlen STRING
        self.name = name

    # -- construction --

    @staticmethod
    def from_numpy(arr: np.ndarray, name: str = "",
                   validity: Optional[np.ndarray] = None) -> "Column":
        arr = np.asarray(arr)
        if arr.dtype.kind in ("U", "S", "O"):
            return Column._encode_strings(arr, name, validity)
        if arr.dtype.kind == "M":  # datetime64
            unit = np.datetime_data(arr.dtype)[0]
            if unit == "D":
                # a date: days since the epoch in 32 bits (Arrow's
                # date32), not a 64-bit timestamp
                return Column(_to_device(arr.astype("int64")
                                         .astype(np.int32)),
                              dtypes.Date32(), _dev_mask(validity), None,
                              name)
            dt = dtypes.Timestamp(_np_unit(unit))
            data = _to_device(arr.astype("int64"))
            return Column(data, dt, _dev_mask(validity), None, name)
        if arr.dtype.kind == "m":
            unit = np.datetime_data(arr.dtype)[0]
            dt = dtypes.Duration(_np_unit(unit))
            return Column(_to_device(arr.astype("int64")), dt,
                          _dev_mask(validity), None, name)
        if arr.dtype.kind == "f" and validity is None and np.isnan(arr).any():
            # pandas-style: NaN means null for float columns coming from host
            validity = ~np.isnan(arr)
        dt = dtypes.from_np_dtype(arr.dtype)
        return Column(_to_device(arr), dt, _dev_mask(validity), None, name)

    @staticmethod
    def _encode_strings(arr: np.ndarray, name: str,
                        validity: Optional[np.ndarray]) -> "Column":
        from .strings import DICT_MAX_RATIO, DICT_MAX_VOCAB, VarBytes

        if arr.dtype.kind == "U" and validity is None and arr.ndim == 1:
            col = Column._encode_fixed_width(arr, name)
            if col is not None:
                return col
        obj = arr.astype(object)
        if validity is None:
            validity = np.array([v is not None and v == v for v in obj], dtype=bool)
        filler = ""
        safe = np.array([v if ok else filler for v, ok in zip(obj, validity)],
                        dtype=object)
        n = len(obj)
        thresh = min(DICT_MAX_VOCAB, max(16, int(n * DICT_MAX_RATIO)))
        # chunked distinct probe with early bail: the varbytes branch
        # (exactly the high-cardinality case) must not pay np.unique's
        # O(n log n) host string sort just to discard it. The same
        # chunked pass detects BINARY values (bytes must go straight to
        # varbytes — a str() decode corrupts non-UTF-8 payloads).
        seen: set = set()
        for lo in range(0, n, 1 << 16):
            chunk = safe[lo: lo + (1 << 16)]
            seen.update(chunk)
            if any(isinstance(v, bytes) for v in chunk):
                vb = VarBytes.from_host(safe)
                return Column.from_varbytes(
                    vb, _dev_mask(validity if not validity.all() else None),
                    name, dtypes.Binary())
            if len(seen) > thresh:
                # bailing early: later chunks may still hold BINARY
                # values — their scan is negligible next to from_host's
                # own full pass on this (varbytes) path
                is_bin = any(isinstance(v, bytes)
                             for v in safe[lo + (1 << 16):])
                vb = VarBytes.from_host(safe)
                return Column.from_varbytes(
                    vb, _dev_mask(validity if not validity.all() else None),
                    name, dtypes.Binary() if is_bin else None)
        vocab, codes = np.unique(safe.astype(str), return_inverse=True)
        col = Column(jnp.asarray(codes.astype(np.int32)), dtypes.String(),
                     _dev_mask(validity if not validity.all() else None),
                     vocab, name)
        return col

    @staticmethod
    def _encode_fixed_width(arr: np.ndarray, name: str
                            ) -> Optional["Column"]:
        """`_encode_strings` for a fixed-width ``U`` array with no
        validity, without a Python-level pass or an object copy (minutes
        and ~10 GB at 7.5e7 rows): the same sorted vocabulary and the
        same codes. One code point a string is compared as the integer it
        is (its uint32 view orders as the string does). None where the
        distinct values pass the dictionary's threshold: the general
        path's varbytes branch takes those."""
        from .strings import DICT_MAX_RATIO, DICT_MAX_VOCAB

        n = len(arr)
        thresh = min(DICT_MAX_VOCAB, max(16, int(n * DICT_MAX_RATIO)))
        keys = arr.view(np.uint32) if arr.dtype.itemsize == 4 else arr
        vocab = keys[:0]
        for lo in range(0, n, 1 << 22):
            vocab = np.union1d(vocab, np.unique(keys[lo: lo + (1 << 22)]))
            if len(vocab) > thresh:
                return None
        codes = np.searchsorted(vocab, keys).astype(np.int32)
        return Column(jnp.asarray(codes), dtypes.String(), None,
                      vocab.view(arr.dtype) if keys is not arr else vocab,
                      name)

    @staticmethod
    def from_varbytes(vb, validity=None, name: str = "",
                      dtype: Optional[DataType] = None) -> "Column":
        """Wrap device-native varlen storage (data/strings.py). The
        Column's ``data`` array carries the byte lengths so generic
        shape/row plumbing works; content lives in ``varbytes``."""
        return Column(vb.lengths, dtype or dtypes.String(), validity,
                      None, name, varbytes=vb)

    @staticmethod
    def from_pyarrow(pa_arr, name: str = "") -> "Column":
        """Build from a pyarrow Array/ChunkedArray (combines chunks)."""
        import pyarrow as pa

        if isinstance(pa_arr, pa.ChunkedArray):
            pa_arr = pa_arr.combine_chunks()
        if isinstance(pa_arr, pa.ChunkedArray):  # 0-chunk edge
            pa_arr = pa.concat_arrays(pa_arr.chunks) if pa_arr.num_chunks else \
                pa.array([], type=pa_arr.type)
        t = pa_arr.type
        nulls = pa_arr.null_count > 0
        if pa.types.is_string(t) or pa.types.is_large_string(t) or \
                pa.types.is_binary(t) or pa.types.is_large_binary(t):
            import pyarrow.compute as pac

            from .strings import DICT_MAX_RATIO, DICT_MAX_VOCAB, VarBytes

            n = len(pa_arr)
            is_bin = pa.types.is_binary(t) or pa.types.is_large_binary(t)
            nuniq = pac.count_distinct(pa_arr).as_py() if n else 0
            if is_bin or \
                    nuniq > min(DICT_MAX_VOCAB, max(16, int(n * DICT_MAX_RATIO))):
                # high cardinality (or non-UTF8 binary, which the sorted-
                # str vocab can't represent) → varbytes straight from
                # Arrow buffers; nulls become empty rows under validity
                if nulls:
                    validity = np.asarray(pa_arr.is_valid())
                    pa_arr = pac.fill_null(pa_arr, b"" if is_bin else "")
                else:
                    validity = None
                bufs = pa_arr.buffers()
                odt = np.int64 if pa.types.is_large_string(t) or \
                    pa.types.is_large_binary(t) else np.int32
                offsets = np.frombuffer(bufs[1], odt)[
                    pa_arr.offset: pa_arr.offset + n + 1]
                data = bufs[2].to_pybytes() if bufs[2] is not None else b""
                vb = VarBytes.from_arrow_buffers(offsets, data)
                return Column.from_varbytes(
                    vb, _dev_mask(validity), name,
                    dtype=dtypes.Binary() if is_bin else None)
            np_obj = pa_arr.to_numpy(zero_copy_only=False)
            validity = np.array([v is not None for v in np_obj]) if nulls else None
            return Column._encode_strings(np.asarray(np_obj, dtype=object), name, validity)
        if pa.types.is_dictionary(t):
            return Column.from_pyarrow(pa_arr.dictionary_decode(), name)
        np_arr = pa_arr.to_numpy(zero_copy_only=False)
        validity = None
        if nulls:
            validity = np.asarray(pa_arr.is_valid())
            if np_arr.dtype.kind == "f":
                np_arr = np.nan_to_num(np_arr)  # keep device data finite where null
            elif np_arr.dtype == object:
                fill = 0
                np_arr = np.array([v if ok else fill
                                   for v, ok in zip(np_arr, validity)])
        return Column.from_numpy(np_arr, name, validity)

    @staticmethod
    def Make(ctx, name, dtype, values) -> "Column":
        """Reference parity: Column::Make / VectorColumn::Make (column.hpp:84-113)."""
        del ctx
        c = Column.from_numpy(np.asarray(values), name)
        if c.dtype.type != dtype.type and not c.dtype.is_var_width():
            c = c.astype(dtype)
        return c

    # -- properties --

    def __len__(self) -> int:
        return int(self.data.shape[-1])  # rows are the last axis

    @property
    def is_planes(self) -> bool:
        """A 64-bit column held as two 32-bit word planes, ``uint32[2,
        n]`` (module docstring): x64 is off."""
        return is_word_planes(self.data)

    @property
    def host_dtype(self) -> np.dtype:
        """The dtype of `_host_data`: a plane-held column's logical
        64-bit dtype, else the device array's own."""
        return np.dtype(self.dtype.np_dtype if self.is_planes
                        else self.data.dtype)

    @property
    def is_string(self) -> bool:
        return self.dictionary is not None or self.varbytes is not None

    @property
    def is_varbytes(self) -> bool:
        return self.varbytes is not None

    def null_count(self) -> int:
        if self.validity is None:
            return 0
        return int((~self.validity).sum())

    def valid_mask(self) -> jnp.ndarray:
        if self.validity is None:
            return jnp.ones(len(self), dtype=bool)
        return self.validity

    # -- transforms --

    def astype(self, dtype: DataType) -> "Column":
        if self.is_string:
            raise CylonError(Code.TypeError, "cannot cast string column")
        refuse_planes([self], "astype")
        return Column(self.data.astype(dtype.np_dtype), dtype, self.validity,
                      None, self.name)

    def take(self, indices, fill_invalid: bool = True) -> "Column":
        """Gather rows; negative indices produce NULL rows (the reference's
        −1→null gather, util/copy_arrray.cpp:16-287)."""
        idx = jnp.asarray(indices)
        if len(self) == 0 and not self.is_varbytes:
            data = jnp.zeros(self.data.shape[:-1] + idx.shape,
                             self.data.dtype)
            return Column(data, self.dtype, jnp.zeros(idx.shape, bool),
                          self.dictionary, self.name)
        neg = idx < 0
        safe = jnp.where(neg, 0, idx)
        validity = None
        if fill_invalid or self.validity is not None:
            # NOTE: an all-True mask is NOT collapsed to None here — that
            # would force a device→host sync on every gather. Export
            # paths collapse it instead.
            if len(self) == 0:
                validity = jnp.zeros(idx.shape, bool)
            else:
                validity = jnp.take(self.valid_mask(), safe, axis=0) & ~neg
        if self.is_varbytes:
            vb = self.varbytes.take(idx)  # negatives → empty rows
            return Column(vb.lengths, self.dtype, validity, None, self.name,
                          varbytes=vb)
        data = jnp.take(self.data, safe, axis=-1)
        return Column(data, self.dtype, validity, self.dictionary, self.name)

    def slice(self, start: int, stop: int) -> "Column":
        v = None if self.validity is None else self.validity[start:stop]
        if self.is_varbytes:
            vb = self.varbytes.slice(start, stop)
            return Column(vb.lengths, self.dtype, v, None, self.name,
                          varbytes=vb)
        return Column(self.data[..., start:stop], self.dtype, v,
                      self.dictionary, self.name)

    def rename(self, name: str) -> "Column":
        return Column(self.data, self.dtype, self.validity, self.dictionary,
                      name, varbytes=self.varbytes)

    # -- export --

    def _host_mask(self) -> Optional[np.ndarray]:
        """Validity as a host array, collapsing all-True to None."""
        if self.validity is None:
            return None
        mask = np.asarray(jax.device_get(self.validity))
        return None if mask.all() else mask

    def _host_data(self) -> np.ndarray:
        """``data`` on the host, one value a row: the word planes of a
        plane-held column put back together as its 64-bit dtype."""
        data = np.asarray(jax.device_get(self.data))
        if self.is_planes:
            return dtypes.from_word_planes(data, self.dtype.np_dtype)
        return data

    def to_numpy(self) -> np.ndarray:
        if self.is_varbytes:
            out = self.varbytes.to_host(
                as_str=self.dtype.type != Type.BINARY)
            mask = self._host_mask()
            if mask is not None:
                out[~mask] = None
            return out
        data = self._host_data()
        mask = self._host_mask()
        if self.is_string:
            out = self.dictionary[data].astype(object)
            if mask is not None:
                out[~mask] = None
            return out
        if mask is not None:
            if data.dtype.kind == "f":
                out = data.astype(data.dtype, copy=True)
                out[~mask] = np.nan
                return out
            out = data.astype(object)
            out[~mask] = None
            return out
        if self.dtype.is_temporal():
            unit = {None: "us"}.get(self.dtype.unit, None)
            unit = _unit_str(self.dtype.unit)
            if self.dtype.type == Type.TIMESTAMP:
                return data.astype(f"datetime64[{unit}]")
            if self.dtype.type == Type.DURATION:
                return data.astype(f"timedelta64[{unit}]")
            if self.dtype.type == Type.DATE32:
                return data.astype("datetime64[D]")
        return data

    def to_pyarrow(self):
        import pyarrow as pa

        valid = self._host_mask()
        mask = None if valid is None else ~valid
        if self.is_varbytes:
            if self.dtype.type == Type.BINARY:
                return pa.array(self.varbytes.to_host(as_str=False),
                                type=pa.binary(), mask=mask)
            return pa.array(self.varbytes.to_host(), type=pa.string(),
                            mask=mask)
        data = self._host_data()
        if self.is_string:
            vals = self.dictionary[data]
            return pa.array(vals, type=pa.string(),
                            mask=mask if mask is not None else None)
        return pa.array(data, mask=mask)


def as_varbytes(col: Column) -> Column:
    """Lift a string column to device-native varbytes storage. Dictionary
    columns build the (small, host-resident by definition) vocab's
    VarBytes once, then ONE device varlen gather re-materializes rows —
    no per-row host work."""
    from .strings import VarBytes

    if col.is_varbytes:
        return col
    if not col.is_string:
        raise CylonError(Code.TypeError, "as_varbytes needs a string column")
    vocab_vb = VarBytes.from_host(col.dictionary)
    vb = vocab_vb.take(col.data)
    return Column(vb.lengths, col.dtype, col.validity, None, col.name,
                  varbytes=vb)


def align_string_columns(a: Column, b: Column) -> Tuple[Column, Column]:
    """Make two string columns directly comparable on device: if either
    side is varbytes, lift both (content hashes compare with no shared
    vocabulary); two dictionary columns unify vocabularies instead."""
    if a.is_varbytes or b.is_varbytes:
        return as_varbytes(a), as_varbytes(b)
    return unify_dictionaries(a, b)


def string_key_arrays(col: Column, k_words: int = None):
    """Device key arrays standing in for one string key column.

    varbytes, short (≤ EXACT_KEY_WORDS words): the raw prefix word lanes
    + byte length — byte-EXACT equality, matching the reference's
    guarantee (join/join.cpp:648-799) with zero hashing. ``k_words``
    forces the lane count so two joined columns emit aligned lanes
    (pass max of both sides' max_words).

    varbytes, long: (h1, h2, h3, len) 96-bit content-hash identity.
    dictionary: the (already rank-preserving) codes.
    Returns (keys, valids, flags) triples ready to extend a
    join/groupby key list."""
    from .strings import EXACT_KEY_WORDS

    if col.is_varbytes:
        vb = col.varbytes
        k = vb.max_words if k_words is None else max(int(k_words),
                                                     vb.max_words)
        if k <= EXACT_KEY_WORDS:
            ks = vb.word_lanes(k) + [vb.lengths.astype(jnp.uint32)]
        else:
            ks = list(vb.hash_keys())
        return (ks, [col.validity] + [None] * (len(ks) - 1),
                [False] * len(ks))
    return [col.data], [col.validity], [True]


def unify_dictionaries(a: Column, b: Column) -> Tuple[Column, Column]:
    """Re-encode two string columns onto one shared *sorted* vocabulary so
    their codes are directly comparable on device. Host cost is O(|vocab|);
    device cost is one gather per column."""
    if not (a.is_string and b.is_string):
        raise CylonError(Code.TypeError, "unify_dictionaries needs string columns")
    if a.dictionary.shape == b.dictionary.shape and \
            (a.dictionary == b.dictionary).all():
        return a, b
    union = np.union1d(a.dictionary, b.dictionary)
    map_a = jnp.asarray(np.searchsorted(union, a.dictionary).astype(np.int32))
    map_b = jnp.asarray(np.searchsorted(union, b.dictionary).astype(np.int32))
    na = Column(jnp.take(map_a, a.data), a.dtype, a.validity, union, a.name)
    nb = Column(jnp.take(map_b, b.data), b.dtype, b.validity, union, b.name)
    return na, nb


def _to_device(arr: np.ndarray):
    """The host array on the device, exactly: an 8-byte dtype on a backend
    without x64 as its two word planes (``jnp.asarray`` would narrow it
    to 32 bits and say nothing)."""
    if arr.dtype.itemsize == 8 and not jax.config.jax_enable_x64:
        return jnp.asarray(dtypes.to_word_planes(arr))
    return jnp.asarray(arr)


def is_word_planes(x) -> bool:
    """Whether the array is a 64-bit column's two word planes,
    ``uint32[2, n]`` (module docstring), and not one lane ``[n]``."""
    return x.ndim == 2 and x.shape[0] == 2 and x.dtype == np.uint32


def split_planes(x):
    """``(hi, lo)``: the two ``uint32[n]`` planes of a word-plane array."""
    return x[0], x[1]


def join_planes(hi, lo):
    """`split_planes`' inverse: ``uint32[2, n]`` on the device."""
    return jnp.stack([hi, lo])


def refuse_planes(cols, operator: str) -> None:
    """Raise for the first plane-held column among ``cols``: ``operator``
    cannot take a 64-bit column held as word planes yet."""
    for c in cols:
        if c.is_planes:
            raise CylonError(
                Code.NotImplemented,
                f"{operator}: column {c.name!r} is {c.dtype.type.name} held "
                f"as two 32-bit word planes (jax_enable_x64 is off) and "
                f"{operator} cannot take such a column yet (the local inner, "
                f"left and right joins can)")


def _dev_mask(validity: Optional[np.ndarray]):
    if validity is None:
        return None
    v = np.asarray(validity, dtype=bool)
    if v.all():
        return None
    return jnp.asarray(v)


def _np_unit(unit: str):
    from ..dtypes import TimeUnit

    return {"s": TimeUnit.SECOND, "ms": TimeUnit.MILLI,
            "us": TimeUnit.MICRO, "ns": TimeUnit.NANO}[unit]


def _unit_str(unit) -> str:
    from ..dtypes import TimeUnit

    if unit is None:
        return "us"
    return {TimeUnit.SECOND: "s", TimeUnit.MILLI: "ms",
            TimeUnit.MICRO: "us", TimeUnit.NANO: "ns"}[unit]
