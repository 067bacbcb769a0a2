"""CSV IO — pyarrow-backed read, host stringify write.

Reference: cpp/src/cylon/io/arrow_io.cpp:34-62 (Arrow CSV TableReader over
a memory-mapped file, options from the type-erased CSVConfigHolder) and
table.cpp:1019-1064 (multi-file concurrent read, one thread per file).
Here pyarrow's C++ CSV reader does the parsing (same engine family the
reference leans on), and the parsed host table is dictionary-encoded +
device_put into HBM.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Sequence, Union

from ..config import CSVReadOptions, CSVWriteOptions
from ..context import CylonContext
from ..data.table import Table, concat_tables
from ..resilience import inject as _inject
from ..resilience import retry as _retry
from ..status import Code, CylonDataError, CylonError


def _arrow_options(options: CSVReadOptions):
    import pyarrow.csv as pacsv

    o = options
    read_opts = pacsv.ReadOptions(
        use_threads=o._use_threads,
        block_size=o._block_size,
        skip_rows=o._skip_rows,
        column_names=o._column_names,
        autogenerate_column_names=o._autogenerate_column_names,
    )
    parse_opts = pacsv.ParseOptions(
        delimiter=o._delimiter,
        quote_char=o._quote_char if o._quoting else '"',
        double_quote=o._double_quote,
        escape_char=o._escape_char if o._escaping else False,
        newlines_in_values=o._newlines_in_values,
        ignore_empty_lines=bool(o._ignore_empty_lines),
    )
    convert_kwargs = dict(
        check_utf8=True,
        strings_can_be_null=o._strings_can_be_null,
        include_columns=o._include_columns,
        include_missing_columns=o._include_missing_columns,
    )
    if o._null_values is not None:
        convert_kwargs["null_values"] = o._null_values
    if o._true_values is not None:
        convert_kwargs["true_values"] = o._true_values
    if o._false_values is not None:
        convert_kwargs["false_values"] = o._false_values
    if o._column_types is not None:
        import pyarrow as pa

        m = {}
        for name, dt in o._column_types.items():
            m[name] = pa.from_numpy_dtype(dt.np_dtype) \
                if not dt.is_var_width() else pa.string()
        convert_kwargs["column_types"] = m
    convert_opts = pacsv.ConvertOptions(**convert_kwargs)
    return read_opts, parse_opts, convert_opts


def read_csv(ctx: CylonContext, path: Union[str, Sequence[str]],
             options: Optional[CSVReadOptions] = None) -> Table:
    """Reference: FromCSV (table.cpp:367-386); multi-file variant spawns a
    reader per file then merges (table.cpp:1030-1064)."""
    options = options or CSVReadOptions()
    if isinstance(path, (list, tuple)):
        paths: List[str] = list(path)
        if options.IsConcurrentFileReads():
            with ThreadPoolExecutor(max_workers=len(paths)) as ex:
                tables = list(ex.map(lambda p: _read_one(ctx, p, options), paths))
        else:
            tables = [_read_one(ctx, p, options) for p in paths]
        return concat_tables(tables, ctx)
    return _read_one(ctx, path, options)


def read_csv_per_rank(ctx: CylonContext, path_pattern: str,
                      options: Optional[CSVReadOptions] = None) -> Table:
    """Per-rank file placement: ``path_pattern`` contains ``{rank}``,
    substituted with each shard index (the reference's per-rank CSV
    convention, cpp/test/join_test.cpp:22-24 ``csv1_<rank>.csv``).

    Single-controller: reads EVERY shard's file and assembles them
    shard-aligned (shard i of the result holds file i's rows). Multi-host:
    each controller process reads only the files of the shards it owns —
    collective, all processes must call it.
    """
    from ..parallel import shard as _shard

    options = options or CSVReadOptions()
    local = ctx.local_shard_indices()
    paths = [path_pattern.format(rank=i) for i in local]
    if options.IsConcurrentFileReads() and len(paths) > 1:
        with ThreadPoolExecutor(max_workers=len(paths)) as ex:
            tables = list(ex.map(lambda p: _read_one(ctx, p, options), paths))
    else:
        tables = [_read_one(ctx, p, options) for p in paths]
    return _shard.assemble_process_local(tables, ctx)


def _read_one(ctx: CylonContext, path: str, options: CSVReadOptions) -> Table:
    import pyarrow as pa
    import pyarrow.csv as pacsv

    read_opts, parse_opts, convert_opts = _arrow_options(options)

    def attempt():
        _inject.fire("ingest", detail=f"csv {path}")
        try:
            return pacsv.read_csv(path, read_options=read_opts,
                                  parse_options=parse_opts,
                                  convert_options=convert_opts)
        except OSError as e:
            # environment errors (missing file, permissions, disk) are
            # IOError — fixable without touching the bytes, NOT bad
            # data
            raise CylonError(Code.IOError, str(e))
        except (pa.ArrowInvalid, pa.ArrowException, ValueError) as e:
            # malformed bytes are a DATA error, typed and
            # non-retryable — the parser's traceback never reaches
            # the caller
            raise CylonDataError(f"malformed CSV {path}: {e}") from e

    # transient filesystem failures retry under the same bounded
    # policy as exchanges; IOError/DataError are non-retryable and
    # leave the loop on the first attempt
    return Table.from_arrow(ctx, _retry.run_retryable("ingest",
                                                      attempt))


def write_csv(table: Table, path: str,
              options: Optional[CSVWriteOptions] = None) -> None:
    """Reference: Table::WriteCSV via PrintToOStream (table.cpp:429-440,
    1091-1142 — native C++ row stringify there, native C++ here: all-
    numeric tables go through the multithreaded writer in
    native/cylon_host.cpp; strings/temporal/bool fall back to pandas)."""
    options = options or CSVWriteOptions()
    names = options.GetColumnNames()
    t = table.compact() if table.row_mask is not None else table
    from .. import native as _native

    native_ok = (
        all(not c.is_string and not c.dtype.is_temporal()
            and c.host_dtype in _native.SUPPORTED_CSV_DTYPES
            for c in t._columns)
        and (names is None or len(names) == t.column_count))
    if native_ok:
        # one value a row: a 64-bit column held as word planes goes to
        # the writer as int64 / uint64 / float64, never as its planes
        cols = [c._host_data() for c in t._columns]
        valids = [c._host_mask() for c in t._columns]
        out_names = list(names) if names is not None else \
            [c.name or f"c{i}" for i, c in enumerate(t._columns)]
        if _native.write_csv_numeric(cols, valids, out_names, path,
                                     options.GetDelimiter()):
            return
    df = t.to_pandas()
    if names is not None:
        df.columns = names
    df.to_csv(path, sep=options.GetDelimiter(), index=False)
