"""Data sources: CSV / JSON-lines / Parquet readers into ColumnarFrame.

Parity: ``sql/core/src/main/scala/.../DataFrameReader.scala:64`` (the
``spark.read.csv/json/parquet`` front door) and the format implementations
under ``sql/core/.../execution/datasources/``.

TPU-first mapping: a data source's job here is to land numeric columns as
device arrays (ready for the fused expression DSL / segment aggregates) and
keep string columns host-side.  CSV and JSON-lines are parsed natively
(stdlib); Parquet rides pyarrow when present (the environment ships it) and
fails with a clear message when not -- a columnar wire format needs a real
decoder, and vendoring one would be padding, not capability.
"""

from __future__ import annotations

import csv as _csv
import json as _json
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from asyncframework_tpu.sql.frame import ColumnarFrame


_I32 = (np.iinfo(np.int32).min, np.iinfo(np.int32).max)
_F32_EXACT = 1 << 24  # float32 represents integers exactly up to 2**24


def _int_column(ints: List[int]):
    """int32 device column when every value fits; otherwise a HOST column of
    Python ints.  The frame's device dtype for integers is int32 (jax x64 is
    off), and silently wrapping a 64-bit ID would corrupt data -- wide
    integers are identifiers in practice, and identifiers are join/group
    keys, which host columns serve exactly."""
    if all(_I32[0] <= v <= _I32[1] for v in ints):
        return np.asarray(ints, np.int32)
    return np.asarray(ints, dtype=object)


def _to_column(values: List[str], name: str):
    """Infer int -> float -> string, with '' treated as missing (NaN for
    floats; kept as '' for strings; promotes int columns to float)."""
    has_missing = any(v == "" for v in values)
    if not has_missing:
        try:
            return _int_column([int(v) for v in values])
        except ValueError:
            pass
    else:
        # nullable int column: float32 only when every value is exactly
        # representable; wide IDs stay a host column with None for missing
        try:
            ints = [int(v) if v != "" else None for v in values]
            if any(
                v is not None and abs(v) > _F32_EXACT for v in ints
            ):
                return np.asarray(ints, dtype=object)
        except ValueError:
            pass
    try:
        return np.asarray(
            [float(v) if v != "" else np.nan for v in values], np.float32
        )
    except ValueError:
        return np.asarray(values, dtype=object)


def _apply_pushdown(
    cols: Dict[str, object],
    select: Optional[Sequence[str]],
    where,
    mask=None,
) -> ColumnarFrame:
    """Shared reader pushdown (Optimizer.scala:38's data-source rules, in
    spirit): the predicate filters HOST arrays before any device placement
    -- the chip never receives pruned rows -- and the projection drops
    unselected columns before the frame is built.  ``mask`` short-circuits
    a predicate the caller already evaluated."""
    if where is not None or mask is not None:
        if mask is None:
            mask = where(cols)
        mask = np.asarray(mask, bool)
        cols = {k: np.asarray(v)[mask] for k, v in cols.items()}
    if select is not None:
        missing = [c for c in select if c not in cols]
        if missing:
            raise KeyError(f"select columns not in source: {missing}")
        cols = {c: cols[c] for c in select}
    return ColumnarFrame(cols)


def _needed_for_predicate(where, materialize, names):
    """Discover the predicate's column set by evaluation: start empty,
    materialize each column the evaluation KeyErrors on.  Columns the
    predicate never touches are never parsed (projection pushdown reaches
    through the predicate).  Returns ``(cols, mask)`` -- the successful
    evaluation IS the row mask, so callers never re-evaluate."""
    cols: Dict[str, object] = {}
    while True:
        try:
            return cols, where(cols)
        except KeyError as e:
            name = e.args[0].split("'")[1] if "'" in str(e.args[0]) else None
            if name is None or name in cols or name not in names:
                raise
            cols[name] = materialize(name)


class _FastPathUnsupported(Exception):
    """Internal: this CSV needs the general python-csv path (quoted
    fields, exotic delimiters, no pandas)."""


def _to_column_fast(vals: np.ndarray, name: str):
    """Vectorized ``_to_column``: the SAME int -> float -> string inference
    over exact cell strings, with numpy's C parsers instead of per-cell
    Python.  Falls back to the reference implementation for corners the
    vector ops cannot reproduce (e.g. > 64-bit integers)."""
    s = np.asarray(vals).astype("U")  # fixed-width unicode: C compare/parse
    missing = s == ""
    has_missing = bool(missing.any())
    if not has_missing:
        try:
            return _int_column(s.astype(np.int64).tolist())
        except (ValueError, OverflowError):
            # looks integral but did not parse as int64 (e.g. wider than
            # 64 bits): the exact python path owns that corner
            stripped = np.char.lstrip(s, "+-")
            if stripped.size and bool(np.char.isdigit(stripped).all()):
                return _to_column([str(v) for v in s], name)
    else:
        try:
            nz = s[~missing].astype(np.int64)
        except (ValueError, OverflowError):
            nz = None
        if nz is not None and nz.size and int(np.abs(nz).max()) > _F32_EXACT:
            # nullable int column with wide IDs: host column, None missing
            out = np.empty(s.shape[0], dtype=object)
            out[~missing] = [int(v) for v in nz]
            return out
    try:
        return np.where(missing, "nan", s).astype(np.float32)
    except ValueError:
        return s.astype(object)


def _raise_ragged(path, text, delimiter, header, want_count):
    """Locate the first bad row for the python path's exact error shape."""
    lines = [l for l in text.splitlines() if l]
    data = lines[1:] if header else lines
    for i, line in enumerate(data):
        c = line.count(delimiter)
        if c != want_count:
            raise ValueError(
                f"{path}: row {i + 1} has {c + 1} fields, "
                f"expected {want_count + 1}"
            )
    raise ValueError(f"{path}: inconsistent field counts")


def _read_csv_fast(path, header, columns, delimiter, select, where):
    """pandas-C-parser fast path (~7x the python csv module at 1M rows,
    CPU rig): clean numeric columns parse typed in C
    (``keep_default_na=False`` keeps empty cells as '' so mixed/missing
    columns arrive as exact strings and run through the same inference).
    Restricted to quote-free single-char delimiters.  Ragged rows keep the
    python path's validation contract: the C parser rejects extra fields,
    and a whole-file delimiter count catches missing ones (an extra-field
    row cannot mask a short row -- it raises first)."""
    try:
        import pandas as pd
    except ImportError:  # pragma: no cover - pandas ships in this image
        raise _FastPathUnsupported("no pandas")
    if len(delimiter) != 1:
        raise _FastPathUnsupported("multi-char delimiter")
    with open(path, newline="") as f:
        text = f.read()
    if '"' in text:
        raise _FastPathUnsupported("quoted fields")
    if not text.strip():
        raise ValueError(f"{path}: empty CSV")
    if not header and columns is None:
        raise ValueError("header=False requires explicit column names")
    import io as _io

    kw = dict(keep_default_na=False, sep=delimiter, engine="c")
    try:
        if header:
            df = pd.read_csv(_io.StringIO(text), **kw)
            names = list(df.columns)
            if columns is not None:
                names = list(columns)
                df.columns = names
        else:
            names = list(columns)
            df = pd.read_csv(_io.StringIO(text), header=None, names=names,
                             **kw)
    except pd.errors.ParserError:
        _raise_ragged(path, text, delimiter, header,
                      len(columns) - 1 if columns is not None and not header
                      else text.split("\n", 1)[0].count(delimiter))
    except pd.errors.EmptyDataError:
        raise ValueError(f"{path}: empty CSV")
    want_count = len(names) - 1
    header_cnt = (text.split("\n", 1)[0].count(delimiter) if header else 0)
    if text.count(delimiter) != want_count * len(df) + header_cnt:
        _raise_ragged(path, text, delimiter, header, want_count)

    def materialize(name: str):
        a = df[name].to_numpy()
        if a.dtype.kind == "i":  # clean int64 parse: downcast rules only
            lo, hi = (int(a.min()), int(a.max())) if len(a) else (0, 0)
            if _I32[0] <= lo and hi <= _I32[1]:
                return a.astype(np.int32)
            return np.asarray(a.tolist(), dtype=object)
        if a.dtype.kind == "f":
            # the python path's float32(str) also rounds through float64
            # (float() then np.float32), so this is bit-identical
            return a.astype(np.float32)
        if a.dtype.kind != "O":  # bool or other pandas inference: bail
            raise _FastPathUnsupported(f"pandas dtype {a.dtype}")
        return _to_column_fast(a, name)

    wanted = list(select) if select is not None else names
    missing_cols = [c for c in wanted if c not in names]
    if missing_cols:
        raise KeyError(f"select columns not in source: {missing_cols}")
    cols: Dict[str, object] = {}
    mask = None
    if where is not None:
        cols, mask = _needed_for_predicate(where, materialize, set(names))
    for name in wanted:
        if name not in cols:
            cols[name] = materialize(name)
    return _apply_pushdown(cols, wanted, where, mask=mask)


def read_csv(
    path: Union[str, Path],
    header: bool = True,
    columns: Optional[Sequence[str]] = None,
    delimiter: str = ",",
    select: Optional[Sequence[str]] = None,
    where=None,
) -> ColumnarFrame:
    """Load a CSV into a ColumnarFrame.

    Numeric columns (int/float inference per column) become device arrays;
    anything else stays a host string column.  ``columns`` overrides/provides
    names (required when ``header=False``).

    Pushdown: ``select`` keeps only the named columns -- unselected columns
    (beyond those the predicate needs) are never parsed or inferred at all;
    ``where`` (a Column predicate) filters rows before device placement.

    Quote-free files take the pandas-C-parser fast path (same inference
    over exact cell strings); quoted fields and exotic delimiters use the
    python csv module below.
    """
    try:
        return _read_csv_fast(path, header, columns, delimiter, select,
                              where)
    except _FastPathUnsupported:
        pass
    with open(path, newline="") as f:
        reader = _csv.reader(f, delimiter=delimiter)
        rows = [r for r in reader if r]
    if not rows:
        raise ValueError(f"{path}: empty CSV")
    if header:
        names = rows[0]
        rows = rows[1:]
    else:
        if columns is None:
            raise ValueError("header=False requires explicit column names")
        names = list(columns)
    if columns is not None and header:
        names = list(columns)
    width = len(names)
    for i, r in enumerate(rows):
        if len(r) != width:
            raise ValueError(
                f"{path}: row {i + 1} has {len(r)} fields, expected {width}"
            )
    index = {name: j for j, name in enumerate(names)}

    def materialize(name: str):
        return _to_column([r[index[name]] for r in rows], name)

    wanted = list(select) if select is not None else names
    bad = [c for c in wanted if c not in index]
    if bad:
        raise KeyError(f"select columns not in source: {bad}")
    cols: Dict[str, object] = {}
    mask = None
    if where is not None:
        cols, mask = _needed_for_predicate(where, materialize, set(names))
    for name in wanted:
        if name not in cols:
            cols[name] = materialize(name)
    return _apply_pushdown(cols, wanted, where, mask=mask)


def read_json(
    path: Union[str, Path],
    select: Optional[Sequence[str]] = None,
    where=None,
) -> ColumnarFrame:
    """JSON-lines (one object per line) into a ColumnarFrame; the schema is
    the union of keys, missing values become NaN/''.  ``select``/``where``
    push projection and row filtering below device placement."""
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                records.append(_json.loads(line))
    if not records:
        raise ValueError(f"{path}: no records")
    names: List[str] = []
    for r in records:
        for k in r:
            if k not in names:
                names.append(k)
    cols: Dict[str, object] = {}
    for name in names:
        vals = [r.get(name) for r in records]
        if all(
            isinstance(v, int) and not isinstance(v, bool) for v in vals
        ):
            # pure-integer column: size-check BEFORE any float32 round trip
            # (float32 silently distorts ints above 2**24)
            cols[name] = _int_column(vals)
        elif all(isinstance(v, (int, float)) or v is None for v in vals):
            if any(
                isinstance(v, int) and not isinstance(v, bool)
                and abs(v) > _F32_EXACT
                for v in vals
            ):
                # nullable/mixed column with wide ints: a single null must
                # not reroute IDs through lossy float32
                cols[name] = np.asarray(vals, dtype=object)
            else:
                cols[name] = np.asarray(
                    [float(v) if v is not None else np.nan for v in vals],
                    np.float32,
                )
        else:
            cols[name] = np.asarray(
                ["" if v is None else str(v) for v in vals], dtype=object
            )
    if select is not None or where is not None:
        return _apply_pushdown(cols, select, where)
    return ColumnarFrame(cols)


def read_parquet(
    path: Union[str, Path],
    columns: Optional[Sequence[str]] = None,
    select: Optional[Sequence[str]] = None,
    where=None,
) -> ColumnarFrame:
    """Parquet into a ColumnarFrame via pyarrow.  ``select`` prunes columns
    AT the pyarrow layer (true columnar projection: unselected column
    chunks are never decoded, beyond what ``where`` needs); ``where``
    filters rows before device placement."""
    try:
        import pyarrow.parquet as pq
    except ImportError as e:  # pragma: no cover - environment ships pyarrow
        raise ImportError(
            "read_parquet requires pyarrow; install it or convert the data "
            "to CSV/JSON-lines for the native readers"
        ) from e
    def convert(arr: np.ndarray) -> np.ndarray:
        if arr.dtype == np.float64:
            return arr.astype(np.float32)
        if arr.dtype == np.int64:
            # downcast only when lossless; wide ints become host columns
            # (see _int_column -- silent int32 wraparound corrupts IDs)
            if len(arr) == 0 or (
                arr.min() >= _I32[0] and arr.max() <= _I32[1]
            ):
                return arr.astype(np.int32)
            return np.asarray([int(v) for v in arr], dtype=object)
        if not np.issubdtype(arr.dtype, np.number):
            return arr.astype(object)
        return arr

    want = list(select) if select is not None else (
        list(columns) if columns else None
    )
    schema_names = pq.read_schema(path).names

    def materialize(name: str):
        t = pq.read_table(path, columns=[name])
        return convert(t.column(name).to_numpy(zero_copy_only=False))

    cols: Dict[str, object] = {}
    mask = None
    if where is not None:
        cols, mask = _needed_for_predicate(
            where, materialize, set(schema_names)
        )
    remaining = [c for c in (want or schema_names) if c not in cols]
    if remaining:
        table = pq.read_table(path, columns=remaining)
        for name in table.column_names:
            cols[name] = convert(
                table.column(name).to_numpy(zero_copy_only=False)
            )
    return _apply_pushdown(cols, want, where, mask=mask)


class LazyTable:
    """A registered-but-unread data source: the optimizer pushes projection
    and predicates into ``reader(select=, where=)`` so unneeded columns are
    never parsed and filtered rows never reach the device (the
    datasource-v2 pushdown role, ``Optimizer.scala:38`` data-source rules).
    """

    def __init__(self, name: str, reader, schema: Optional[List[str]] = None):
        self.name = name
        self.reader = reader
        self.schema = schema

    def materialize(self) -> ColumnarFrame:
        """Full read -- the compatibility path for direct ``ctx.table()``
        callers that expect an eager frame."""
        return self.reader(select=None, where=None)


def lazy_csv(name: str, path: Union[str, Path], **kw) -> LazyTable:
    with open(path, newline="") as f:
        first = f.readline().strip()
    schema = (
        first.split(kw.get("delimiter", ",")) if kw.get("header", True)
        else list(kw.get("columns") or [])
    ) or None

    def reader(select=None, where=None):
        return read_csv(path, select=select, where=where, **kw)

    return LazyTable(name, reader, schema)


def lazy_json(name: str, path: Union[str, Path]) -> LazyTable:
    # JSON-lines schema is the union of keys -- unknown without a full
    # scan, so pruning is disabled (predicate pushdown still applies)
    def reader(select=None, where=None):
        return read_json(path, select=select, where=where)

    return LazyTable(name, reader, None)


def lazy_parquet(name: str, path: Union[str, Path]) -> LazyTable:
    try:
        import pyarrow.parquet as pq

        schema = list(pq.read_schema(path).names)
    except Exception:
        schema = None

    def reader(select=None, where=None):
        return read_parquet(path, select=select, where=where)

    return LazyTable(name, reader, schema)


def write_csv(frame: ColumnarFrame, path: Union[str, Path]) -> None:
    """Round-trip writer (tests / interchange)."""
    names = frame.columns
    host = {n: np.asarray(frame[n]) for n in names}
    with open(path, "w", newline="") as f:
        w = _csv.writer(f)
        w.writerow(names)
        for i in range(len(frame)):
            w.writerow([host[n][i] for n in names])
