"""Native C++ CSV loader: parity with pandas, fallback gating."""
import numpy as np
import pandas as pd
import pytest

from gymfx_tpu.data.feed import load_dataframe
from gymfx_tpu.data.native_loader import (
    _header_is_canonical,
    load_ohlcv_csv,
    native_enabled,
)

SAMPLE = "examples/data/eurusd_sample.csv"


def test_native_lib_builds_and_parses_sample():
    df = load_ohlcv_csv(SAMPLE)
    if df is None:
        pytest.skip("native loader unavailable in this environment")
    ref = pd.read_csv(SAMPLE)
    assert len(df) == len(ref)
    np.testing.assert_allclose(df["CLOSE"].to_numpy(), ref["CLOSE"].to_numpy())
    np.testing.assert_allclose(df["VOLUME"].to_numpy(), ref["VOLUME"].to_numpy())
    # timestamps parse identically
    ref_ts = pd.to_datetime(ref["DATE_TIME"])
    np.testing.assert_array_equal(df.index.to_numpy(), ref_ts.to_numpy())


def test_native_and_pandas_paths_agree_through_load_dataframe(monkeypatch):
    native = load_dataframe({"input_data_file": SAMPLE})
    monkeypatch.setenv("GYMFX_NATIVE_LOADER", "0")
    pandas_df = load_dataframe({"input_data_file": SAMPLE})
    assert list(native.columns) == list(pandas_df.columns)
    np.testing.assert_allclose(
        native["CLOSE"].to_numpy(), pandas_df["CLOSE"].to_numpy()
    )
    np.testing.assert_array_equal(
        native.index.to_numpy(), pandas_df.index.to_numpy()
    )


def test_non_canonical_headers_fall_back(tmp_path):
    p = tmp_path / "extra.csv"
    pd.DataFrame(
        {
            "DATE_TIME": pd.date_range("2024-01-01", periods=40, freq="1min"),
            "CLOSE": np.linspace(1.0, 1.1, 40),
            "my_feature": np.arange(40.0),
        }
    ).to_csv(p, index=False)
    assert not _header_is_canonical(str(p))
    assert load_ohlcv_csv(str(p)) is None
    df = load_dataframe({"input_data_file": str(p)})
    assert "my_feature" in df.columns  # pandas path preserved the column


def test_garbage_rows_refuse_native(tmp_path):
    p = tmp_path / "bad.csv"
    p.write_text(
        "DATE_TIME,OPEN,HIGH,LOW,CLOSE,VOLUME\n"
        "2024-01-01 00:00:00,1,1,1,1,0\n"
        "not-a-date,1,1,1,1,0\n"
    )
    assert load_ohlcv_csv(str(p)) is None  # strict parser refuses


def test_max_rows_applies_on_native_path():
    if load_ohlcv_csv(SAMPLE) is None:
        pytest.skip("native loader unavailable")
    df = load_dataframe({"input_data_file": SAMPLE, "max_rows": 17})
    assert len(df) == 17


def test_trailing_garbage_in_numbers_refused(tmp_path):
    p = tmp_path / "junk.csv"
    p.write_text(
        "DATE_TIME,OPEN,HIGH,LOW,CLOSE,VOLUME\n"
        "2024-01-01 00:00:00,1.1,1.2,1.0,1.5garbage,10\n"
    )
    assert load_ohlcv_csv(str(p)) is None


def test_timezone_suffix_timestamps_refused(tmp_path):
    p = tmp_path / "tz.csv"
    p.write_text(
        "DATE_TIME,OPEN,HIGH,LOW,CLOSE,VOLUME\n"
        "2024-01-01 00:00:00+02:00,1.1,1.2,1.0,1.1,10\n"
    )
    assert load_ohlcv_csv(str(p)) is None


def test_partial_schema_uses_pandas_backfill(tmp_path):
    # DATE_TIME+CLOSE only: must take the pandas path so price_column
    # semantics apply (native would synthesize OHLC silently)
    p = tmp_path / "partial.csv"
    p.write_text(
        "DATE_TIME,CLOSE\n2024-01-01 00:00:00,1.5\n2024-01-01 00:01:00,1.6\n"
    )
    assert load_ohlcv_csv(str(p)) is None
    df = load_dataframe({"input_data_file": str(p)})
    np.testing.assert_allclose(df["OPEN"], df["CLOSE"])


def test_native_library_is_named_after_its_source_not_its_mtime(tmp_path):
    """The library a loader picks up is the one built from the
    csv_loader.cpp on disk: its name carries the source hash, so a
    ``.so`` carried over from another source (``*.so`` is git-ignored
    and travels with copied trees) is never preferred, however new its
    mtime."""
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "build_native",
        pathlib.Path(__file__).resolve().parents[1] / "tools" / "build_native.py",
    )
    bn = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bn)
    if load_ohlcv_csv(SAMPLE) is None:
        pytest.skip("native loader unavailable in this environment")
    current = bn.library_path()
    assert current.exists() and current.name.startswith("libgymfx_csv.")
    # a library "of another source", newer than everything else
    stale = bn.NATIVE / "libgymfx_csv.0000000000000000.so"
    stale.write_bytes(b"not a library")
    try:
        assert bn.build() == current          # the stale one is not chosen
        assert bn.library_path() == current
        # the hash follows the source text
        other = tmp_path / "csv_loader.cpp"
        other.write_text(bn.SOURCE.read_text() + "\n// changed\n")
        real, bn.SOURCE = bn.SOURCE, other
        try:
            assert bn.library_path().name != current.name
        finally:
            bn.SOURCE = real
    finally:
        stale.unlink(missing_ok=True)


def test_served_by_names_the_loader(monkeypatch):
    from gymfx_tpu.data import native_loader

    if load_ohlcv_csv(SAMPLE) is None:
        pytest.skip("native loader unavailable in this environment")
    assert native_loader.served_by(SAMPLE) == "native"
    monkeypatch.setenv("GYMFX_NATIVE_LOADER", "0")
    assert load_ohlcv_csv(SAMPLE) is None
    assert native_loader.served_by(SAMPLE).startswith("pandas")
