"""``indexcov``'s one-pass index load (io/bai.py ``read_tile_sizes`` over
the native ``bai_tile_sizes``) against the path it stands in for:
``read_bai(...).sizes()`` and ``ops.median_size_per_tile``, which stay as
the fallback of a ``.crai``, a URL and a build without the library. Sizes,
totals and the median have to be theirs to the bit."""

import concurrent.futures as cf
import gzip
import os
import struct
import sys

import numpy as np
import pytest

from goleft_tpu import obs
from goleft_tpu.commands.indexcov import SampleIndex
from goleft_tpu.io import native
from goleft_tpu.io.bai import (
    BaiIndex, RefIndex, read_bai, read_tile_sizes, write_bai)
from goleft_tpu.ops import indexcov_ops as ops

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from makers import bai_cohort  # noqa: E402

pytestmark = pytest.mark.native_io

LOADS = "indexcov.index_native_loads_total"
GROWS = "indexcov.index_buffer_grows_total"


@pytest.fixture(autouse=True)
def _the_library():
    if native.get_lib() is None:
        pytest.skip("native lib unavailable")


def counter(name: str) -> int:
    return obs.get_registry().counters().get(name, 0)


def python_path(data: bytes):
    """(sizes, mapped, unmapped, nbytes, median) as ``SampleIndex`` read
    them before the pass, and reads them still without the library."""
    idx = read_bai(data)
    sizes = idx.sizes()
    return (sizes, idx.mapped_total, idx.unmapped_total, len(data),
            ops.median_size_per_tile(sizes))


def assert_same_as_python(path: str) -> None:
    with open(path, "rb") as fh:
        sizes, mapped, unmapped, nbytes, median = python_path(fh.read())
    loads = counter(LOADS)
    got = SampleIndex(path)
    assert counter(LOADS) == loads + 1
    assert len(got.sizes) == len(sizes)
    for mine, theirs in zip(got.sizes, sizes):
        assert mine.dtype == theirs.dtype == np.int64
        assert mine.shape == theirs.shape and np.array_equal(mine, theirs)
    assert (got.mapped, got.unmapped, got.nbytes) == (mapped, unmapped,
                                                      nbytes)
    assert isinstance(got.median, float) and got.median == median


def offsets(rng, n: int) -> np.ndarray:
    """n linear-index offsets that never fall, with runs that stay."""
    steps = rng.integers(0, 1 << 34, size=n) * (rng.random(n) < 0.8)
    return (np.uint64(1 << 16)
            + np.cumsum(steps).astype(np.uint64)).astype(np.uint64)


def written_index(rng, no_coor: int) -> BaiIndex:
    """Through ``write_bai``: a reference with no interval and one with
    one (neither has a tile), one without the pseudo-bin, bins of several
    chunks."""
    chunks = [(1 << 16, 2 << 16), (3 << 16, 5 << 16), (5 << 16, 9 << 16)]
    return BaiIndex([
        RefIndex({4681: chunks, 4682: chunks[:1]}, offsets(rng, 300), 7, 2),
        RefIndex({}, np.zeros(0, np.uint64), 3, 1),
        RefIndex({0: chunks[:2]}, offsets(rng, 1), -1, -1),
        RefIndex({585: chunks}, offsets(rng, 41), 1 << 40, 0),
        RefIndex({}, offsets(rng, 2), -1, -1),
    ], no_coor)


@pytest.mark.parametrize("no_coor", [0, 12345])
def test_an_index_of_write_bai_reads_as_the_python_path(tmp_path, no_coor):
    path = str(tmp_path / "w.bai")
    write_bai(written_index(np.random.default_rng(no_coor), no_coor), path)
    assert_same_as_python(path)


@pytest.mark.parametrize("trailing", [True, False])
@pytest.mark.parametrize("bin_table", [True, False])
def test_an_index_of_the_cohort_maker_reads_as_the_python_path(
        tmp_path, bin_table, trailing):
    rng = np.random.default_rng(11)
    sizes = [rng.integers(0, 1 << 36, size=n) * (rng.random(n) < 0.9)
             for n in (2300, 0, 120, 1, 40)]
    data = bai_cohort.bai_bytes(sizes, [9, 0, 5, 1, 2], [1, 0, 0, 0, 3],
                                bin_table)
    if not trailing:  # the maker ends on n_no_coor; the field is optional
        data = data[:-8]
    path = str(tmp_path / "m.bai")
    with open(path, "wb") as fh:
        fh.write(data)
    assert_same_as_python(path)


def one_reference(sizes: np.ndarray) -> bytes:
    """A .bai of one reference, no bin, whose tiles have ``sizes``."""
    iv = np.concatenate([[0], np.cumsum(sizes)]).astype("<u8")
    return (b"BAI\x01" + struct.pack("<ii", 1, 0)
            + struct.pack("<i", len(iv)) + iv.tobytes())


def drawn(kind: str, rng) -> np.ndarray:
    n = int(rng.integers(2, 5000))
    if kind == "ties":
        return rng.integers(0, 50, size=n)
    if kind == "zeros":
        return rng.integers(0, 1 << 30, size=n) * (rng.random(n) < 0.3)
    if kind == "all_zero":
        return np.zeros(n, np.int64)
    if kind == "all_equal":
        return np.full(n, int(rng.integers(1, 1 << 35)))
    if kind == "length_1":
        return rng.integers(0, 1 << 35, size=1)
    if kind == "near_2_40":
        return (1 << 40) + rng.integers(-1000, 1000, size=n)
    if kind == "one_deep_tile":  # chrM: what the cap at rank 0.98 n is for
        return np.append(rng.integers(1 << 20, 1 << 21, size=n), 1 << 44)
    if kind == "heavy_tail":
        return np.rint(np.exp(rng.normal(20, 3, size=n))).astype(np.int64)
    return rng.integers(0, 1 << 42, size=n)  # "uniform"


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("kind", [
    "ties", "zeros", "all_zero", "all_equal", "length_1", "near_2_40",
    "one_deep_tile", "heavy_tail", "uniform"])
def test_the_median_is_median_size_per_tile_to_the_bit(tmp_path, kind, seed):
    sizes = drawn(kind, np.random.default_rng([seed, len(kind)])).astype(
        np.int64)
    path = str(tmp_path / "one.bai")
    with open(path, "wb") as fh:
        fh.write(one_reference(sizes))
    got = read_tile_sizes(path)
    assert np.array_equal(got.sizes[0], sizes)
    assert got.median == ops.median_size_per_tile([sizes])


def test_an_index_with_no_tile_is_refused_in_the_old_words(tmp_path):
    path = str(tmp_path / "none.bai")
    with open(path, "wb") as fh:
        fh.write(one_reference(np.zeros(0, np.int64)))  # one interval
    assert read_tile_sizes(path).median is None
    with pytest.raises(ValueError, match="^indexcov: no usable chromosomes "
                                         "in index$"):
        SampleIndex(path)


def test_offsets_that_fall_are_refused_in_the_old_words(tmp_path):
    iv = np.array([1 << 20, 2 << 20, (2 << 20) - 1, 3 << 20], "<u8")
    data = (b"BAI\x01" + struct.pack("<ii", 1, 0)
            + struct.pack("<i", len(iv)) + iv.tobytes())
    with pytest.raises(ValueError) as old:
        python_path(data)
    path = str(tmp_path / "falls.bai")
    with open(path, "wb") as fh:
        fh.write(data)
    with pytest.raises(ValueError) as new:
        SampleIndex(path)
    assert str(new.value) == str(old.value) == (
        "bai: negative voffset delta in linear index")


def test_eight_threads_read_what_one_reads_on_buffers_they_keep(tmp_path):
    """32 indexes of three sizes, three times each and twice over. A
    pair of buffers is made only where no idle one is left or an index
    is larger than the one taken: at most 8 pairs, each at most three
    times. One thread over one index again makes none."""
    rng = np.random.default_rng(3)
    paths = []
    for i in range(32):
        tiles = (400, 3000, 9000)[i % 3]
        sizes = [rng.integers(0, 1 << 34, size=n)
                 for n in (tiles, tiles // 7, 0, 5)]
        paths.append(str(tmp_path / f"s{i}.bam.bai"))
        with open(paths[-1], "wb") as fh:
            fh.write(bai_cohort.bai_bytes(sizes, [i, 1, 0, 2], [0, 0, 0, 1],
                                          True))
    alone = [SampleIndex(p) for p in paths]
    grows, loads = counter(GROWS), counter(LOADS)
    for _ in range(2):
        with cf.ThreadPoolExecutor(max_workers=8) as ex:
            pooled = list(ex.map(SampleIndex, paths * 3))
        for got, want in zip(pooled, alone * 3):
            assert got.median == want.median and got.nbytes == want.nbytes
            assert (got.mapped, got.unmapped) == (want.mapped, want.unmapped)
            assert all(np.array_equal(a, b)
                       for a, b in zip(got.sizes, want.sizes))
    assert counter(GROWS) - grows <= 2 * 8 * 3
    assert counter(LOADS) - loads == 2 * 96
    SampleIndex(paths[2])
    grows = counter(GROWS)
    SampleIndex(paths[2])  # takes the pair the last load gave back
    assert counter(GROWS) == grows


def crai_path(tmp_path) -> str:
    path = str(tmp_path / "s.cram.crai")
    with open(path, "wb") as fh:
        fh.write(gzip.compress(b"".join(
            b"0\t%d\t16384\t%d\t0\t500\n" % (1 + 16384 * i, 1000 * i)
            for i in range(9))))
    return path


def test_a_crai_takes_the_old_code(tmp_path):
    loads = counter(LOADS)
    assert SampleIndex(crai_path(tmp_path)).median > 0
    assert counter(LOADS) == loads


def test_a_build_without_the_library_takes_the_old_code(tmp_path,
                                                        monkeypatch):
    path = str(tmp_path / "w.bai")
    write_bai(written_index(np.random.default_rng(5), 0), path)
    with_library = SampleIndex(path)
    monkeypatch.setattr(native, "get_lib", lambda: None)
    loads = counter(LOADS)
    assert read_tile_sizes(path) is None
    got = SampleIndex(path)
    assert counter(LOADS) == loads
    assert got.median == with_library.median
    assert (got.mapped, got.unmapped, got.nbytes) == (
        with_library.mapped, with_library.unmapped, with_library.nbytes)
    assert all(np.array_equal(a, b)
               for a, b in zip(got.sizes, with_library.sizes))


def test_a_remote_index_takes_the_old_code(tmp_path):
    from goleft_tpu.io import remote
    from goleft_tpu.io.remote_stub import StubServer

    path = str(tmp_path / "w.bai")
    write_bai(written_index(np.random.default_rng(6), 0), path)
    local = SampleIndex(path)
    remote.invalidate_identity()
    try:
        with StubServer() as srv, open(path, "rb") as fh:
            url = srv.put("w.bai", fh.read())
            loads = counter(LOADS)
            got = SampleIndex(url)
            assert counter(LOADS) == loads
    finally:
        remote.invalidate_identity()
        remote._POOL.clear()
    assert got.median == local.median and got.nbytes == local.nbytes
    assert all(np.array_equal(a, b)
               for a, b in zip(got.sizes, local.sizes))
