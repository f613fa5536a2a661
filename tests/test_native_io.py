"""The C++ io core is ACTIVE and agrees with the Python reader.

Reference parity: ``src/io/`` is native in the reference; here the
native layer is the mmap recordio scanner + GIL-free prefetch ring
(``mxnet_tpu/_native/io_core.cpp``).  These tests pin that the library
builds/loads in this environment (no silent pure-Python fallback) and
that both paths return identical bytes.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio


@pytest.fixture()
def pack(tmp_path):
    rec = str(tmp_path / "x.rec")
    idx = str(tmp_path / "x.idx")
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    rs = onp.random.RandomState(0)
    payloads = []
    for i in range(20):
        b = rs.bytes(rs.randint(10, 2000))
        payloads.append(b)
        w.write_idx(i, b)
    w.close()
    return rec, payloads


def test_native_lib_builds_and_loads():
    from mxnet_tpu import _native
    assert _native.get_lib() is not None, \
        "native io core failed to build/load — dataset reads silently " \
        "fell back to pure Python"


def test_native_record_file_matches_python_reader(pack):
    rec, payloads = pack
    from mxnet_tpu._native import NativeRecordFile
    nf = NativeRecordFile(rec)
    assert len(nf) == len(payloads)
    for i, expect in enumerate(payloads):
        assert bytes(nf.read(i)) == expect
    nf.close()
    # python-side reader agrees
    r = recordio.MXRecordIO(rec, "r")
    for expect in payloads:
        assert r.read() == expect


def test_native_prefetcher_order_and_contents(pack):
    rec, payloads = pack
    from mxnet_tpu._native import NativePrefetcher, NativeRecordFile
    nf = NativeRecordFile(rec)
    order = [7, 0, 19, 3, 3, 11]
    got = [bytes(b) for b in NativePrefetcher(nf, order, num_threads=2,
                                              depth=4)]
    assert got == [payloads[i] for i in order]
    nf.close()


def test_record_dataset_uses_native(pack):
    rec, payloads = pack
    from mxnet_tpu.gluon.data.dataset import RecordFileDataset
    ds = RecordFileDataset(rec)
    assert getattr(ds, "_native", None) is not None, \
        "RecordFileDataset did not take the native path"
    assert len(ds) == len(payloads)
    assert bytes(ds[5]) == payloads[5]


def test_native_lib_staleness_is_judged_by_content(tmp_path, monkeypatch):
    """A copied tree has arbitrary mtimes: the library is rebuilt when
    io_core.cpp's digest is not the one compiled into it, and only
    then."""
    import os
    import shutil

    from mxnet_tpu import _native
    src = str(tmp_path / "io_core.cpp")
    out = str(tmp_path / "libmxtpu_io.local.so")
    shutil.copy(_native._SRC, src)
    monkeypatch.setattr(_native, "_SRC", src)
    monkeypatch.setattr(_native, "_OUT", out)
    builds = []
    real_build = _native._build
    monkeypatch.setattr(_native, "_build",
                        lambda *a: (builds.append(a), real_build(*a)))
    _native._load()
    assert len(builds) == 1
    os.utime(out, (1, 1))           # library looks ancient: still fresh
    _native._load()
    assert len(builds) == 1
    with open(src, "a") as f:       # source changed: rebuilt
        f.write("\n// edited\n")
    os.utime(src, (1, 1))           # ... though it looks older than ever
    assert _native._load().mxtpu_version() == 1
    assert len(builds) == 2
