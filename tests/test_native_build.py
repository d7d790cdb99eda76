"""Native build under concurrent first use, and with a source that
cannot compile.

A fresh checkout has no `native/_build/` (git-ignored), and the first
users of the libraries arrive together: the test workers of one run,
the service hosts of one wire cluster. Every one of them must end up
with a working library, and a toolchain that is present but fails must
be an error, never a quiet drop to the pure-Python encoder.
"""
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from cadence_tpu.native import build as native_build

pytestmark = pytest.mark.skipif(shutil.which("g++") is None,
                                reason="g++ not installed")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: what each racing process runs: point the build at an EMPTY directory
#: (never the shared one other tests load from), load all three
#: libraries, and call into each so a torn file would fault here
_CHILD = textwrap.dedent("""
    import os, sys, time
    import numpy as np
    from cadence_tpu.native import build
    build._BUILD_DIR = sys.argv[1]
    while not os.path.exists(sys.argv[2]):   # start line: race for real
        time.sleep(0.005)
    packer, wirec, gen = build.load(), build.load_wirec(), build.load_generator()
    assert packer is not None and wirec is not None and gen is not None
    from cadence_tpu.native import gen_native, packing, wirec as nwirec
    from cadence_tpu.gen.corpus import generate_corpus
    from cadence_tpu.core import codec
    lanes, real = gen_native.generate_corpus_native(7, 0, 4, 16)
    assert lanes.shape[:2] == (4, 16) and real > 0
    hist = generate_corpus("basic", 3, seed=1, target_events=20)
    blobs = [codec.serialize_history(h) for h in hist]
    ev = packing.pack_serialized(blobs, 32)
    corpus = nwirec.pack_wirec_native(ev)
    assert corpus.slab.shape[0] == 3
    print("LOADED", os.getpid())
""")


def _expected_sos():
    """The three file names a complete build leaves behind."""
    b = native_build
    return sorted(os.path.basename(p) for p in (
        b._so_path(b._SRC, "cadence_packer"),
        b._so_path(b._SRC_WIREC, "cadence_wirec", deps=(b._SRC,)),
        b._so_path(b._SRC_GEN, "cadence_generator")))


def test_concurrent_first_use_builds_once_per_library(tmp_path):
    build_dir = tmp_path / "_build"
    start = tmp_path / "go"
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _CHILD, str(build_dir), str(start)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for _ in range(6)]
    start.write_text("go")
    try:
        outs = [p.communicate(timeout=300) for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, err[-2000:]
        assert "LOADED" in out
    assert sorted(os.listdir(build_dir)) == _expected_sos()


@pytest.fixture
def scratch_build(tmp_path, monkeypatch):
    """The module's build state pointed at a scratch directory, with
    nothing memoised, and put back afterwards."""
    monkeypatch.setattr(native_build, "_BUILD_DIR", str(tmp_path / "_build"))
    monkeypatch.setattr(native_build, "_cached", {})
    return tmp_path


def test_broken_source_with_toolchain_raises(scratch_build, monkeypatch):
    broken = scratch_build / "generator.cc"
    broken.write_text("this is not C++ {\n")
    monkeypatch.setattr(native_build, "_SRC_GEN", str(broken))
    with pytest.raises(RuntimeError, match="g\\+\\+ failed"):
        native_build.load_generator()
    # not memoised as "unavailable": the next call tries (and fails) again
    with pytest.raises(RuntimeError, match="g\\+\\+ failed"):
        native_build.load_generator()
    assert os.listdir(native_build._BUILD_DIR) == []


def test_no_toolchain_is_the_only_none(scratch_build, monkeypatch):
    monkeypatch.setattr(native_build.shutil, "which", lambda name: None)
    assert native_build.load_generator() is None
    assert not os.path.exists(native_build._BUILD_DIR)


def test_unloadable_library_raises(scratch_build, monkeypatch):
    """A file of the right name that is not a shared object (a torn
    write the old shared temporary name allowed) is a load error."""
    so = native_build._so_path(native_build._SRC_GEN, "cadence_generator")
    os.makedirs(os.path.dirname(so))
    with open(so, "wb") as f:
        f.write(b"not an ELF file")
    with pytest.raises(OSError):
        native_build.load_generator()
