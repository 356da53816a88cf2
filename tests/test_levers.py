"""The served path has no A/B levers: what the library reads of the
environment is a short list, the documents name no variable the code does
not read, the stripe plan is the constants every measured run uses, and
the chain-forward overlap follows the one thing it observes."""

import glob
import os
import re
import types

import pytest

from tpu3fs.rpc import services
from tpu3fs.rpc.services import RpcMessenger
from tpu3fs.storage import craq

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: every TPU3FS_* name the Python library may hold: the device-codec
#: switch the benchmark's harness sets, the chain-encode EC route (off by
#: default), two debugging aids, and an ABI tag that is no variable
LIBRARY_NAMES = {
    "TPU3FS_STRIPE_DEVICE", "TPU3FS_EC_CHAIN_ENCODE",
    "TPU3FS_DFATAL_ABORT", "TPU3FS_CHAOS_BUG", "TPU3FS_ENGINE_ABI_6",
}
#: every environment variable the library reads
LIBRARY_ENV_READS = (LIBRARY_NAMES - {"TPU3FS_ENGINE_ABI_6"}) | {
    "JAX_COMPILATION_CACHE_DIR"}

_NAME = re.compile(r"TPU3FS_[A-Z_0-9]+")
_ENV_USE = re.compile(r"\bos\.(?:environ|getenv)\b")
_ENV_KEY = re.compile(
    r"""\bos\.(?:getenv\(|environ(?:\.(?:get|pop|setdefault)\(|\[))"""
    r"""\s*["']([A-Za-z_0-9]+)["']""")


def _library_sources():
    for path in glob.glob(os.path.join(REPO, "tpu3fs", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            yield os.path.relpath(path, REPO), f.read()


def test_the_library_holds_no_lever_name():
    found = {}
    for path, src in _library_sources():
        for name in _NAME.findall(src):
            found.setdefault(name, path)
    assert set(found) == LIBRARY_NAMES, {
        n: p for n, p in found.items() if n not in LIBRARY_NAMES}


def test_the_library_reads_only_the_listed_environment():
    keys = {}
    for path, src in _library_sources():
        uses = len(_ENV_USE.findall(src))
        named = _ENV_KEY.findall(src)
        # every use names its key as a literal: no computed name hides one
        assert uses == len(named), (path, uses, named)
        for key in named:
            keys.setdefault(key, path)
    assert set(keys) == LIBRARY_ENV_READS, keys


def test_documents_name_only_variables_the_code_reads():
    read = set(LIBRARY_ENV_READS)
    for path in glob.glob(os.path.join(REPO, "native", "*.cpp")):
        with open(path) as f:
            read |= set(re.findall(r'getenv\("(TPU3FS_[A-Z_0-9]+)"',
                                   f.read()))
    stale = {}
    for path in [os.path.join(REPO, "README.md"),
                 *glob.glob(os.path.join(REPO, "docs", "*.md"))]:
        with open(path) as f:
            for name in _NAME.findall(f.read()):
                if name not in read:
                    stale.setdefault(name, os.path.relpath(path, REPO))
    assert not stale, stale


# -- the stripe plan at the constants the cells run --------------------------

MIB = 1 << 20


def _reads(n, length):
    return [types.SimpleNamespace(length=length, chunk_size=MIB)
            for _ in range(n)]


def _writes(n, length):
    return [types.SimpleNamespace(data=_Sized(length)) for _ in range(n)]


class _Sized:
    """Stands in for a payload: the plan reads only its length."""

    def __init__(self, n):
        self._n = n

    def __len__(self):
        return self._n


def _plan(side, ops, ring):
    m = RpcMessenger.__new__(RpcMessenger)   # the plan reads class constants
    if side == "read":
        return m._stripe_spans(ops)
    spans = m._write_stripe_spans(ops)
    if ring:
        spans = m._cap_spans(spans, services.USRBIO_WRITE_STRIPES)
    return spans


@pytest.mark.parametrize("side,ops,ring,want", [
    # a node group under twice the 4-MiB threshold stays whole
    ("read", _reads(7, MIB), False, [(0, 7)]),
    ("write", _writes(7, MIB), False, [(0, 7)]),
    ("read", _reads(1, 64 * MIB), False, [(0, 1)]),
    # 16 MiB of 1-MiB ops splits in 4
    ("read", _reads(16, MIB), False, [(0, 4), (4, 8), (8, 12), (12, 16)]),
    ("write", _writes(16, MIB), False, [(0, 4), (4, 8), (8, 12), (12, 16)]),
    # a read to the chunk's end counts as the chunk size
    ("read", _reads(16, -1), False, [(0, 4), (4, 8), (8, 12), (12, 16)]),
    # 3 ops of 8 MiB split in 3: never more stripes than ops
    ("read", _reads(3, 8 * MIB), False, [(0, 1), (1, 2), (2, 3)]),
    ("write", _writes(3, 8 * MIB), False, [(0, 1), (1, 2), (2, 3)]),
    # 8 MiB is the least that splits, and in 2
    ("write", _writes(8, MIB), False, [(0, 4), (4, 8)]),
    # a ring write is capped to ONE SQE a node group
    ("write", _writes(16, MIB), True, [(0, 16)]),
    ("write", _writes(3, 8 * MIB), True, [(0, 3)]),
], ids=["read-7MiB-whole", "write-7MiB-whole", "read-one-op-whole",
        "read-16x1MiB-in-4", "write-16x1MiB-in-4", "read-to-end-in-4",
        "read-3x8MiB-in-3", "write-3x8MiB-in-3", "write-8MiB-in-2",
        "ring-write-16x1MiB-one-sqe", "ring-write-3x8MiB-one-sqe"])
def test_stripe_plan_at_the_served_constants(side, ops, ring, want):
    assert (services.READ_STRIPES, services.READ_STRIPE_MIN_BYTES,
            services.WRITE_STRIPES, services.WRITE_STRIPE_MIN_BYTES,
            services.USRBIO_WRITE_STRIPES, services.USRBIO_ENTRIES,
            services.USRBIO_IOV_BYTES) == (
                4, 4 * MIB, 4, 4 * MIB, 1, 128, 64 * MIB)
    assert _plan(side, ops, ring) == want


def test_cap_spans_merges_contiguously():
    spans = [(0, 4), (4, 8), (8, 12), (12, 16)]
    assert RpcMessenger._cap_spans(spans, 4) == spans
    assert RpcMessenger._cap_spans(spans, 2) == [(0, 8), (8, 16)]
    assert RpcMessenger._cap_spans(spans, 3) == [(0, 4), (4, 8), (8, 16)]


@pytest.mark.parametrize("cpus,want", [(1, False), (None, False),
                                       (2, True), (13, True)])
def test_forward_overlap_follows_the_cpu_count(monkeypatch, cpus, want):
    monkeypatch.setattr(os, "cpu_count", lambda: cpus)
    assert craq._overlap_enabled() is want
    assert craq._OVERLAP_MIN_BYTES == 32 << 10
