"""Checkpoint manifest: pytree structure + shard map + atomic-commit paths.

The manifest is the checkpoint's single source of truth (the analogue of
the reference positioning 3FS as the checkpoint target, README.md:14): a
serde-encoded record of the pytree skeleton, one ``LeafSpec`` per array
leaf (dtype, global shape, the mesh axes it was sharded over), and one
``ShardSpec`` per DISTINCT saved shard — its global index box, the data
file holding its row-major bytes, and a CRC32C over those bytes.

Commit protocol: a save writes everything under ``<root>/<step>.tmp/``
(data files first, ``MANIFEST`` last) and becomes visible only through a
single meta ``rename`` to ``<root>/<step>/``. Readers therefore never
observe a partial checkpoint: either the step directory exists with a
complete manifest, or it does not exist at all. A crashed save is just a
``.tmp`` directory the retention GC sweeps.

The pytree skeleton is stored as a JSON string whose leaves are integer
indices into ``leaves`` — dict/list/tuple nodes round-trip exactly, so a
restore rebuilds the pytree the training loop handed to save().
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from tpu3fs.rpc.serde import deserialize, serialize
from tpu3fs.utils.result import Code
from tpu3fs.utils.result import err as _err

MANIFEST_NAME = "MANIFEST"
TMP_SUFFIX = ".tmp"
ARC_SUFFIX = ".arc"
FORMAT_VERSION = 1


def dtype_tag(dtype) -> str:
    """The manifest's name for a dtype: numpy's ``.str`` ("<f4"), except
    for the extension types a chip-resident tree is made of (bfloat16, the
    float8s), whose ``.str`` is an opaque "<V2" — those go by name."""
    dt = np.dtype(dtype)
    return dt.name if dt.kind == "V" else dt.str


def parse_dtype(tag: str) -> np.dtype:
    try:
        return np.dtype(tag)
    except TypeError:
        import ml_dtypes  # noqa: F401  (registers the extension names)

        return np.dtype(tag)


@dataclass
class LeafSpec:
    """One pytree array leaf."""

    key: str                 # "/"-joined keypath (diagnostics; tree is
    #                          authoritative for structure)
    dtype: str               # dtype_tag(), e.g. "<f4" or "bfloat16"
    shape: List[int] = field(default_factory=list)   # global shape
    # mesh axis name per dim ("" = unsharded dim) as saved — informational
    # for inspect; restore computes overlap boxes from ShardSpec directly
    spec: List[str] = field(default_factory=list)


@dataclass
class ShardSpec:
    """One distinct saved shard: a global index box -> one data file."""

    leaf: int                                       # index into leaves
    offset: List[int] = field(default_factory=list)  # global origin per dim
    shape: List[int] = field(default_factory=list)   # box extent per dim
    file: str = ""            # data file name inside the step dir
    length: int = 0           # byte length (= prod(shape) * itemsize)
    crc: int = 0              # crc32c over the shard's row-major bytes


@dataclass
class Manifest:
    format_version: int = FORMAT_VERSION
    step: int = 0
    created: float = 0.0
    # saving mesh (axis name -> size), informational
    mesh: Dict[str, int] = field(default_factory=dict)
    tree: str = ""            # JSON skeleton, leaves are indices
    leaves: List[LeafSpec] = field(default_factory=list)
    shards: List[ShardSpec] = field(default_factory=list)

    def encode(self) -> bytes:
        return serialize(self, Manifest)

    @staticmethod
    def decode(raw: bytes) -> "Manifest":
        try:
            m = deserialize(raw, Manifest)
        except Exception as e:
            raise _err(Code.CKPT_CORRUPT, f"manifest decode: {e!r}")
        if m.format_version > FORMAT_VERSION:
            raise _err(Code.CKPT_CORRUPT,
                       f"manifest format {m.format_version} > {FORMAT_VERSION}")
        return m

    def shards_of_leaf(self, leaf_idx: int) -> List[ShardSpec]:
        return [s for s in self.shards if s.leaf == leaf_idx]

    def total_bytes(self) -> int:
        return sum(s.length for s in self.shards)


# -- step-directory naming ---------------------------------------------------

def step_dir(root: str, step: int) -> str:
    return f"{root}/{step}"


def tmp_dir(root: str, step: int) -> str:
    return f"{root}/{step}{TMP_SUFFIX}"


def arc_dir(root: str, step: int) -> str:
    return f"{root}/{step}{ARC_SUFFIX}"


def parse_step(name: str) -> Optional[int]:
    """Committed step-directory name -> step number; None for anything
    else (``.tmp``/``.arc`` staging dirs, foreign files)."""
    if not name.isdigit():
        return None
    return int(name)


def parse_staging(name: str) -> Optional[Tuple[int, str]]:
    """``<step>.tmp`` / ``<step>.arc`` -> (step, suffix); else None."""
    for suffix in (TMP_SUFFIX, ARC_SUFFIX):
        if name.endswith(suffix) and name[: -len(suffix)].isdigit():
            return int(name[: -len(suffix)]), suffix
    return None


def shard_file_name(leaf_idx: int, shard_idx: int) -> str:
    return f"l{leaf_idx}.s{shard_idx}"


# -- pytree skeleton <-> JSON ------------------------------------------------
#
# Only dict / list / tuple containers are treated as structure; anything
# else is a leaf. Dict keys must be strings (JSON round-trip exactness);
# insertion order is preserved, so the rebuilt tree is identical.

def flatten_tree(tree) -> Tuple[str, List[object]]:
    """-> (JSON skeleton, leaves in skeleton order)."""
    leaves: List[object] = []

    def walk(node):
        if isinstance(node, dict):
            for k in node:
                if not isinstance(k, str):
                    raise _err(Code.INVALID_ARG,
                               f"checkpoint dict keys must be str, got {k!r}")
            return {"t": "d", "k": list(node.keys()),
                    "v": [walk(v) for v in node.values()]}
        if isinstance(node, (list, tuple)):
            return {"t": "l" if isinstance(node, list) else "u",
                    "v": [walk(v) for v in node]}
        leaves.append(node)
        return {"t": "x", "i": len(leaves) - 1}

    return json.dumps(walk(tree)), leaves


def unflatten_tree(skeleton: str, leaves: List[object]):
    """Rebuild the pytree from its JSON skeleton + leaf values."""
    def walk(node):
        t = node["t"]
        if t == "d":
            return {k: walk(v) for k, v in zip(node["k"], node["v"])}
        if t == "l":
            return [walk(v) for v in node["v"]]
        if t == "u":
            return tuple(walk(v) for v in node["v"])
        return leaves[node["i"]]

    return walk(json.loads(skeleton))


def leaf_keypaths(skeleton: str) -> List[str]:
    """Human-readable "/"-joined keypath per leaf, in leaf order."""
    out: List[str] = []

    def walk(node, path):
        t = node["t"]
        if t == "d":
            for k, v in zip(node["k"], node["v"]):
                walk(v, path + [k])
        elif t in ("l", "u"):
            for i, v in enumerate(node["v"]):
                walk(v, path + [str(i)])
        else:
            out.append("/".join(path))

    walk(json.loads(skeleton), [])
    return out


# -- resharding math ---------------------------------------------------------

def overlap_box(src_off, src_shape, dst_off, dst_shape
                ) -> Optional[Tuple[List[int], List[int]]]:
    """Intersection of two global index boxes -> (origin, shape) or None."""
    lo, shape = [], []
    for so, ss, do, ds in zip(src_off, src_shape, dst_off, dst_shape):
        a = max(so, do)
        b = min(so + ss, do + ds)
        if b <= a:
            return None
        lo.append(a)
        shape.append(b - a)
    return lo, shape


def contiguous_runs(box_off: List[int], box_shape: List[int],
                    src_off: List[int], src_shape: List[int],
                    itemsize: int) -> List[Tuple[int, int]]:
    """Byte ranges of a global box inside a row-major saved shard.

    The box (``box_off``/``box_shape``, global coordinates) must lie
    within the source shard (``src_off``/``src_shape``). Returns
    ``[(byte_offset_in_shard, byte_length)]`` runs, emitted in C order of
    the box — so concatenating the fetched runs yields exactly the box's
    row-major bytes. Trailing dims where the box spans the full source
    extent fold into each run (one run per remaining outer index), which
    is what makes same-sharding restores single-run per shard.
    """
    nd = len(src_shape)
    if nd == 0:
        return [(0, itemsize)]
    # source strides in elements
    strides = [1] * nd
    for d in range(nd - 2, -1, -1):
        strides[d] = strides[d + 1] * src_shape[d + 1]
    rel = [box_off[d] - src_off[d] for d in range(nd)]
    # j = first dim (from the left) such that dims j..nd-1 are full-source
    j = nd
    while j > 0 and box_shape[j - 1] == src_shape[j - 1]:
        j -= 1
    # the run covers dims j-1..nd-1 (partial dim j-1 + full trailing);
    # j == 0 means the whole box is one contiguous run
    run_dim = max(0, j - 1)
    run_elems = 1
    for d in range(run_dim, nd):
        run_elems *= box_shape[d]
    outer = box_shape[:run_dim]
    runs: List[Tuple[int, int]] = []

    def emit(idx: List[int]) -> None:
        off = 0
        for d in range(nd):
            off += (rel[d] + (idx[d] if d < run_dim else 0)) * strides[d]
        runs.append((off * itemsize, run_elems * itemsize))

    idx = [0] * run_dim
    while True:
        emit(idx)
        d = run_dim - 1
        while d >= 0:
            idx[d] += 1
            if idx[d] < outer[d]:
                break
            idx[d] = 0
            d -= 1
        if d < 0:
            break
    return runs
