"""Content-addressed prefix-block store: prompt-prefix KV dedup.

Decoder KV tensors are cached as fixed-size TOKEN-BLOCK pages keyed by a
rolling prefix-hash chain:

    key[0] = H(root, tokens[0:B])
    key[i] = H(key[i-1], tokens[i*B:(i+1)*B])

A block's key therefore commits to the ENTIRE token prefix up to and
including it — two requests sharing a prompt prefix derive the same chain
of keys and dedupe to the same fs entries (vLLM-style prefix caching, but
the page table is the filesystem namespace: nothing to synchronize
between inference processes). Divergent suffixes fork the chain at the
first differing block; partial trailing blocks are never stored (their
tokens recompute in one step's prefill).

Because keys are content-addressed, entries are IMMUTABLE: the host tier
(tier.py) can cache them forever without staleness, a double store is
idempotent, and ``match_prefix`` is pure presence-probing — one batched
stat for the whole chain, then the longest present prefix.

``get_blocks`` returns device-ready arrays: the fs bytes decode as
zero-copy views (layout.decode_array) and ``device=`` hands each block to
``jax.device_put`` so a serving loop can feed attention kernels directly.
"""

from __future__ import annotations

import hashlib
import struct
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

from tpu3fs.analytics import spans as _spans
from tpu3fs.kvcache.layout import decode_array, encode_array
from tpu3fs.monitor.recorder import CounterRecorder
from tpu3fs.utils.result import Code, FsError
from tpu3fs.utils.result import err as _err

_TOKEN = struct.Struct("<q")
_ROOT = b"tpu3fs-kvblock-v1"


def _digest(parent: bytes, token_ids: Sequence[int]) -> bytes:
    h = hashlib.blake2b(parent, digest_size=16)
    for t in token_ids:
        h.update(_TOKEN.pack(t))
    return h.digest()


def chain_keys(token_ids: Sequence[int], block_tokens: int,
               *, salt: bytes = b"") -> List[str]:
    """Keys of every FULL block of the sequence, in chain order. The
    trailing ``len % block_tokens`` tokens have no key (never stored)."""
    if block_tokens <= 0:
        raise _err(Code.INVALID_ARG, f"block_tokens {block_tokens}")
    parent = _ROOT + salt
    keys: List[str] = []
    for lo in range(0, len(token_ids) - block_tokens + 1, block_tokens):
        parent = _digest(parent, token_ids[lo:lo + block_tokens])
        keys.append(parent.hex())
    return keys


@dataclass
class PrefixMatch:
    """Longest stored prefix of a token sequence."""

    tokens: int = 0                       # matched token count (blocks*B)
    blocks: int = 0                       # matched full blocks
    keys: List[str] = field(default_factory=list)   # their chain keys


class PrefixBlockStore:
    """Prefix-hash-chained KV block pages over any cache with the
    get/put/batch surface (``KVCacheClient`` or ``TieredKVCache``)."""

    def __init__(self, cache, *, block_tokens: int = 16,
                 salt: bytes = b"", leases=None):
        if block_tokens <= 0:
            raise _err(Code.INVALID_ARG, f"block_tokens {block_tokens}")
        self._cache = cache
        self.block_tokens = block_tokens
        self._salt = salt
        self._leases = leases
        self._stale_reads = CounterRecorder("kvcache.stale_reads")

    @property
    def cache(self):
        return self._cache

    def block_keys(self, token_ids: Sequence[int]) -> List[str]:
        return chain_keys(token_ids, self.block_tokens, salt=self._salt)

    # -- lookup -------------------------------------------------------------
    def match_prefix(self, token_ids: Sequence[int]) -> PrefixMatch:
        """Longest-prefix lookup: ONE batched presence probe over the
        whole chain (over RPC one batchStatByPath round trip a meta
        partition), then the longest run of present blocks from the
        start. (A mid-chain hole ends the match — later blocks' KV
        depends on the missing tokens' positions being resident.)"""
        keys = self.block_keys(token_ids)
        if not keys:
            return PrefixMatch()
        with _spans.root_span("kvcache.match_prefix"):
            present = self._cache.batch_contains(keys)
        n = 0
        for hit in present:
            if not hit:
                break
            n += 1
        return PrefixMatch(tokens=n * self.block_tokens, blocks=n,
                           keys=keys[:n])

    # -- writes -------------------------------------------------------------
    def append_blocks(self, token_ids: Sequence[int], kv_blocks,
                      *, start_block: int = 0,
                      write_through: Optional[bool] = None) -> int:
        """Store per-block KV arrays for blocks [start_block,
        start_block + len(kv_blocks)) of the sequence; returns blocks
        actually WRITTEN. Already-present keys are skipped (one batched
        probe), so two sessions extending a shared prefix store each
        shared block exactly once — content addressing makes the racy
        double-store idempotent anyway (same key, same bytes)."""
        keys = self.block_keys(token_ids)
        want = keys[start_block:start_block + len(kv_blocks)]
        if len(want) != len(kv_blocks):
            raise _err(Code.INVALID_ARG,
                       f"{len(kv_blocks)} blocks at {start_block} but the "
                       f"sequence only chains {len(keys)} full blocks")
        with _spans.root_span("kvcache.append_blocks") as sp:
            with _spans.span("kvcache.append_blocks", "probe"):
                present = self._cache.batch_contains(want)
            fresh = [(key, arr) for key, arr, hit
                     in zip(want, kv_blocks, present) if not hit]
            if not fresh:
                return 0
            with _spans.span("kvcache.append_blocks", "encode_array"):
                items = [(key, encode_array(arr)) for key, arr in fresh]
            if sp is not None:   # the blocks' payload, headers apart
                sp.nbytes = sum(getattr(arr, "nbytes", len(raw))
                                for (_, arr), (_, raw) in zip(fresh, items))
            self._put_items(items, write_through)
        return len(items)

    def _put_items(self, items, write_through: Optional[bool]) -> None:
        # drain as ONE batched put (KVCacheClient.batch_put: one
        # batch_create + one striped batch write + one batch_close for
        # the whole drain) — the last per-block serial-create path
        # (ROADMAP carried follow-up; regression-pinned in
        # tests/test_kvcache.py)
        batched = getattr(self._cache, "batch_put", None)
        if batched is not None and len(items) > 1:
            if write_through is None:
                batched(items)
            else:
                try:
                    batched(items, write_through=write_through)
                except TypeError:  # fs-tier cache: always through
                    batched(items)
        else:
            for key, raw in items:
                if write_through is None:
                    self._cache.put(key, raw)
                else:
                    self._cache.put(key, raw, write_through=write_through)

    # -- reads --------------------------------------------------------------
    def get_blocks(self, token_ids: Sequence[int], *,
                   count: Optional[int] = None, device=None) -> List:
        """Fetch the sequence's first `count` blocks (default: every full
        block) as arrays — host-tier hits from RAM, all misses as ONE
        striped batch underneath. Missing blocks come back as None (the
        caller re-prefills that suffix). With ``device=``, each block is
        handed off via ``jax.device_put``."""
        keys = self.block_keys(token_ids)
        if count is not None:
            keys = keys[:count]
        with _spans.root_span("kvcache.get_blocks") as sp:
            blobs = self._cache.batch_get(keys)
            with _spans.span("kvcache.get_blocks", "decode"):
                # zero-copy views; None for a miss or a stale entry
                out: List = [None if raw is None
                             else self._decode(keys[i], raw)
                             for i, raw in enumerate(blobs)]
            nbytes = sum(arr.nbytes for arr in out if arr is not None)
            if sp is not None:
                sp.nbytes = nbytes
            if device is not None:
                import jax

                # the dispatch only: landing is the caller's
                # block_until_ready
                with _spans.span("kvcache.get_blocks", "device_put",
                                 nbytes=nbytes):
                    out = [arr if arr is None
                           else jax.device_put(arr, device) for arr in out]
        return out

    def _decode(self, key: str, raw):
        """Decode one block; a KVCACHE_STALE read (cached inode outlived
        a GC'd entry — zero-hole payload) invalidates and re-probes ONCE
        so the caller sees a plain miss, never zeros-as-KV."""
        try:
            return decode_array(raw)
        except FsError as e:
            if e.code != Code.KVCACHE_STALE:
                raise
        self._stale_reads.add()
        invalidate = getattr(self._cache, "invalidate", None)
        if invalidate is None:
            return None
        with _spans.span("kvcache.get_blocks", "reprobe"):
            invalidate(key)
            raw = self._cache.get(key)
            if raw is None:
                return None
            return decode_array(raw)

    # -- leases -------------------------------------------------------------
    def pin_prefix(self, match: PrefixMatch, ttl_s: Optional[float] = None):
        """Pin a matched prefix's blocks for the decode's lifetime (needs
        a LeaseManager wired at construction)."""
        if self._leases is None:
            raise _err(Code.INVALID_ARG,
                       "PrefixBlockStore built without a LeaseManager")
        return self._leases.pin(match.keys, ttl_s)
