"""Stripe codec: the device-resident EC data plane the serving path calls.

One stripe = one file chunk split into k data shards of S bytes plus m
parity shards. Encode (RS(k,m) GF(2) bit-matmul, Pallas on TPU) and batched
CRC32C run on device; decode/reconstruct applies the same RSCode decode matrix
and kernel (pallas_rs.gf2_matmul) the multi-chip rebuild uses, as ONE
jitted program of its own, ``decode_device``.

The reference has no RS path (it replicates via CRAQ, docs/design_notes.md
"Data replication"); "EC" exists there as a chain-table type in the
placement solver (deploy/data_placement/src/model/data_placement.py:30).
This module is the added TPU-native capability from BASELINE.json, gated by
ChainInfo.ec_k/ec_m the way the reference gates engines per target
(src/storage/store/StorageTarget.h:162).
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu3fs.analytics import spans as _spans
from tpu3fs.ops.crc32c import BatchCrc32c, crc32c, crc32c_batch_host
from tpu3fs.ops.rs import RSCode

# codecs are heavyweight (device matrices + compiled fns): share per-process
_cache_lock = threading.Lock()
_codecs: Dict[Tuple[int, int, int], "StripeCodec"] = {}


def get_codec(k: int, m: int, shard_size: int) -> "StripeCodec":
    key = (k, m, shard_size)
    with _cache_lock:
        codec = _codecs.get(key)
        if codec is None:
            codec = StripeCodec(k, m, shard_size)
            _codecs[key] = codec
        return codec


def _bucket(b: int) -> int:
    """Round a batch size up to the next power of two (shape bucketing for
    the device paths: bounds XLA recompiles at O(log B) per codec)."""
    p = 1
    while p < b:
        p <<= 1
    return p


# Most shard bytes one device dispatch may hold. The batch a caller hands
# the codec is unbounded (one FileIoClient.write of N full stripes is ONE
# encode_parity call), and the CRC kernel widens its input to int8 bit
# planes, 8x the bytes — so the device branches run a large batch as a
# sequence of dispatches of at most this size.
DEVICE_BATCH_BYTES = 128 << 20
# ... and the most stripes of an encode or a reconstruct dispatch. Every
# power of two up to it is a program the codec builds before it serves
# (StripeCodec._prepare): 5 for the encode, and 5 for each count of lost
# shards the codec decodes. On a v5e the round trip of one encode stopped
# scaling past 16 stripes of RS(12,4) S=87552 (PERF.md, "The codec's
# buckets": 0.9 ms a stripe at 16, 3.6 at 32, 3.9 at 64), measured when
# the encode program still returned all k+m shards; it now returns the
# parity and the CRCs alone, and whether the bound should move with that
# is unmeasured. The CRC keeps the bound by bytes alone.
DEVICE_BATCH_ITEMS = 16


def aligned_shard_size(n: int) -> int:
    """Round a working shard size up to the same 512B/64B grid
    shard_size_of uses — zero padding is free for RS/CRC math, and the
    alignment keeps the per-(k, m, S) codec cache from fragmenting into one
    compiled kernel per distinct logical tail length."""
    align = 512 if n >= 512 else 64
    return -(-n // align) * align


def shard_size_of(chunk_size: int, k: int) -> int:
    """Shard size for a chunk striped over k data shards (last shard padded).

    Rounded up to a CRC-block/TPU-lane-friendly boundary (512B, or 64B for
    tiny shards) — client and server both derive S through here, so the
    alignment is part of the stripe format."""
    s0 = -(-chunk_size // k)
    align = 512 if s0 >= 512 else 64
    return -(-s0 // align) * align


class StripeCodec:
    """Encode/decode/checksum a batch of stripes on the device."""

    def __init__(self, k: int, m: int, shard_size: int):
        self.k = k
        self.m = m
        self.shard_size = shard_size
        self.rs = RSCode(k, m)
        block = 512 if shard_size % 512 == 0 else shard_size
        self._crc = BatchCrc32c(shard_size, block=block)
        self._host_mode: Optional[bool] = None
        # device programs (jit is lazy: building them touches no backend)
        self._encode_dev = jax.jit(self._encode_device)
        self._crc_dev = jax.jit(self._crc.compute)
        self._decode_dev = jax.jit(self._decode_device)
        # "encode", and each lost count: every bucket of it is built
        self._prepared: set = set()
        self._prepare_lock = threading.Lock()

    def _use_host(self) -> bool:
        """The serving path stays on host kernels even when a TPU is
        attached: StripeCodec's contract is host bytes in / host bytes out
        (the RPC layer), one stripe batch per request, and a synchronous
        device round trip per call is transfer-bound. The device kernels
        (Pallas bit-matmul + fused CRC) remain the path for
        device-RESIDENT data: RSCode.encode / reconstruct_fn as used by
        tpu3fs.parallel.{rebuild,shuffle} and the benches.
        TPU3FS_STRIPE_DEVICE=1 asks for the device path, for hosts whose
        accelerator is local enough to win on big batches; asking for it
        in a process whose backend is not a TPU is an error, not a
        request the host quietly serves."""
        if self._host_mode is None:
            want_device = os.environ.get("TPU3FS_STRIPE_DEVICE", "") == "1"
            if want_device:
                backend = jax.default_backend()
                if backend != "tpu":
                    raise RuntimeError(
                        "TPU3FS_STRIPE_DEVICE=1 but no TPU: the default "
                        f"jax backend is {backend!r}")
            self._host_mode = not want_device
        return self._host_mode

    def _device_step(self, rows_per_item: int) -> int:
        """Items per device dispatch: the largest power of two whose rows
        fit DEVICE_BATCH_BYTES (at least one)."""
        n = max(1, DEVICE_BATCH_BYTES // (rows_per_item * self.shard_size))
        return 1 << (n.bit_length() - 1)

    def _buckets(self, rows_per_item: int) -> List[int]:
        """Every batch size an encode or reconstruct dispatch can have
        (``rows_per_item``: k+m, or k + lost): the powers of two up to
        DEVICE_BATCH_ITEMS, or to _device_step where that is less."""
        step = min(self._device_step(rows_per_item), DEVICE_BATCH_ITEMS)
        return [1 << i for i in range(step.bit_length())]

    def _prepare(self, key, fn, rows_per_item: int) -> None:
        """Build ``fn``'s program of every bucket, once a ``key`` ("encode",
        or a decode's lost count): XLA compiles a program the first time
        it meets a shape, and which batch sizes a put or a degraded read
        will bring is the traffic's to say (a KVCache suffix of 1 to 16
        blocks, a drain of the write-back tier, a document of 128, the
        degraded stripes of a load), so whichever call comes first pays
        for all of them — at start-up, in practice — and none compiles on
        a later request's path. Each is run once on zeros: that is what
        fills jit's own table."""
        if key in self._prepared:
            return
        with self._prepare_lock:
            if key in self._prepared:
                return
            for bp in self._buckets(rows_per_item):
                jax.block_until_ready(fn(np.zeros(
                    (bp, self.k, self.shard_size), dtype=np.uint8)))
            self._prepared.add(key)

    def _device_map(self, fn, items: np.ndarray, step: int,
                    op: str = "codec.encode"):
        """Run fn over items in power-of-two-bucketed dispatches of at
        most ``step`` (a power of two: _device_step's, or the encode's
        largest bucket) and yield (lo, n, host outputs) per dispatch. XLA
        compiles one program per input SHAPE, so free-running batch sizes
        (every distinct run length the file client flushes) would each
        pay a fresh multi-second compile — with bucketing there are
        O(log B) programs per codec, reused forever. Zero rows encode to
        zero parity, so the pad rows are simply sliced off by the caller.

        Traced per dispatch as two stages of ``op``: ``dispatch`` (pad
        and launch) and ``fetch`` (device_get: the wait for the program
        and device -> host; on the chip the launch returns before the
        host -> device copy is done, so that copy ends inside ``fetch``
        too). Bytes make no sense for a
        launch, so both stages' ``nbytes`` holds the COUNT of items
        (stripes, for an encode) the dispatch carries."""
        for lo in range(0, items.shape[0], step):
            part = items[lo:lo + step]
            n = part.shape[0]
            with _spans.span(op, "dispatch", nbytes=n):
                bp = _bucket(n)
                if bp != n:
                    part = np.concatenate(
                        [part, np.zeros((bp - n,) + part.shape[1:],
                                        dtype=np.uint8)], axis=0)
                launched = fn(part)
            with _spans.span(op, "fetch", nbytes=n):
                got = jax.device_get(launched)
            yield lo, n, got

    # -- encode --------------------------------------------------------------
    def encode_parity(self, data: np.ndarray
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """(B, k, S) uint8 -> (parity (B, m, S), crcs (B, k+m) uint32) —
        the serving-path shape: callers already hold the data-shard bytes.
        Honors the same host/device policy as encode_batch
        (TPU3FS_STRIPE_DEVICE=1 keeps the device kernels for hosts whose
        accelerator is local enough to win). On the device branch it goes
        through encode_batch, which fetches only the parity and the CRCs
        from the chip: every device encode a client serves passes that one
        method, where the benchmark notes the encodes its roofline counts
        and its controls plant a fault."""
        b, k, s = data.shape
        assert k == self.k and s == self.shard_size, (data.shape, self.k)
        if not self._use_host():
            shards, crcs = self.encode_batch(data)
            return shards[:, k:], crcs
        # the host kernels: a codec.encode op span with code 1 (the device
        # path's, in encode_batch, has code 0)
        with _spans.root_span("codec.encode", nbytes=b * k * s, code=1):
            parity = self.rs.encode_host(data)
            crcs = np.empty((b, k + self.m), dtype=np.uint32)
            crcs[:, :k] = crc32c_batch_host(
                np.ascontiguousarray(data).reshape(b * k, s)).reshape(b, k)
            if self.m:
                crcs[:, k:] = crc32c_batch_host(
                    np.ascontiguousarray(parity).reshape(b * self.m, s)
                ).reshape(b, self.m)
        return parity, crcs

    def encode_batch(self, data: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(B, k, S) uint8 -> (shards (B, k+m, S), crcs (B, k+m) uint32),
        both materialized on host for the RPC layer. On the device branch
        the chip sends back the parity rows and the CRCs alone
        (_encode_device); the data half of ``shards`` is copied from
        ``data`` on the host, which the caller already holds."""
        b, k, s = data.shape
        assert k == self.k and s == self.shard_size, (data.shape, self.k)
        if self._use_host():
            # host kernel selection (native SIMD vs numpy gold) lives in
            # RSCode.encode_host / crc32c_batch_host — one dispatch layer
            parity, crcs_np = self.encode_parity(data)
            shards_np = np.concatenate([data, parity], axis=1)
            return shards_np, crcs_np
        with _spans.root_span("codec.encode", nbytes=b * k * s):
            shards = np.empty((b, k + self.m, s), dtype=np.uint8)
            crcs = np.empty((b, k + self.m), dtype=np.uint32)
            rows = self.k + self.m
            self._prepare("encode", self._encode_dev, rows)
            for lo, n, (out_p, out_c) in self._device_map(
                    self._encode_dev, data, self._buckets(rows)[-1]):
                shards[lo:lo + n, k:] = out_p[:n]
                crcs[lo:lo + n] = out_c[:n]
            shards[:, :k] = data
        return shards, crcs

    def _encode_device(self, data):
        """(Bp, k, S) -> (parity (Bp, m, S), crcs (Bp, k+m)): encode and
        checksum as ONE jitted program per batch bucket. The CRCs cover
        all k+m rows, computed on the chip; the data rows themselves do
        not come back, since every caller holds them."""
        bp = data.shape[0]
        n = self.k + self.m
        parity = self.rs.encode(data) if self.m else data[:, :0]
        shards = jnp.concatenate([data, parity], axis=1)
        crcs = self._crc.compute(shards.reshape(bp * n, self.shard_size))
        return parity, crcs.reshape(bp, n)

    def delta_parity(self, j: int, delta) -> np.ndarray:
        """Parity-row deltas for a sub-stripe change on data shard j:
        ``delta`` is D'_j ^ D_j zero-padded to S bytes -> (m, S) rows to
        XOR into the stored parity shards (``P'_i = P_i ^ c_ij * dD``).
        The RMW write path calls this instead of re-encoding the stripe:
        the moved bytes drop from k*S reads + (k+m)*S writes to
        (touched + m) shards each way. Host kernels (native SIMD / LUT
        gold) — the serving-path policy of _use_host applies, and the
        device path has no per-call win at one stripe."""
        d = np.frombuffer(delta, dtype=np.uint8) \
            if not isinstance(delta, np.ndarray) else delta
        assert d.shape[-1] == self.shard_size, (d.shape, self.shard_size)
        return self.rs.delta_parity_host(j, d)

    def hop_accumulate(self, j: int, payloads, acc: np.ndarray) -> np.ndarray:
        """One chain-encode hop over a stripe batch: XOR data shard j's
        coefficient-scaled contribution into the in-flight parity
        accumulators and return the contribution CRCs.

        ``payloads`` is a length-B sequence of the hop's raw (trimmed)
        shard-j bytes — one per stripe of the batch; ``acc`` is the
        (B, m, S) uint8 accumulator frame riding the chain forward,
        updated IN PLACE. Returns (B, m) uint32 CRC32Cs of the
        contribution rows for the per-hop partial-CRC composition
        (crc32c_xor): the tail's validated install then checks the whole
        relay, not just the last wire crossing. Host kernels only — this
        runs inside storage hops (the serving-path policy of _use_host)."""
        B = len(payloads)
        assert acc.shape == (B, self.m, self.shard_size), (acc.shape, B)
        d = np.zeros((B, self.shard_size), dtype=np.uint8)  # copy-ok: pad to S
        for b, p in enumerate(payloads):
            flat = np.frombuffer(p, dtype=np.uint8)
            d[b, : flat.size] = flat
        contrib = self.rs.gf_accumulate(j, d, acc)
        return crc32c_batch_host(
            np.ascontiguousarray(contrib).reshape(B * self.m,
                                                  self.shard_size)
        ).reshape(B, self.m)

    def encode_stripe(self, chunk: bytes) -> Tuple[np.ndarray, np.ndarray]:
        """One chunk (<= k*S bytes, zero-padded) -> ((k+m, S), (k+m,))."""
        buf = np.zeros((self.k, self.shard_size), dtype=np.uint8)
        flat = np.frombuffer(chunk, dtype=np.uint8)
        buf.reshape(-1)[: flat.size] = flat
        shards, crcs = self.encode_batch(buf[None])
        return shards[0], crcs[0]

    # -- decode --------------------------------------------------------------
    def reconstruct_batch(
        self,
        present_idx: Sequence[int],
        lost_idx: Sequence[int],
        present: np.ndarray,
    ) -> np.ndarray:
        """(B, k, S) survivors at present_idx -> (B, len(lost), S) rebuilt.
        The single-chip serving path — a batched read hands it every
        degraded stripe of one loss pattern at once; the pod-scale variant
        is tpu3fs.parallel.rebuild.rebuild_lost_shard over a mesh (the
        same RSCode decode matrix underneath). On the device, B goes out
        in bucketed dispatches of at most DEVICE_BATCH_ITEMS stripes, and
        the first call of a lost count builds every bucket of it
        (_prepare): host-mode codecs compile nothing."""
        if self._use_host():
            return self.rs.reconstruct_host(present_idx, lost_idx, present)
        b = present.shape[0]
        matrix = self.rs.decode_operand(present_idx, lost_idx)
        out = np.empty((b, len(lost_idx), self.shard_size), dtype=np.uint8)
        rows = self.k + len(lost_idx)

        def decode(part):
            return self._decode_dev(matrix, part)

        with _spans.root_span("codec.reconstruct",
                              nbytes=b * self.k * self.shard_size):
            self._prepare(len(lost_idx), decode, rows)
            for lo, n, rebuilt in self._device_map(
                    decode, present, self._buckets(rows)[-1],
                    op="codec.reconstruct"):
                out[lo:lo + n] = rebuilt[:n]
        return out

    def _decode_device(self, matrix, present):
        """(8*lost, 8k) decode matrix x (Bp, k, S) survivors -> (Bp, lost,
        S): ONE jitted program per (lost count, batch bucket), named
        ``decode_device`` as the encode's is ``encode_device``; the buckets
        of a lost count are the powers of two up to DEVICE_BATCH_ITEMS,
        all built by its first call. The matrix is an OPERAND
        (RSCode.decode_operand), so every loss pattern of one shape shares
        the program; the kernel's lane padding and the slice back to S are
        inside it, not dispatches of their own."""
        return self.rs.apply_operand(matrix, present)

    def crc_batch(self, shards: np.ndarray) -> np.ndarray:
        """(N, S) uint8 -> (N,) uint32 (device; host CRC on CPU backends)."""
        if self._use_host():
            return crc32c_batch_host(shards)
        out = np.empty(shards.shape[0], dtype=np.uint32)
        for lo, n, crcs in self._device_map(self._crc_dev, shards,
                                            self._device_step(1),
                                            op="codec.crc"):
            out[lo:lo + n] = crcs[:n]
        return out

    # -- host-side assembly helpers ------------------------------------------
    def assemble(self, data_shards: List[Optional[bytes]], length: int) -> bytes:
        """Concatenate k data shards (None = absent, an error upstream)
        and trim the stripe padding to the chunk's logical length."""
        assert all(s is not None for s in data_shards)
        return b"".join(data_shards)[:length]

    def crc_host(self, shard: bytes) -> int:
        """Host-side single-shard CRC of the STORED (trimmed) bytes — the
        ShardWriteReq.crc wire convention."""
        return crc32c(shard)


def trim_rebuilt_shard(
    rebuilt: bytes, j: int, survivor_lens: Dict[int, int], k: int, S: int
) -> bytes:
    """Trim a rebuilt data shard back to its stored (logical) extent.

    Shards are stored trimmed — shard j holds chunk bytes [j*S, (j+1)*S) up
    to the stripe's logical length — so the rebuilt padded bytes must be
    cut back or the re-installed shard would inflate the stripe's recorded
    length. survivor_lens maps surviving DATA shard index -> stored length.

    Exact cases: any nonempty survivor above j proves shard j was full; a
    nonempty-to-empty boundary below j proves it was empty. The one
    ambiguous case (j is the last nonempty shard, partially filled) falls
    back to trailing-zero trimming: bytes stay exact either way, only the
    recorded length can undershoot if the true content ends in zeros."""
    if j >= k:
        return rebuilt  # parity shards are always stored full
    if any(lj > 0 for i, lj in survivor_lens.items() if i > j and i < k):
        return rebuilt  # a later data shard has content: j was full
    below = [lj for i, lj in survivor_lens.items() if i < j]
    if below and min(below) < S:
        return b""  # an earlier shard is short: logical length < j*S
    return rebuilt.rstrip(b"\x00")
