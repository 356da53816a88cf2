"""Static check: the served read AND write paths must stay copy-free.

The zero-copy pipelines (docs/readpath.md, docs/writepath.md) hold only
as long as nobody quietly re-introduces a payload copy on the wire path —
a single ``bytes(seg)`` on a 1 MiB segment silently costs more than the
whole serde envelope. This check walks the functions that make up the
served read path (engine view -> gather reply -> client receive view)
and the served write path (client bulk-frame gather -> server
receive-view attach -> engine hand-off -> streaming chain forward) and
flags the three ways payload copies sneak back in:

- ``bytes(...)`` calls (materializing a view),
- ``b"".join(...)`` / ``b''.join(...)`` (concatenation),
- ``+=`` accumulation whose right-hand side names payload-ish data
  (``data``/``payload``/``seg``/``blob``/``body``/``chunk``/``part``).

A line that NEEDS a copy (ops that outlive the request, EC decode
re-buffering) must say so: a ``# copy-ok: <reason>`` comment on the line
exempts it, and the reason is required.

Run: ``python tools/check_copy_hotpath.py`` (exit 0 = clean); wired into
tier-1 via tests/test_copy_hotpath.py, like check_rpc_registry.py.
"""

from __future__ import annotations

import ast
import os
import re
import sys
from typing import List, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (file, [function names]) — every function (top-level, nested or method)
# with a matching name inside the file is checked
HOT_PATH: List[Tuple[str, List[str]]] = [
    ("tpu3fs/rpc/net.py",
     ["_send_packet", "_sendmsg_all", "_recv_packet", "split_bulk",
      "start_call", "finish_call"]),
    ("tpu3fs/rpc/services.py",
     ["_read_h", "_batch_read_h", "_attach_read_segs",
      "batch_read_pipelined",
      # write path: bulk-frame receive attach + handler unwrap + the
      # client-side striped pipelined gather fan-out
      "_attach", "_write_h", "_batch_write_h", "_one_write",
      "_batch_write", "batch_write_pipelined"]),
    ("tpu3fs/storage/craq.py",
     ["_batch_read_impl",
      # write path: batched stage/forward/commit pipeline + the streaming
      # chain forward (the received views are re-gathered onward)
      "_handle_batch_update", "_forward_batch", "_make_forward_req",
      # pipelined chain encode: the hop must forward accumulator ROWS as
      # memoryviews and install via the shared validated path
      "chain_encode", "_chain_encode_hop"]),
    ("tpu3fs/storage/engine.py", ["batch_read_views"]),
    ("tpu3fs/storage/native_engine.py",
     ["batch_read_views",
      # write path: iovec-mode engine hand-off (no blob concatenation)
      "batch_update", "_payload_addr"]),
    ("tpu3fs/client/storage_client.py",
     # the public batch_read/batch_write/write_stripes names are thin
     # tracing wrappers (root spans); the hot bodies are the _op twins
     ["_batch_read_op",
      # write path: pipelined batch fan-out + batched stripe writes
      "_batch_write_op", "_write_stripes_op", "_write_stripe_batch",
      "_send_shard_batches",
      # EC data plane: batched shard fetch, clean/degraded stripe
      # assembly (the degraded fill), delta-parity sub-stripe RMW
      "_issue_wire_reads", "_plan_stripe_read", "_stripe_clean",
      "_stripe_degraded", "_degraded_plan", "_decode_stripes",
      "_finish_stripe_reads", "_write_stripe_rmw",
      # chain-encode planning: raw data shards go out as VIEWS of the
      # caller's stripe bytes (the whole client-CPU offload story)
      "_write_stripes_chain"]),
    # EC kernels: XOR-scheduled host encode + delta-parity column apply
    # + the chain-encode hop accumulate (in-place XOR, no staging copies)
    ("tpu3fs/ops/rs.py", ["encode_np", "delta_parity_host",
                          "gf_accumulate"]),
    ("tpu3fs/ops/stripe.py", ["encode_parity", "delta_parity",
                              "hop_accumulate"]),
    # EC rebuild: batched recovery gather + batched shard install
    ("tpu3fs/storage/ec_resync.py",
     ["_gather_batched", "_install_batch", "_rebuild_batch"]),
    ("tpu3fs/client/file_io.py",
     ["read_into", "_batch_read_into", "_batch_read_files_direct",
      "_fetch_window",
      # write path: user-buffer gather into per-chunk views
      "write", "batch_write_files", "_byte_view", "_flush_cr"]),
    # the dataload batch-assembly hot loop: records must be sliced out of
    # fetched spans as views and land in the batch array in ONE copy
    ("tpu3fs/dataload/recordio.py", ["read_batch", "plan_coalesced"]),
    ("tpu3fs/dataload/loader.py",
     ["_fetch", "_assemble_array", "_read_with_backoff"]),
    ("tpu3fs/dataload/dataset.py", ["read_samples"]),
    # the kvcache serving read path: host-tier hits and batched fill must
    # hand buffers through as views; block decode is a frombuffer view.
    # write-back: the flusher drains as one batched striped write
    ("tpu3fs/kvcache/tier.py",
     ["batch_get", "_local", "_fill", "_flush_items"]),
    ("tpu3fs/kvcache/cache.py", ["batch_put"]),
    ("tpu3fs/kvcache/blocks.py", ["get_blocks"]),
    ("tpu3fs/kvcache/layout.py", ["decode_array"]),
]

_BYTES_CALL = re.compile(r"(?<![\w.])bytes\s*\(")
_JOIN = re.compile(r"b(\"\"|'')\s*\.\s*join\s*\(")
_PAYLOAD_CONCAT = re.compile(
    r"\+=\s*.*\b(data|payload|seg|segment|blob|body|chunk|part)\w*\b")
_COPY_OK = re.compile(r"#\s*copy-ok:\s*\S")


def _function_spans(tree: ast.AST, names: set) -> List[Tuple[str, int, int]]:
    spans = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name in names:
            lo = node.lineno
            body = node.body
            if body and isinstance(body[0], ast.Expr) and \
                    isinstance(body[0].value, ast.Constant) and \
                    isinstance(body[0].value.value, str):
                lo = body[0].end_lineno + 1  # skip the docstring
            spans.append((node.name, lo, node.end_lineno))
    return spans


def check() -> List[str]:
    errors: List[str] = []
    for rel, names in HOT_PATH:
        path = os.path.join(REPO, rel)
        try:
            with open(path, "r") as f:
                src = f.read()
        except OSError as e:
            errors.append(f"{rel}: unreadable ({e})")
            continue
        tree = ast.parse(src)
        lines = src.splitlines()
        spans = _function_spans(tree, set(names))
        found = {n for n, _, _ in spans}
        for missing in set(names) - found:
            errors.append(
                f"{rel}: hot-path function {missing!r} not found — "
                "update tools/check_copy_hotpath.py HOT_PATH")
        for fname, lo, hi in spans:
            for ln in range(lo, hi + 1):
                line = lines[ln - 1]
                code = line.split("#", 1)[0]
                if _COPY_OK.search(line):
                    continue
                hit = None
                if _BYTES_CALL.search(code):
                    hit = "bytes() materializes a copy"
                elif _JOIN.search(code):
                    hit = 'b"".join concatenation copy'
                elif _PAYLOAD_CONCAT.search(code):
                    hit = "+= payload concatenation"
                if hit:
                    errors.append(
                        f"{rel}:{ln} in {fname}: {hit} on a served "
                        f"hot path: {line.strip()!r} — make it a "
                        "view/gather, or annotate '# copy-ok: <why>'")
    return errors


def main() -> int:
    errors = check()
    if errors:
        print(f"check_copy_hotpath: {len(errors)} problem(s)",
              file=sys.stderr)
        for e in errors:
            print(f"  - {e}", file=sys.stderr)
        return 1
    print("check_copy_hotpath: served read/write paths are copy-clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
