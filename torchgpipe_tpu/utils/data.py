"""Input-pipeline utilities: device prefetching and sequence packing.

The reference's data story is the rank-aware
``DistributedGPipeDataLoader`` (reference: torchgpipe/distributed/
gpipe.py:197-275, mirrored in :mod:`torchgpipe_tpu.distributed`); on TPU
the other half of the story is keeping the host→device copy off the
critical path.  ``jax.device_put`` is asynchronous, so holding a small
queue of already-transferred batches overlaps the next batch's transfer
(and any host-side preprocessing in the iterator) with the current step's
compute — the standard double-buffering recipe.

The second half of this module is **sequence packing** for ragged
corpora: GPipe-style pipelining needs fixed micro-batch shapes, so
variable-length documents are PACKED into the fixed ``[B, S]`` blocks
the engines already certify instead of padded to them.  The packer
(:func:`pack_documents`) is a deterministic greedy first-fit over
documents — no document is ever split across blocks, packing is a pure
function of the document list (resume replays it bit-for-bit) — and
each block carries ``segment_ids`` (0 = pad, 1.. per document) plus
per-token ``positions`` that reset at document boundaries, which is
what the segment-aware attention mask and packed rotary embeddings in
:mod:`torchgpipe_tpu.models.transformer` consume.  ``labels`` are the
within-document next tokens and ``weights`` mark the REAL supervised
positions, so the cross-entropy reduction weights by real tokens, not
block size (:func:`torchgpipe_tpu.models.transformer.
packed_cross_entropy`).
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
import numpy as np

Pytree = Any


def prefetch_to_device(
    iterable: Iterable[Pytree],
    size: int = 2,
    device: Optional[Any] = None,
) -> Iterator[Pytree]:
    """Yield batches from ``iterable`` with ``size`` transfers in flight.

    Each batch (any pytree of arrays) is committed to ``device`` (or a
    ``NamedSharding`` — pass the sharding object itself) before the
    consumer needs it.  ``size=2`` double-buffers: while the training step
    runs on batch k, batch k+1's host→device copy is already underway.

    The iterator is advanced at most ``size`` items ahead, so host-side
    memory is bounded and generator-backed loaders see backpressure.
    """
    if size < 1:
        raise ValueError(f"prefetch size must be >= 1, got {size}")
    it = iter(iterable)
    queue: collections.deque = collections.deque()

    def enqueue(n: int) -> None:
        for _ in range(n):
            try:
                item = next(it)
            except StopIteration:
                return
            queue.append(jax.device_put(item, device))

    enqueue(size)
    while queue:
        yield queue.popleft()
        enqueue(1)


def pipe_data_sharding(pipe: Any, *, stacked: bool = False) -> Any:
    """The right host→device placement for FULL training batches of
    ``pipe`` — what :func:`prefetch_to_device`'s ``device`` should be.

    * :class:`~torchgpipe_tpu.spmd.SpmdGPipe`: a ``NamedSharding`` over
      the pipe's mesh with the batch dimension split across the data
      axes (dp, ep) — the engine's own data convention, so the compiled
      step consumes the prefetched array without a resharding copy.
      ``stacked=True`` shifts the spec right by one for megastep's
      ``[K, ...]``-stacked batches (the K axis stays unsharded).
    * :class:`~torchgpipe_tpu.gpipe.GPipe`: stage 0's device (micro-
      batches enter the pipeline there); remaining dims ride along.

    Placement is a PERFORMANCE property, not a correctness one — the
    engines' ``jit``/``shard_map`` in-specs reshard mismatched inputs —
    so this helper only has to be good, never exact.
    """
    from torchgpipe_tpu.gpipe import GPipe

    if isinstance(pipe, GPipe):
        return pipe.devices[0]
    from jax.sharding import NamedSharding, PartitionSpec

    batch_axes = tuple(
        a for a in (pipe.dp_axis, pipe.ep_axis) if a is not None
    )
    batch = batch_axes if batch_axes else None
    spec = (
        PartitionSpec(None, batch) if stacked else PartitionSpec(batch)
    )
    return NamedSharding(pipe.mesh, spec)


def prefetch_to_pipe(
    iterable: Iterable[Pytree],
    pipe: Any,
    size: int = 2,
    *,
    stacked: bool = False,
) -> Iterator[Pytree]:
    """:func:`prefetch_to_device` with the placement resolved from the
    pipe (:func:`pipe_data_sharding`) — the one-liner the training-loop
    call sites use::

        for x, y in prefetch_to_pipe(loader, pipe):
            loss, params, opt_state = guard(params, opt_state, x, y)

    Each yielded batch (any pytree — ``(x, y)`` tuples included) is
    already committed to the engine's devices while the PREVIOUS step
    computes, so the step dispatch never waits on a host→device copy
    and the iterator's host-side work (tokenization, augmentation)
    overlaps device compute.  ``stacked=True`` places megastep's
    ``[K, ...]``-stacked batches (leading K axis unsharded).
    """
    return prefetch_to_device(
        iterable, size, device=pipe_data_sharding(pipe, stacked=stacked)
    )


# --------------------------------------------------------------------- #
# sequence packing (ragged corpora into fixed [B, S] blocks)            #
# --------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class Packing:
    """The result of :func:`pack_documents`: every document placed into
    fixed-length blocks, ready to slice into fixed ``[B, S]`` batches.

    Arrays are host-side ``np.ndarray`` (the input pipeline's domain);
    ``[R, S]`` with ``R`` the number of packed blocks:

    * ``tokens`` — the documents' tokens, back to back; ``pad_id`` fills
      each block's tail.
    * ``segment_ids`` — ``0`` on pad, ``1..k`` numbering the documents
      WITHIN each block (the block-diagonal attention-mask term).
    * ``positions`` — 0-based position of each token within ITS document
      (the packed rotary/learned-position index; resets per document).
    * ``labels`` / ``weights`` — within-document next token (causal-LM
      objective) and a ``1.0`` weight at every REAL supervised position;
      the last token of each document and all pad carry weight ``0.0``.
    * ``doc_locs`` — per input document ``(row, offset, length)``: where
      it landed.  The order is the input order; no document is split.
    """

    tokens: np.ndarray        # [R, S] int32
    segment_ids: np.ndarray   # [R, S] int32
    positions: np.ndarray     # [R, S] int32
    labels: np.ndarray        # [R, S] int32
    weights: np.ndarray       # [R, S] float32
    doc_locs: Tuple[Tuple[int, int, int], ...]
    block_len: int
    pad_id: int

    @property
    def n_blocks(self) -> int:
        return int(self.tokens.shape[0])

    @property
    def n_real_tokens(self) -> int:
        return int(np.sum(self.segment_ids != 0))

    @property
    def pad_fraction(self) -> float:
        """Fraction of block positions that hold pad, not document."""
        total = self.tokens.size
        return 1.0 - (self.n_real_tokens / total) if total else 0.0


def pack_documents(
    docs: Sequence[Any], block_len: int, *, pad_id: int = 0
) -> Packing:
    """Deterministic greedy FIRST-FIT packing of ``docs`` into
    ``block_len``-token blocks.

    Each document (a 1-D int token array) is placed whole into the first
    open block with room, else a new block opens — a pure function of
    the document list, so re-packing the same corpus (e.g. on resume)
    replays the identical layout.  A document longer than ``block_len``
    is a :class:`ValueError`: packing never splits documents (a split
    document's second half would attend nothing — train on shorter
    documents or raise ``block_len``).
    """
    if block_len < 2:
        raise ValueError(f"block_len must be >= 2, got {block_len}")
    arrs = [np.asarray(d, np.int32).reshape(-1) for d in docs]
    for i, a in enumerate(arrs):
        if a.size < 1:
            raise ValueError(f"document {i} is empty")
        if a.size > block_len:
            raise ValueError(
                f"document {i} has {a.size} tokens > block_len="
                f"{block_len}; packing never splits a document across "
                "blocks — raise block_len or pre-chunk the corpus"
            )
    free: List[int] = []           # free tokens per open block
    rows: List[List[np.ndarray]] = []
    locs: List[Tuple[int, int, int]] = []
    for a in arrs:
        for r, f in enumerate(free):
            if a.size <= f:
                row = r
                break
        else:
            row = len(free)
            free.append(block_len)
            rows.append([])
        locs.append((row, block_len - free[row], a.size))
        rows[row].append(a)
        free[row] -= a.size
    R = len(rows)
    tokens = np.full((R, block_len), pad_id, np.int32)
    seg = np.zeros((R, block_len), np.int32)
    pos = np.zeros((R, block_len), np.int32)
    labels = np.full((R, block_len), pad_id, np.int32)
    weights = np.zeros((R, block_len), np.float32)
    per_row_seg = [0] * R
    for a, (r, off, n) in zip(arrs, locs):
        per_row_seg[r] += 1
        tokens[r, off:off + n] = a
        seg[r, off:off + n] = per_row_seg[r]
        pos[r, off:off + n] = np.arange(n)
        # Within-document shift: position i predicts token i+1 of the
        # SAME document; the document's last token supervises nothing.
        labels[r, off:off + n - 1] = a[1:]
        weights[r, off:off + n - 1] = 1.0
    return Packing(
        tokens=tokens, segment_ids=seg, positions=pos,
        labels=labels, weights=weights, doc_locs=tuple(locs),
        block_len=block_len, pad_id=pad_id,
    )


def _batch_of(packing: Packing, rows: np.ndarray) -> Tuple[Pytree, Pytree]:
    """(x, y) for a row-index slice: the engines' packed batch contract
    — ``x`` a dict the packed-aware embedding unpacks, ``y`` the
    labels/weights dict :func:`~torchgpipe_tpu.models.transformer.
    packed_cross_entropy` consumes."""
    x = {
        "tokens": packing.tokens[rows],
        "segment_ids": packing.segment_ids[rows],
        "positions": packing.positions[rows],
    }
    y = {
        "labels": packing.labels[rows],
        "weights": packing.weights[rows],
    }
    return x, y


def packed_batches(
    packing: Packing,
    batch_rows: int,
    *,
    start: int = 0,
) -> Iterator[Tuple[Pytree, Pytree]]:
    """Slice a :class:`Packing` into fixed ``[batch_rows, block_len]``
    batches — every batch the SAME shape (a short final batch is topped
    up with all-pad rows: ``segment_ids == 0`` everywhere, zero loss
    weight — one compiled program serves the whole corpus).

    ``start=k`` resumes at batch ``k``: packing being deterministic, the
    resumed stream is bit-identical to the tail of the original one
    (tested).  Compose with :func:`prefetch_to_pipe` as usual; for the
    megastep path stack K consecutive batches along a leading axis
    (``stacked=True`` placement).
    """
    if batch_rows < 1:
        raise ValueError(f"batch_rows must be >= 1, got {batch_rows}")
    R = packing.n_blocks
    n_batches = -(-R // batch_rows)
    for b in range(start, n_batches):
        idx = np.arange(b * batch_rows, (b + 1) * batch_rows)
        idx = np.minimum(idx, R - 1)
        x, y = _batch_of(packing, idx)
        # Rows past the corpus end become all-pad no-ops rather than
        # repeats of the last block.
        tail = np.arange(batch_rows) + b * batch_rows >= R
        if tail.any():
            for k in ("tokens", "labels"):
                d = x if k in x else y
                d[k] = np.where(tail[:, None], packing.pad_id, d[k])
            x["segment_ids"] = np.where(tail[:, None], 0, x["segment_ids"])
            x["positions"] = np.where(tail[:, None], 0, x["positions"])
            y["weights"] = np.where(tail[:, None], 0.0, y["weights"])
        yield x, y


def padded_batches(
    docs: Sequence[Any],
    block_len: int,
    batch_rows: int,
    *,
    pad_id: int = 0,
    start: int = 0,
) -> Iterator[Tuple[Pytree, Pytree]]:
    """The PADDED baseline over the same documents: one document per
    ``[block_len]`` row, tail padded — the layout whose pad FLOPs
    :func:`pack_documents` exists to reclaim.  ``x`` is a plain ``[B, S]`` token
    array (no segment ids — the un-packed contract); ``y`` carries the
    same labels/weights schema, so ONE loss function serves both paths.
    """
    arrs = [np.asarray(d, np.int32).reshape(-1) for d in docs]
    n_batches = -(-len(arrs) // batch_rows)
    for b in range(start, n_batches):
        chunk = arrs[b * batch_rows:(b + 1) * batch_rows]
        tokens = np.full((batch_rows, block_len), pad_id, np.int32)
        labels = np.full((batch_rows, block_len), pad_id, np.int32)
        weights = np.zeros((batch_rows, block_len), np.float32)
        for r, a in enumerate(chunk):
            if a.size > block_len:
                raise ValueError(
                    f"document has {a.size} tokens > block_len={block_len}"
                )
            tokens[r, :a.size] = a
            labels[r, :a.size - 1] = a[1:]
            weights[r, :a.size - 1] = 1.0
        yield tokens, {"labels": labels, "weights": weights}


def real_token_fraction(x: Pytree, *, pad_id: int = 0) -> float:
    """Fraction of batch positions holding REAL tokens — the honest-MFU
    scale (:class:`torchgpipe_tpu.obs.StepReporter`'s
    ``real_token_fraction``): a packed batch (dict with
    ``segment_ids``) counts non-zero segments; a plain token array
    counts everything outside each row's TRAILING run of ``pad_id``
    (leading/interior ``pad_id`` tokens may be real vocabulary)."""
    if isinstance(x, dict) and "segment_ids" in x:
        seg = np.asarray(x["segment_ids"])
        return float(np.mean(seg != 0)) if seg.size else 0.0
    a = np.asarray(x)
    if a.ndim != 2 or a.size == 0:
        return 1.0
    rev = a[:, ::-1] != pad_id
    # Trailing pad run per row = leading run of pad_id in the reversal.
    trailing = np.where(
        rev.any(axis=1), np.argmax(rev, axis=1), a.shape[1]
    )
    return 1.0 - float(np.sum(trailing)) / a.size


def global_batch_from_local(
    mesh: Any,
    spec: Any,
    local_batch: Pytree,
) -> Pytree:
    """Assemble a GLOBAL sharded batch from each process's LOCAL shard.

    The multi-host data recipe (docs/multihost.md): every process loads
    only its own slice of the global batch (e.g. its dp lanes' examples)
    and this stitches them into one global ``jax.Array`` sharded by
    ``spec`` over ``mesh`` — no host ever holds, or sends, the full batch.
    Wraps ``jax.make_array_from_process_local_data``, which infers the
    global shape from the local one and the sharding's process layout.

    Single-process (all devices addressable) it degrades to a plain
    ``device_put``, so the same input pipeline runs everywhere.

    ``spec`` is a ``PartitionSpec`` applied to every leaf of the batch
    pytree (the engines' data convention: batch dim sharded over the data
    axes, e.g. ``P(("dp", "ep"))``).
    """
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh, spec)
    if sharding.is_fully_addressable:
        return jax.device_put(local_batch, sharding)
    return jax.tree_util.tree_map(
        lambda leaf: jax.make_array_from_process_local_data(sharding, leaf),
        local_batch,
    )
