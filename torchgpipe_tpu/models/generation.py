"""KV-cache autoregressive generation for the llama family.

New TPU-native capability (the reference is a training library with no
inference engine at all — SURVEY.md §2 has no generation component): a
user who trains a transformer with this framework can decode from it
without leaving the framework.

Design, TPU-first:

* **Two paths, one parameter schema.**  Prefill runs the SAME
  ``llama(cfg)`` layers the training engines run (one full forward over
  the prompt filling the caches); decode runs a cache-specialized
  single-token path (``_decode_step``) over the very same param pytrees
  (``wq/wk/wv/wo``, ``w_gate/w_up/w_down``, embed ``table``, head
  ``scale``/``w``), so there is no weight conversion step and the two
  paths cannot diverge in schema.  Numerical agreement IS tested
  (``tests/test_generation.py`` teacher-forces decode against the full
  forward).
* **Static shapes everywhere.**  The cache is a set of fixed buffers
  written at a traced position (what a cache row is — the kinds, their
  banks and layout, the writes — lives in :mod:`.kv_cache`; this module
  chooses the attention that reads them); the decode
  loop is ONE ``lax.scan`` over ``max_new_tokens`` ticks compiled once
  — no per-token retracing, no data-dependent shapes (XLA requirement).
  Finished rows (EOS seen) keep scanning but freeze their output — the
  compiler-friendly alternative to early exit.
* **GQA native**: caches store ``n_kv_heads`` (the memory win is the
  point of GQA); queries group at the compute site exactly like the
  training path.
* **Sequence-packing hooks**: :func:`_attend_full` and
  :func:`_attend_chunk` take optional segment planes (``seg`` /
  ``seg_q``+``seg_k``) folding the block-diagonal
  ``segment_ids[i] == segment_ids[j]`` term into their causal masks —
  packed documents teacher-forced through the decode path never attend
  each other (``utils.data.pack_documents``; dense path only, the
  flash kernels have no segment hook yet).
* **Sliding-window ready**: with ``cfg.attn_window`` the decode mask
  attends to at most ``window`` trailing positions — the same band the
  training path computes — so a Mistral-style model decodes with its
  training-time locality.  ``cache_mode='ring'`` goes further: W-slot
  ring caches (slot ``pos % W``) cut cache memory AND per-step
  attention reads from O(max_len) to O(window), bit-equal to the
  masked path (the in-band-by-construction property of the ring makes
  ``p_j >= 0`` the only mask needed).
* **Window and full layers mixed** (``cfg.attn_layers`` of more than
  one entry): every entry point reads layer ``i``'s entry
  (``cfg.attn_layer(i)``: its window, its rope record or none), and the
  cache holds a ring for each window layer beside ``max_len`` rows for
  each full one (``kv_cache.layer_rows``); a write lands at ``pos %
  rows`` and every mask goes by the position a ring row holds.

Sampling: greedy (``temperature=0``) or temperature softmax sampling
with optional top-k truncation and top-p (nucleus) filtering, driven by
an explicit ``jax.random`` key (deterministic, reproducible — the
framework-wide RNG discipline).  :func:`speculative_generate` wraps the
same machinery in a draft-propose / chunk-verify loop with the exact
output distribution (accept ``min(1, p/q)``, resample the residual).

Scope: single-host decode over replicated weights.  Pipelined decode
(pp-sharded stages serving one token stream) is latency-bound by design
and out of scope here; for batch inference over a pipeline use
``GPipe.apply``/``SpmdGPipe.apply`` on full sequences.

MoE models (``llama_moe``): pass the training ``moe=MoEConfig(...)`` —
the expert feed-forward runs its own apply on the decode hidden states.
Capacity caveat: token-choice capacity is computed per forward call, so
a decode step's pool is ``batch`` tokens while training pools
``batch*seq`` — with a tight ``capacity_factor`` the dropped-token sets
can differ between training and decode.  Decode==training equality (the
teacher-forced test) holds when capacity admits every token
(``capacity_factor >= n_experts/top_k``, or ``dispatch='dropless'``).
"""

from __future__ import annotations

import functools
from typing import Any, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from torchgpipe_tpu.models import kv_cache, mla
from torchgpipe_tpu.models.kv_cache import (  # noqa: F401 (re-exported)
    KVCache,
    LatentCache,
    QuantKVCache,
    _cache_rows,
    _dequant_rows,
    _refuse_mla,
    init_cache,
    init_quant_cache,
)
from torchgpipe_tpu.models.transformer import (
    TransformerConfig,
    _act_fn,
    _block_norm,
    _head_w,
    _lora_delta,
    _maybe_rope,
    _rms,
)

Pytree = Any


def _embed(cfg: TransformerConfig, embed_p: Pytree,
           tokens: jnp.ndarray, pos0: Any = 0) -> jnp.ndarray:
    """Token embedding with the optional Gemma-style output scaling (the
    tied head reads the UNSCALED table, so the scale lives here, not in
    the table) — mirrors token_embedding.apply.  A learned position
    table (GPT-2 class, ``embed_p['pos']``) adds rows at ``pos0 +
    arange(s)`` — decode callers pass ``cache.length``; a ``[b]``-shaped
    ``pos0`` gives every row its own base position (the slot-pooled
    serving decode)."""
    x = jnp.take(embed_p["table"], tokens, axis=0)
    if cfg.embed_scale is not None:
        x = x * jnp.asarray(cfg.embed_scale, x.dtype)
    if "pos" in embed_p:
        s = tokens.shape[-1]
        p0 = jnp.asarray(pos0)
        idx = (
            cfg.pos_emb_offset + p0[:, None] + jnp.arange(s)[None, :]
            if p0.ndim == 1
            else cfg.pos_emb_offset + p0 + jnp.arange(s)
        )
        x = x + jnp.take(embed_p["pos"], idx, axis=0).astype(x.dtype)
    return x


def _window(cfg: TransformerConfig, layer: int = 0) -> Optional[int]:
    """The window layer ``layer`` attends in."""
    return cfg.attn_layer(layer).window


def _attn_scope(window: Optional[int]) -> Any:
    """The scope the operation table tells the two kinds of layer apart
    by (the training block's names)."""
    return jax.named_scope("attn.window" if window is not None
                           else "attn.full")


def _w(cfg: TransformerConfig, p: Pytree, key: str) -> jnp.ndarray:
    """Weight read-site accessor: plain arrays pass through; weight-only
    int8 leaves (``models.quant``) dequantize here, so every decode path
    supports quantized params via this single definition."""
    from torchgpipe_tpu.models.quant import dequantize_weight

    return dequantize_weight(p[key], cfg.dtype)


def _split_params(cfg: TransformerConfig, params: Pytree) -> Tuple:
    """(embed, blocks, head) params from the flat ``llama(cfg)`` list —
    the MPMD engine's per-layer pytree sequence, or any sequence whose
    first element is the embedding, middle the blocks, last the head."""
    params = list(params)
    if len(params) != cfg.n_layers + 2:
        raise ValueError(
            f"expected {cfg.n_layers + 2} per-layer params (embed, "
            f"{cfg.n_layers} blocks, head), got {len(params)}; build the "
            "model with models.transformer.llama(cfg)"
        )
    return params[0], params[1 : 1 + cfg.n_layers], params[-1]


def _attend_ring(
    q: jnp.ndarray,          # [b, 1, nh, hd] — rope'd query for this step
    ck: jnp.ndarray,         # [b, W, nkv, hd] ring cache (slot = pos % W)
    cv: jnp.ndarray,
    pos: jnp.ndarray,        # [] int32 — this token's position
) -> jnp.ndarray:
    """Windowed decode attention over a RING cache: slot ``j`` holds the
    newest position ``<= pos`` congruent to ``j`` (mod W), which is
    in-band by construction (``0 <= pos - p_j < W``) — so the only mask
    is ``p_j >= 0`` (slots not yet written during the first W tokens).
    O(W) reads instead of O(max_len)."""
    b, _, nh, hd = q.shape
    W = ck.shape[1]
    nkv = ck.shape[2]
    r = nh // nkv
    qg = q[:, 0].reshape(b, nkv, r, hd)
    scores = jnp.einsum(
        "bgrd,bsgd->bgrs", qg.astype(jnp.float32), ck.astype(jnp.float32)
    ) * (hd ** -0.5)
    j = jnp.arange(W)
    p_j = pos - jnp.mod(pos - j, W)
    scores = jnp.where((p_j >= 0)[None, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrs,bsgd->bgrd", p, cv.astype(jnp.float32))
    return out.reshape(b, 1, nh * hd)


def _block_qkv(
    cfg: TransformerConfig,
    p: Pytree,
    x: jnp.ndarray,              # [b, g, dim]
    pos: jnp.ndarray,            # [] int32 first-query position, or [b] per row
    layer: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Shared per-block decode prologue: ln1, q/k/v projections (+LoRA
    deltas, +Qwen2 biases), head reshape, Qwen3 per-head q/k RMSNorm,
    rope at ``pos`` as layer ``layer``'s entry says (its theta and YaRN
    record, or no rotation).  ONE body for prefill and the single-token,
    chunked, and slot-masked decode paths — a model-family quirk added
    here reaches all four at once; only where the rows are written
    (``kv_cache``'s two writes) and the attend stay with each caller."""
    b, g, _ = x.shape
    hd = cfg.head_dim
    wq, wk, wv = _w(cfg, p, "wq"), _w(cfg, p, "wk"), _w(cfg, p, "wv")
    nh_loc = wq.shape[1] // hd
    nkv_loc = wk.shape[1] // hd
    h = _block_norm(cfg, p, "ln1", x)
    q, k, v = h @ wq, h @ wk, h @ wv
    if "lora" in p:
        lo = p["lora"]
        q = q + _lora_delta(cfg, lo, h, "qa", "qb")
        k = k + _lora_delta(cfg, lo, h, "ka", "kb")
        v = v + _lora_delta(cfg, lo, h, "va", "vb")
    if "bq" in p:  # Qwen2-style projection biases
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, g, nh_loc, hd)
    k = k.reshape(b, g, nkv_loc, hd)
    v = v.reshape(b, g, nkv_loc, hd)
    if "qn" in p:  # Qwen3-style per-head q/k RMSNorm, pre-rope
        q = _rms(q, p["qn"], cfg.norm_eps)
        k = _rms(k, p["kn"], cfg.norm_eps)
    q = _maybe_rope(cfg, q, pos, layer)
    k = _maybe_rope(cfg, k, pos, layer)
    return q, k, v


def _block_attn_out(
    cfg: TransformerConfig,
    p: Pytree,
    x: jnp.ndarray,              # [b, g, dim] — block input (residual stream)
    attn: jnp.ndarray,           # [b, g, nh*hd] — attention output
    mlp_layer: Optional[Any],
    valid: Optional[jnp.ndarray] = None,     # [b, g] bool: real tokens
    counts_out: Optional[List[jnp.ndarray]] = None,
) -> jnp.ndarray:
    """Shared per-block decode epilogue: the output gate
    (``cfg.attn_gate``: the heads' output times ``sigmoid(ln1(x) @
    wg)``), wo projection (+LoRA, +bias), attention residual, ln2
    (parallel or sequential residual), MLP residual; under
    ``cfg.sandwich_norm`` each branch's output is normed (``ln1p`` /
    ``ln2p``) before it joins the stream.  Counterpart of
    :func:`_block_qkv` (and of ``mla.project`` + ``mla.attend``).
    ``valid`` / ``counts_out`` as in :func:`_mlp_out`."""
    attn = attn.astype(x.dtype)
    if cfg.attn_gate:
        with jax.named_scope("attn.gate"):
            # ln1(x) is the prologue's: one computation in the program.
            attn = attn * jax.nn.sigmoid(
                _block_norm(cfg, p, "ln1", x) @ _w(cfg, p, "wg"))
    o = attn @ _w(cfg, p, "wo")
    if "lora" in p:
        o = o + _lora_delta(cfg, p["lora"], attn, "oa", "ob")
    if "bo" in p:
        o = o + p["bo"]
    if cfg.sandwich_norm:
        o = _block_norm(cfg, p, "ln1p", o)
    if cfg.layer_pattern is not None:   # an attention-only layer
        return x + o
    x_in = x
    x = x + o
    h = _block_norm(
        cfg, p, "ln2", x_in if cfg.parallel_residual else x
    )
    out = _mlp_out(cfg, p, h, mlp_layer, valid, counts_out)
    if cfg.sandwich_norm:
        out = _block_norm(cfg, p, "ln2p", out)
    return x + out


def _decode_step(
    cfg: TransformerConfig,
    block_params: List[Pytree],
    x: jnp.ndarray,              # [b, 1, dim] — embedded current token
    cache: Any,
    mlp_layer: Optional[Any] = None,
    ring: bool = False,
) -> Tuple[jnp.ndarray, Any]:
    """One token through all blocks, reading+extending the cache
    (``ring=True``: W-slot ring buffers, written at ``pos % W`` and read
    by :func:`_attend_ring`; a :class:`QuantKVCache` stores int8 rows
    with per-(position, head) scales, dequantized at the attention
    read).

    Mirrors ``transformer_block.apply`` exactly (same RMS/rope/GQA/SwiGLU
    math on the same param schema) minus the sp/tp collectives — decode
    here is single-host over replicated weights.  ``mlp_layer`` (built by
    :func:`_mlp_layer_for`) serves blocks carrying an ``"mlp"`` params
    key — the MoE feed-forward runs its own apply on the single-token
    hidden states (capacity >= 1 even at one token).

    The non-ring path IS :func:`_decode_chunk` at ``g=1`` (one shared
    per-block body, so a model-family quirk added there serves decode
    and speculative verification alike); only the ring slot/attend
    specialization lives here."""
    if not ring:
        return _decode_chunk(cfg, block_params, x, cache, mlp_layer)
    _refuse_mla(cfg, "a ring cache")
    kv_cache.refuse_rings(cfg, "cache_mode='ring' (one ring for all layers)")
    pos = cache.length
    W = _cache_rows(cache)
    new = []
    for p, layer in zip(block_params, kv_cache.layers(cache)):
        q, *rows = _block_qkv(cfg, p, x, pos)
        slot = jnp.mod(pos, W)
        layer = kv_cache.write_columns(layer, rows, slot)
        rk, rv, cks, cvs = layer
        if cks is not None:
            rk, rv = _dequant_rows(rk, cks), _dequant_rows(rv, cvs)
        attn = _attend_ring(q, rk, rv, pos)
        x = _block_attn_out(cfg, p, x, attn, mlp_layer)
        new.append(layer)
    return x, kv_cache.rebuild(cache, new, pos + 1)


def _flash_decode_eligible(
    q_shape: Tuple[int, ...], bank: Any, window: Optional[int], *,
    quant: bool, per_row: bool,
) -> bool:
    """Whether cache attention of these shapes takes the Pallas decode
    kernel on a TPU (what :func:`_attend_chunk` dispatches by, and what
    :func:`attend_rows_counter` counts by): shapes the kernel tiles, and not an
    int8 cache read per row (the int8 kernel takes one scalar ``pos0``).
    ``bank`` is anything with the cache bank's ``shape`` and ``dtype``."""
    from torchgpipe_tpu.ops.flash_attention import supports_decode

    return not (quant and per_row) and supports_decode(
        q_shape, bank.shape, window, jnp.dtype(bank.dtype).itemsize
    )


def _on_tpu() -> bool:
    """Whether the Pallas kernels compile for this platform (off a TPU
    they run interpreted, for tests)."""
    return jax.devices()[0].platform == "tpu"


def _latent_decode_eligible(
    q_shape: Tuple[int, ...], ckv: Any, kpe: Any,
) -> bool:
    """:func:`_flash_decode_eligible` for a latent pool: whether queries
    ``[rows, g, H, ...]`` against the banks ``ckv`` / ``kpe`` take the
    Pallas latent decode kernel (what :func:`_attend_latent` dispatches
    by and :func:`attend_rows_counter` counts by): a TPU, and shapes
    the kernel tiles."""
    from torchgpipe_tpu.ops.flash_attention import supports_latent_decode

    return _on_tpu() and supports_latent_decode(
        (*q_shape[:3], ckv.shape[2]), ckv.shape, kpe.shape[2]
    )


def _attend_block(
    cfg: TransformerConfig, cache: Any, rows: int, g: int, layer: int,
) -> Optional[int]:
    """The cache block that ``layer``'s attention of a ``decode_slots``
    call of ``rows`` rows of ``g`` tokens reads by, where this platform
    and these shapes take a decode kernel; None where the dense path
    runs."""
    from torchgpipe_tpu.ops.flash_attention import (
        _decode_tiling, _latent_tiling,
    )

    cache, bank_i = kv_cache.attention_view(cfg, cache, layer)
    max_len = kv_cache.bank_rows(cache)[bank_i]
    if cfg.mla is not None:
        bank = cache.ckv[bank_i]
        if not _latent_decode_eligible(
            (rows, g, cfg.n_heads), bank, cache.kpe[bank_i]
        ):
            return None
        return _latent_tiling(g * cfg.n_heads, bank.shape[2], max_len)[0]
    bank = cache.k[bank_i]
    if not _on_tpu() or not _flash_decode_eligible(
        (rows, g, cfg.n_heads, cfg.head_dim), bank, _window(cfg, layer),
        quant=isinstance(cache, QuantKVCache), per_row=True,
    ):
        return None
    return _decode_tiling(
        g, cfg.n_heads, bank.shape[2], bank.dtype.itemsize, max_len
    )[0]


def attend_rows_counter(
    cfg: TransformerConfig, cache: Any, rows: int, g: int, layer: int = 0,
) -> Any:
    """A function ``(pos0 [rows], n_valid [rows]) -> (rows read, row
    capacity)`` for the cache attention of ONE layer (``layer``: its
    window, and its banks' own length, a ring's where it holds one) of a
    :func:`decode_slots` call of ``rows`` rows of ``g`` tokens, counted
    on the HOST (numpy) from the rows' frontiers: capacity is ``rows x``
    the layer's length; read is the block-rounded rows the decode kernel
    (a latent pool's: the latent decode kernel) fetches (nothing for a
    row with ``n_valid == 0``) where this platform and
    these shapes take the kernel, the capacity where the dense path
    runs (off TPU, an int8 pool, shapes the kernel does not
    tile).  What is decided by platform and shape is decided here,
    once; a call is a few numpy operations over ``rows`` ints.  The
    serving engine's ``serving_attend_rows_read`` /
    ``serving_attend_rows_capacity``."""
    from torchgpipe_tpu.ops.flash_attention import decode_rows_read

    view, bank_i = kv_cache.attention_view(cfg, cache, layer)
    max_len = kv_cache.bank_rows(view)[bank_i]
    cap = rows * max_len
    block_k = _attend_block(cfg, cache, rows, g, layer)
    if block_k is None:
        return lambda pos0, n_valid: (cap, cap)
    window = _window(cfg, layer)
    ring = kv_cache.ring_layer(cfg, layer)
    # A band as long as the cache drops no block of any frontier.
    if not ring and window is not None and max_len - window + 1 < block_k:
        window = None

    def count(pos0: Any, n_valid: Any) -> Tuple[int, int]:
        pos0 = np.asarray(pos0)
        # A ring is read up to the row's frontier, wherever that lies.
        end = pos0 + g if ring else np.minimum(pos0 + g, max_len)
        live = np.where(np.asarray(n_valid) > 0, end, 0)
        return decode_rows_read(pos0, live, window, block_k), cap

    return count


def _attend_chunk(
    q: jnp.ndarray,          # [b, g, nh, hd] — rope'd queries, positions pos0..pos0+g-1
    ck: jnp.ndarray,         # [b, max_len, nkv, hd]
    cv: jnp.ndarray,
    pos0: jnp.ndarray,       # [] int32 — first query's position ([b]: per row)
    window: Optional[int],
    use_flash: Optional[bool] = None,
    k_scale: Optional[jnp.ndarray] = None,  # int8 cache: f32 [b, nkv, L]
    v_scale: Optional[jnp.ndarray] = None,
    seg_q: Optional[jnp.ndarray] = None,    # [b, g] packed segment ids
    seg_k: Optional[jnp.ndarray] = None,    # [b, max_len] cache segments
    slots: Optional[jnp.ndarray] = None,    # [b] — row i reads ck[slots[i]]
    lengths: Optional[jnp.ndarray] = None,  # [b] — cache rows a row needs
    ring: bool = False,
) -> jnp.ndarray:
    """Causal attention of ``g`` consecutive queries against the cache —
    one MXU-friendly einsum instead of g masked cache reads.  Query i
    (position ``pos0+i``) sees cache rows ``<= pos0+i`` (optionally
    banded); ``g=1`` is the plain single-token decode read.  A
    ``[b]``-shaped ``pos0`` gives every row its OWN first-query position
    — the serving pool's attention, where each slot sits at its own
    sequence frontier.

    ``slots`` makes ``ck`` / ``cv`` (and the scales) a BANK: query row
    ``i`` attends over bank row ``slots[i]`` (the compact
    ``decode_slots``).  The kernel reads it through its index map; the
    dense path takes one row at a time through a dynamic slice
    (:func:`_attend_row`), never a gather of the bank.  ``lengths``
    says how many cache rows a row needs (``0``: none — the kernel then
    fetches nothing for it and returns zeros); the dense path reads
    every row whatever it says, and a row of length 0 gets garbage that
    its caller never reads.

    ``ring`` (needs ``window``): the ``max_len`` rows of ``ck`` / ``cv``
    are a RING, position ``p`` in row ``p % max_len``, at least ``window
    + g - 1`` rows long.  Every mask then goes by the position a row
    HOLDS: the newest one congruent to it that the chunk has reached.

    ``seg_q``/``seg_k`` fold the sequence-packing mask in: query ``i``
    additionally requires ``seg_q[b, i] == seg_k[b, j]`` (the
    block-diagonal term — packed documents teacher-forced through the
    decode path never attend each other; ``utils.data.pack_documents``).
    Dense path only: the flash decode kernel has no segment hook, so
    segments force the masked einsum (the didactic fallback).

    ``use_flash=None`` auto-dispatches the Pallas decode kernel on TPU
    when the shapes are eligible (:func:`_flash_decode_eligible`) — its
    grid is the blocks inside each row's RUNTIME length, so per-step
    cost follows the rows that hold a token instead of streaming all
    ``max_len`` rows the way this dense einsum does; the dense path
    masks instead.  Pass True/False to force (True off-TPU runs
    interpret mode — tests).

    ``k_scale``/``v_scale``: ``ck``/``cv`` are int8 QuantKVCache buffers
    with per-(position, head) scales.  The kernel path (one scalar
    ``pos0``: ``generate``) dequantizes block-wise in VMEM — HBM moves
    int8 bytes; the dense path dequantizes up front, and is the path
    of an int8 POOL (per-row ``pos0`` or ``slots``)."""
    on_tpu = jax.devices()[0].platform == "tpu"
    if seg_q is not None or seg_k is not None:
        if seg_q is None or seg_k is None:
            raise ValueError(
                "segment-masked cache attention needs BOTH seg_q and "
                "seg_k (query and cache segment planes)"
            )
        if use_flash:
            raise ValueError(
                "the flash decode kernel has no segment-mask hook; "
                "segment-packed attention runs the dense path "
                "(use_flash=False or leave it to auto-dispatch)"
            )
        use_flash = False
    if use_flash is None:
        use_flash = on_tpu and _flash_decode_eligible(
            q.shape, ck, window, quant=k_scale is not None,
            per_row=jnp.ndim(pos0) == 1 or slots is not None,
        )
    if use_flash:
        from torchgpipe_tpu.ops.flash_attention import (
            flash_decode_attention,
        )

        return flash_decode_attention(
            q, ck, cv, pos0, window=window, k_scale=k_scale,
            v_scale=v_scale, slots=slots, lengths=lengths,
            ring=ring, interpret=not on_tpu,
        )
    if slots is not None:
        # One row, one slot: each row attends over ITS slot's
        # ``max_len`` cache rows, read from the bank by a dynamic slice
        # that feeds the row's own einsums — no copy of the rows'
        # slots, let alone of the bank.
        quant = k_scale is not None
        return jnp.concatenate([
            _attend_row(
                q[i:i + 1], _slot_rows(ck, slots[i]),
                _slot_rows(cv, slots[i]), pos0[i:i + 1],
                _slot_rows(k_scale, slots[i]) if quant else None,
                _slot_rows(v_scale, slots[i]) if quant else None,
                window=window, ring=ring,
            )
            for i in range(q.shape[0])
        ], axis=0)
    if k_scale is not None:
        ck, cv = _dequant_rows(ck, k_scale), _dequant_rows(cv, v_scale)
    b, g, nh, hd = q.shape
    max_len = ck.shape[1]
    nkv = ck.shape[2]
    r = nh // nkv
    qg = q.reshape(b, g, nkv, r, hd)
    scores = jnp.einsum(
        "bqgrd,bsgd->bgrqs", qg.astype(jnp.float32), ck.astype(jnp.float32)
    ) * (hd ** -0.5)
    # [B', g, 1] query positions with B' = b (per-row pos0) or 1
    # (shared scalar) — one mask either way; B'=1 broadcasts exactly as
    # the scalar-only [1, 1, 1, g, L] mask did.
    qpos = (
        jnp.asarray(pos0).reshape(-1, 1, 1)
        + jnp.arange(g)[None, :, None]
    )
    idx = jnp.arange(max_len)[None, None, :]      # [1, 1, max_len]
    if ring:
        # The position row ``idx`` of the ring holds: the newest one at
        # or before the chunk's last that is congruent to it (negative:
        # none yet).  Rows the chunk has not written yet hold a position
        # out of every query's band, and are labelled past it.
        last = qpos[:, -1:, :]                    # [B', 1, 1]
        idx = last - jnp.mod(last - idx, max_len)
    valid = idx <= qpos                           # [B', g, max_len]
    if ring:
        valid &= idx >= 0
    if window is not None:
        valid &= idx > qpos - window
    if seg_q is not None:
        # Block-diagonal packing term: [b, g, 1] == [b, 1, max_len].
        valid = valid & (seg_q[:, :, None] == seg_k[:, None, :])
    scores = jnp.where(valid[:, None, None, :, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrqs,bsgd->bqgrd", p, cv.astype(jnp.float32))
    return out.reshape(b, g, nh * hd)


def _decode_chunk(
    cfg: TransformerConfig,
    block_params: List[Pytree],
    x: jnp.ndarray,              # [b, g, dim] — embedded token chunk
    cache: Any,
    mlp_layer: Optional[Any] = None,
) -> Tuple[jnp.ndarray, Any]:
    """``g`` consecutive tokens through all blocks in ONE pass,
    reading+extending the cache — the batched generalization of
    :func:`_decode_step` (same math per position; ``g=1`` agrees with it
    exactly, tested).  This is what makes speculative verification a
    single MXU matmul per block instead of γ sequential cache reads.
    Plain and quantized caches, and the cache of a model that mixes
    layer types, whose window layers hold rings (``g = 1`` there: the
    one column lands at ``pos0 % rows``).  ``cache_mode='ring'``'s one
    ring for all layers is :func:`_decode_step`'s; the speculative path
    that needs chunks rolls positions back, which a ring's slot reuse
    cannot undo."""
    kv_cache.refuse_state(cfg, "the shared-frontier decode (generate, "
                          "speculative verification)")
    g = x.shape[1]
    pos0 = cache.length
    new = []
    for i, (p, layer) in enumerate(zip(block_params, kv_cache.layers(cache))):
        if cfg.mla is not None:
            h = _block_norm(cfg, p, "ln1", x)
            q_nope, q_pe, *rows = mla.project(cfg, p, h, pos0)
            layer = kv_cache.write_columns(layer, rows, pos0)
            attn = mla.attend(cfg, p, q_nope, q_pe, *layer[:2], pos0)
        else:
            q, *rows = _block_qkv(cfg, p, x, pos0, i)
            ring = kv_cache.ring_layer(cfg, i)
            at = pos0
            if ring:
                if g != 1:
                    raise NotImplementedError(
                        f"a chunk of {g} tokens at one shared position "
                        "may straddle a ring's end; window layers' rings "
                        "take chunks through decode_slots (per-row "
                        "scatter), one token here"
                    )
                at = jnp.mod(pos0, layer[0].shape[1])
            layer = kv_cache.write_columns(layer, rows, at)
            ck, cv, cks, cvs = layer
            # An int8 layer's banks (cks/cvs non-None) go to the attend
            # AS-IS: the flash decode kernel dequantizes block-wise in
            # VMEM (int8 HBM traffic); the dense path dequantizes at the
            # attend instead.
            with _attn_scope(_window(cfg, i)):
                attn = _attend_chunk(
                    q, ck, cv, pos0, _window(cfg, i), k_scale=cks,
                    v_scale=cvs, ring=ring,
                )
        x = _block_attn_out(cfg, p, x, attn, mlp_layer)
        new.append(layer)
    return x, kv_cache.rebuild(cache, new, pos0 + g)


def _slot_rows(bank: jnp.ndarray, slot: jnp.ndarray) -> jnp.ndarray:
    """``bank[slot:slot + 1]`` — ONE slot of a cache bank, leading axis
    kept — as a dynamic slice.  Where the decode kernel does not run
    (it reads ``bank[slots[i]]`` through its index map) the compact
    ``decode_slots`` reads each row's slot this way — the latent pool
    always — and not as ``bank[slots]``: the TPU compiler
    serves that gather of whole slots by first copying the ENTIRE bank
    in ``max_len`` pieces (1.3 ms a bank of a 6 GiB pool: 17 of a
    compact prefill step's 45 ms), and a copy of the R slots alone
    (one concatenate of R slices) still costs a quarter of a
    millisecond a row and layer pair; a slice that feeds the row's own
    einsums costs nothing beside them (my chip runs, PR 27)."""
    return lax.dynamic_slice_in_dim(bank, slot, 1, axis=0)


@functools.partial(jax.jit, static_argnames=("window", "ring"))
def _attend_row(
    q: jnp.ndarray, ck: jnp.ndarray, cv: jnp.ndarray, pos0: jnp.ndarray,
    k_scale: Optional[jnp.ndarray], v_scale: Optional[jnp.ndarray],
    *, window: Optional[int], ring: bool = False,
) -> jnp.ndarray:
    """One row of the compact ``decode_slots`` where the kernel does
    not run (off TPU, an int8 pool, shapes it cannot tile): the dense
    :func:`_attend_chunk` over one slot's rows.  Jitted so that the
    ``R`` calls a layer (same shapes, every layer) are traced ONCE and
    lowered as calls of one function — unrolled bare they tripled the
    prefill program's trace-and-lower time, which is set-up time at
    every start of an engine; XLA inlines the calls."""
    return _attend_chunk(
        q, ck, cv, pos0, window, use_flash=False,
        k_scale=k_scale, v_scale=v_scale, ring=ring,
    )


@functools.partial(jax.jit, static_argnames=("cfg",))
def _attend_latent_row(
    cfg: TransformerConfig, wkv_b: jnp.ndarray, q_nope: jnp.ndarray,
    q_pe: jnp.ndarray, ckv: jnp.ndarray, kpe: jnp.ndarray,
    pos0: jnp.ndarray,
) -> jnp.ndarray:
    """:func:`_attend_row` for a latent cache: ``mla.attend`` over one
    slot's rows, jitted for the same reason."""
    return mla.attend(cfg, {"wkv_b": wkv_b}, q_nope, q_pe, ckv, kpe, pos0)


def _attend_latent(
    cfg: TransformerConfig,
    p: Pytree,
    q_nope: jnp.ndarray,         # [b, g, H, n]
    q_pe: jnp.ndarray,           # [b, g, H, r] rotated
    ckv: jnp.ndarray,            # [slots, max_len, c] latent bank
    kpe: jnp.ndarray,            # [slots, max_len, r]
    pos0: jnp.ndarray,           # [b] — first query's position
    slots: Optional[jnp.ndarray] = None,    # [b] — row i reads ckv[slots[i]]
    lengths: Optional[jnp.ndarray] = None,  # [b] — cache rows a row needs
) -> jnp.ndarray:
    """:func:`_attend_chunk` for a latent pool, the one place that
    decides how ``decode_slots`` attends over it: ``[b, g, H * v]``, row
    ``i``'s ``g`` queries against the rows of ITS slot (``slots=None``:
    row ``i`` is slot ``i``).

    On a TPU, for shapes it tiles (:func:`_latent_decode_eligible`), the
    Pallas latent decode kernel between ``mla.attend``'s own absorbed
    einsums: it fetches the blocks of each row's slot inside
    ``lengths`` through its index maps (the banks are its operands as
    they lie; nothing for a row of length 0, whose output is zeros) and
    each latent tile once for scores and output alike.  Elsewhere
    ``mla.attend`` over all ``max_len`` rows whatever ``lengths`` says,
    a row at a time through a dynamic slice of the banks in the compact
    form (:func:`_slot_rows`): the off-TPU path, and the oracle."""
    if _latent_decode_eligible(q_nope.shape, ckv, kpe):
        from torchgpipe_tpu.ops.flash_attention import (
            latent_decode_attention,
        )

        wk, wv = mla.absorbed_halves(cfg, p)
        with jax.named_scope("mla.scores"):
            q_lat = mla.absorb_queries(q_nope, wk, ckv.dtype)
        o_lat = latent_decode_attention(
            q_lat, q_pe, ckv, kpe, pos0, sm_scale=mla.score_scale(cfg.mla),
            slots=slots, lengths=lengths,
            interpret=jax.devices()[0].platform != "tpu",
        )
        with jax.named_scope("mla.out"):
            return mla.expand_output(o_lat, wv)
    if slots is None:
        return mla.attend(cfg, p, q_nope, q_pe, ckv, kpe, pos0)
    return jnp.concatenate([
        _attend_latent_row(
            cfg, p["wkv_b"], q_nope[i:i + 1], q_pe[i:i + 1],
            _slot_rows(ckv, slots[i]), _slot_rows(kpe, slots[i]),
            pos0[i:i + 1],
        )
        for i in range(q_nope.shape[0])
    ], axis=0)


def _mixer_layer(
    cfg: TransformerConfig, p: Pytree, x: jnp.ndarray, tail: jnp.ndarray,
    state: jnp.ndarray, slots: Optional[jnp.ndarray], n_valid: jnp.ndarray,
    pos0: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``(x + mixer(ln1(x)), tails, states)`` of one mixer layer of a
    :func:`decode_slots` call: ``tail`` / ``state`` are the layer's banks
    over the pool's slots, handed back with the rows' new ones in their
    slots (the compact form scatters them; a padded row, ``n_valid =
    0``, writes nothing, as its K/V rows do not)."""
    from torchgpipe_tpu.models import ssm

    # The barriers keep one layer's state rows alive at a time: without
    # them the compiler gathers every layer's rows early and scatters
    # them late, holding 0.4 GiB of float32 rows a mixer layer at once
    # (the compact prefill program of the cell's pool, described-chip
    # compile).
    x, tail, state = lax.optimization_barrier((x, tail, state))
    rows = (slice(None) if slots is None else slots)
    out, new_tail, new_state = ssm.mixer(
        cfg, p, _block_norm(cfg, p, "ln1", x), tail[rows], state[rows],
        n_valid, pos0 == 0)
    if slots is not None:
        dst = jnp.where(n_valid > 0, slots, tail.shape[0])
        new_tail = tail.at[dst].set(new_tail, mode="drop")
        new_state = state.at[dst].set(new_state, mode="drop")
    return lax.optimization_barrier(
        (x + out.astype(x.dtype), new_tail, new_state))


def decode_slots(
    cfg: TransformerConfig,
    params: Pytree,
    tokens: jnp.ndarray,         # [S, g] int32 — per-slot token chunks
    cache: Any,                  # KVCache/QuantKVCache over S slots
    lengths: jnp.ndarray,        # [S] int32 — per-slot sequence frontiers
    n_valid: jnp.ndarray,        # [S] int32 — valid tokens this call (0 = no-op row)
    moe: Optional[Any] = None,
    slots: Optional[jnp.ndarray] = None,  # [R] int32 — row i IS slot slots[i]
    expert_counts: bool = False,
    logits_at: Optional[jnp.ndarray] = None,  # [S] int32 — one position a row
) -> Tuple:
    """The SLOT-MASKED decode step: ``g`` tokens per slot through all
    blocks, each slot at its OWN position ``lengths[i]``, with row
    ``i``'s tokens ``j >= n_valid[i]`` masked no-ops (their K/V writes
    are dropped, their outputs garbage that the caller never reads).
    Returns ``(logits [S, g, vocab] f32, new cache, lengths + n_valid)``.

    This is the one compiled body the serving engine's two programs
    share (``torchgpipe_tpu.serving.engine``): chunked prefill IS this
    step teacher-forcing prompt chunks (``g = prefill_chunk``), decode
    IS this step at ``g = 1`` — request churn changes only the VALUES of
    ``tokens``/``lengths``/``n_valid``, never a shape, so arbitrary
    admission/eviction traffic reuses one program per entry point.

    ``slots`` makes the batch COMPACT: ``tokens`` / ``n_valid`` then
    hold ``R`` rows (any ``R``, not the pool's ``S``) and row ``i`` IS
    slot ``slots[i]`` of the pool — it reads its frontier
    ``lengths[slots[i]]``, scatters its K/V rows into the pool at
    ``(slots[i], frontier + j)`` under the same drop-when-masked rule
    (every other slot stays bit-untouched, and a donated pool is still
    updated in place), attends over that slot's cache rows only (the
    decode kernel's index map picks the slot, the dense path a dynamic
    slice of the pool a row: never a copy of it) and returns ``logits
    [R, g, vocab]`` with ``lengths`` advanced at ``slots``.  The work is then ``R x g`` positions whatever the
    pool's size: the engine's chunked prefill runs this form over the
    rows that are prefilling.  A padded row is any valid slot index
    with ``n_valid = 0`` (it writes nothing and advances nothing; a
    slot may appear again among the padded rows).  Per-row math is that
    of the pool-wide form (``slots=None``: row ``i`` is slot ``i``),
    operation for operation.

    Mechanics (vs :func:`_decode_chunk`, which this generalizes):

    * positions are a ``[S]`` vector — rope, the causal mask, and the
      learned-position gather all take per-row offsets;
    * cache writes are scatters at ``lengths[i] + j`` with out-of-range
      indices for masked tokens (``mode='drop'``): a no-op row's cache
      is bit-untouched, the property the slot-recycling tests pin;
    * the attend is :func:`_attend_chunk` with the ``[S]`` frontiers:
      on a TPU, for shapes it tiles, the Pallas decode kernel, which
      fetches the blocks of each row's slot up to that row's own
      frontier and nothing for a row with ``n_valid == 0`` (what it
      reads is the rows that hold a token, block-rounded, not
      ``S x max_len``); elsewhere, and for an int8 pool, the dense
      einsum over all ``max_len`` rows of every row's slot;
    * ``cache.length`` is IGNORED (per-slot frontiers live in
      ``lengths``); the returned cache carries ``lengths + n_valid``
      summed into its scalar only for schema compatibility.

    Plain and quantized caches, and the :class:`LatentCache` of a
    ``cfg.mla`` model (same write and mask rules on its two banks; the
    attend is :func:`_attend_latent`: on a TPU the latent decode kernel
    over the blocks inside each row's frontier, elsewhere ``mla.attend``
    over all of the slot's latent rows), and the cache of a model that
    mixes layer types
    (``kv_cache.layer_rows``): a window layer's rows are a ring of at
    least ``window + g - 1`` rows, written at ``(frontier + j) % rows``
    and read under the mask of the position each row holds, so a
    recycled slot's stale rows are as dead there as anywhere (a row
    holds a position of its new tenant or one before 0).
    ``cache_mode='ring'``'s one ring for all layers is ``generate``'s.

    A hybrid model (``cfg.layer_pattern``) walks its pattern: an
    attention layer is the above without a feed-forward, an expert
    layer a feed-forward alone, and a mixer layer (``models.ssm.mixer``)
    continues each row's conv tail and recurrent state of its
    :class:`~.kv_cache.HybridCache` from the row's slot: zero where the
    row's frontier is 0 (a new tenant), untouched where the row does
    nothing (``n_valid = 0``; a padded row writes nothing back either).

    ``logits_at`` (a position ``< g`` a row) computes the head at that
    position of each row alone: the logits are then ``[S, 1, vocab]``.

    ``expert_counts=True`` appends a fourth result: ``int32 [expert
    layers, held]``, the tokens this call routed to each expert the
    layer holds (``MoEConfig.held``; masked positions not counted), in
    block order.
    """
    embed_p, block_p, head_p = _split_params(cfg, params)
    mlp_layer = _mlp_layer_for(cfg, moe)
    S, g = tokens.shape          # rows of THIS call (R under ``slots``)
    hybrid = isinstance(cache, kv_cache.HybridCache)
    kv = cache.kv if hybrid else cache
    L = _cache_rows(kv)
    counts: Optional[List[jnp.ndarray]] = [] if expert_counts else None
    compact = slots is not None
    slot_of = slots if compact else jnp.arange(S)       # [S] row -> slot
    pos0 = lengths[slots] if compact else lengths       # [S] row frontiers
    x = _embed(cfg, embed_p, tokens, pos0)
    j = jnp.arange(g)[None, :]                          # [1, g]
    # Write positions: row i token j lands at pos0[i]+j when valid,
    # at L (out of range -> dropped) when masked.
    wpos = jnp.where(j < n_valid[:, None], pos0[:, None] + j, L)
    valid = j < n_valid[:, None]                        # [S, g]
    # Cache rows a row's attention needs: through its chunk's last
    # position, none for a row that does nothing in this call.
    live = jnp.where(n_valid > 0, jnp.minimum(pos0 + g, L), 0)
    at = kv_cache.scatter_index(slot_of, wpos)
    # The same two for the rings of a model that mixes layer types:
    # the frontier itself, which no ring clips, and by ring length the
    # columns modulo the ring (its own length where masked).
    ring_live, ring_at = None, {}
    new = []
    banks = kv_cache.layers(kv)
    states = iter(zip(cache.conv, cache.ssm)) if hybrid else None
    new_conv, new_ssm = [], []
    for i, p in enumerate(block_p):
        kind = cfg.layer_type(i)
        if kind == "mixer":
            x, tail, st = _mixer_layer(cfg, p, x, *next(states), slots,
                                       n_valid, pos0)
            new_conv.append(tail)
            new_ssm.append(st)
            continue
        if kind == "experts":
            h = _block_norm(cfg, p, "ln1", x)
            x = x + _mlp_out(cfg, p, h, mlp_layer, valid, counts)
            continue
        layer = next(banks)
        if cfg.mla is not None:
            h = _block_norm(cfg, p, "ln1", x)
            q_nope, q_pe, *rows = mla.project(cfg, p, h, pos0)
            layer = kv_cache.write_scattered(layer, rows, at)
            attn = _attend_latent(
                cfg, p, q_nope, q_pe, *layer[:2], pos0, slots=slots,
                lengths=live,
            )
        else:
            q, *rows = _block_qkv(cfg, p, x, pos0, i)
            window, ring = _window(cfg, i), kv_cache.ring_layer(cfg, i)
            if ring:
                R = layer[0].shape[1]
                if R < min(window + g - 1, L):
                    raise ValueError(
                        f"layer {i}'s ring of {R} rows does not hold its "
                        f"window of {window} beside a chunk of {g}: size "
                        f"the cache with init_cache(..., chunk={g})"
                    )
                if R not in ring_at:
                    ring_live = jnp.where(n_valid > 0, pos0 + g, 0)
                    ring_at[R] = kv_cache.scatter_index(
                        slot_of,
                        jnp.where(valid, jnp.mod(pos0[:, None] + j, R), R))
            layer = kv_cache.write_scattered(
                layer, rows, ring_at[R] if ring else at)
            ck, cv, cks, cvs = layer
            # Each row over ITS slot's rows, the written ones included,
            # up to its own frontier: on a TPU the decode kernel reads
            # the blocks inside ``live`` through its index maps (the
            # whole bank is its operand, nothing of it is sliced or
            # copied) and skips the rows with nothing to do; elsewhere
            # the dense einsum, a row at a time in the compact form.
            with _attn_scope(window):
                attn = _attend_chunk(
                    q, ck, cv, pos0, window, k_scale=cks, v_scale=cvs,
                    slots=slots, lengths=ring_live if ring else live,
                    ring=ring,
                )
        x = _block_attn_out(cfg, p, x, attn, mlp_layer, valid, counts)
        new.append(layer)
    new_lengths = (
        lengths.at[slots].add(n_valid) if compact else lengths + n_valid
    )
    length = jnp.sum(new_lengths).astype(jnp.int32)  # schema slot only
    out_cache = kv_cache.rebuild(kv, new, length)
    if hybrid:
        out_cache = kv_cache.HybridCache(out_cache, new_conv, new_ssm, length)
    if logits_at is not None:
        x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
    out = (_logits(cfg, head_p, x), out_cache, new_lengths)
    if expert_counts:
        if not counts:
            raise ValueError(
                "expert_counts=True needs expert layers that say what "
                "they hold (moe=MoEConfig(held=...))"
            )
        out += (jnp.stack(counts),)
    return out


def row_frontiers(
    prompt_len: int,
    out: jnp.ndarray,            # [b, T] int32 — tokens from generate()
    eos_id: Optional[int] = None,
) -> jnp.ndarray:
    """Per-row TRUE cache frontiers after a first-turn :func:`generate`
    call with ``return_state=True``: ``prompt_len`` plus the tokens the
    row actually wrote — everything up to and INCLUDING its first
    ``eos_id`` (the finishing step writes its eos K/V; the frozen eos
    padding after it is a masked no-op that never lands in the cache).
    Feed the result to ``generate(..., cache=..., row_lengths=...)`` to
    continue each row at its own frontier; LATER turns return updated
    frontiers directly (the row-mode ``return_state`` 3-tuple), so this
    helper is only needed once, after the shared-scalar first turn."""
    b, T = out.shape
    if eos_id is None:
        return jnp.full((b,), prompt_len + T, jnp.int32)
    is_eos = out == eos_id
    n = jnp.where(is_eos.any(axis=1), jnp.argmax(is_eos, axis=1) + 1, T)
    return (prompt_len + n).astype(jnp.int32)


def _total_len(s: int, max_new_tokens: int, max_len: Optional[int]) -> int:
    total = (s + max_new_tokens) if max_len is None else max_len
    if total < s + max_new_tokens:
        raise ValueError(
            f"max_len={total} cannot hold prompt ({s}) + "
            f"max_new_tokens ({max_new_tokens})"
        )
    return total


def _check_decodable(cfg: TransformerConfig, positions: int) -> None:
    """Every generation entry point's static validity checks: causal
    config (bidirectional/ViT-style models have no autoregressive
    decode) and the learned-position-table bound.  Lives at the TOP
    level (not just prefill) so the ``cache=`` continuation path — which
    skips prefill — is covered too."""
    if not cfg.causal:
        raise ValueError(
            "the KV-cache generation API is causal by construction; "
            "cfg.causal=False (encoder/ViT-style bidirectional "
            "attention) has no autoregressive decode"
        )
    if cfg.norm_position != "pre":
        raise ValueError(
            "the decode paths compute pre-norm blocks; "
            f"norm_position={cfg.norm_position!r} (BERT-class post-norm) "
            "models are encoders — use the training/apply path"
        )
    _check_max_pos(cfg, positions)


def _check_max_pos(cfg: TransformerConfig, positions: int) -> None:
    """Fail fast when a decode would run past a learned position table:
    ``jnp.take`` CLAMPS out-of-range indices under jit, so position
    ``max_pos`` would silently reuse the last row — degraded output with
    no error.  All lengths here are static, so the check is free."""
    if (
        cfg.pos_emb == "learned"
        and positions + cfg.pos_emb_offset > cfg.max_pos
    ):
        off = (
            f" minus {cfg.pos_emb_offset} reserved rows"
            if cfg.pos_emb_offset
            else ""
        )
        raise ValueError(
            f"this decode reaches position {positions - 1} but the "
            f"learned position table has max_pos={cfg.max_pos} rows"
            f"{off} (GPT-2-class models cannot extend context by "
            "decoding further; shorten prompt + max_new_tokens or "
            "retrain with a larger max_pos)"
        )


def _mlp_layer_for(cfg: TransformerConfig, moe: Optional[Any]) -> Optional[Any]:
    """The feed-forward Layer for blocks whose params carry an ``"mlp"``
    key (the MoE family); None for the dense SwiGLU default."""
    if moe is None:
        return None
    from torchgpipe_tpu.models.moe import moe_mlp

    return moe_mlp(cfg, moe)


def _mlp_out(cfg: TransformerConfig, p: Pytree, h: jnp.ndarray,
             mlp_layer: Optional[Any],
             valid: Optional[jnp.ndarray] = None,
             counts_out: Optional[List[jnp.ndarray]] = None) -> jnp.ndarray:
    """The block's feed-forward on normed states ``h [b, g, dim]``, told
    apart by the block's own keys: an ``"mlp"`` subtree is an expert
    layer (run by ``mlp_layer``), else the dense forms — so one model
    may lead with dense blocks and go on with expert blocks.  With
    ``counts_out`` an expert layer appends its held experts' token
    counts (positions where ``valid`` is False not counted)."""
    if "mlp" in p:
        if mlp_layer is None:
            raise ValueError(
                "these block params carry an 'mlp' feed-forward (MoE "
                "family); pass moe=MoEConfig(...) matching the training "
                "configuration to prefill()/generate()"
            )
        if counts_out is not None:
            out, held = mlp_layer.meta["forward_counts"](p["mlp"], h, valid)
            counts_out.append(held)
        else:
            out, _ = mlp_layer.apply(p["mlp"], (), h, rng=None, train=False)
        return out.astype(h.dtype)
    if "w_fc" in p:  # classic (GPT-2-style) fc -> act -> proj
        hid = _act_fn(cfg.act)(h @ _w(cfg, p, "w_fc") + p["b_fc"])
        return hid @ _w(cfg, p, "w_proj") + p["b_proj"]
    gate = _act_fn(cfg.act)(h @ _w(cfg, p, "w_gate"))
    up = h @ _w(cfg, p, "w_up")
    return (gate * up) @ _w(cfg, p, "w_down")


def _logits(cfg: TransformerConfig, head_params: Pytree,
            x: jnp.ndarray) -> jnp.ndarray:
    h = _block_norm(cfg, head_params, "scale", x)
    # _head_w: own 'w', or the tied embedding table transposed (with the
    # didactic error when neither is present).
    return (h @ _head_w(cfg, head_params)).astype(jnp.float32)


def _filter_logits(
    logits: jnp.ndarray,        # [..., vocab] f32
    temperature: float,
    top_k: Optional[int],
    top_p: Optional[float],
) -> jnp.ndarray:
    """Temperature-scaled logits with top-k / nucleus (top-p) masking
    applied — the distribution ``categorical`` (and the speculative
    accept test) actually samples from.  Filters compose in the usual
    order: scale by temperature, keep the top-k, then keep the smallest
    prefix of the sorted distribution whose cumulative probability
    covers ``top_p`` (the most-probable token always survives)."""
    logits = logits / temperature
    if top_k is not None:
        kth = jnp.sort(logits, axis=-1)[..., -top_k, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    # top_p >= 1.0 is a NO-OP by definition; the cumulative-mass test
    # below would still drop tokens whose probability sits below f32
    # resolution (the exclusive cumsum rounds to exactly 1.0 there) —
    # caught by the property suite.
    if top_p is not None and top_p < 1.0:
        srt = jnp.sort(logits, axis=-1)[..., ::-1]          # desc
        probs = jax.nn.softmax(srt, axis=-1)
        # Exclusive cumulative mass before each sorted slot: slot i stays
        # iff the mass of strictly-better slots is still < top_p.
        cum = jnp.cumsum(probs, axis=-1) - probs
        keep_sorted = cum < top_p
        # Cutoff logit = the smallest kept sorted value; everything below
        # it is outside the nucleus.  Ties at the cutoff are kept (they
        # were interchangeable under the sort).
        n_keep = jnp.sum(keep_sorted, axis=-1, keepdims=True)  # >= 1
        cutoff = jnp.take_along_axis(srt, n_keep - 1, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


def _sample(
    logits: jnp.ndarray,        # [b, vocab] f32
    key: jnp.ndarray,
    temperature: float,
    top_k: Optional[int],
    top_p: Optional[float] = None,
) -> jnp.ndarray:
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, _filter_logits(logits, temperature, top_k, top_p), axis=-1
    ).astype(jnp.int32)


def _attend_full(
    q: jnp.ndarray,          # [b, s, nh, hd] — rope'd
    k: jnp.ndarray,          # [b, s, nkv, hd]
    v: jnp.ndarray,
    window: Optional[int],
    use_flash: Optional[bool] = None,
    seg: Optional[jnp.ndarray] = None,   # [b, s] packed segment ids
) -> jnp.ndarray:
    """Causal (optionally banded) full-sequence attention, GQA-grouped —
    the batched twin of :func:`_attend_chunk` (prefill's one big
    MXU-friendly pass instead of s cache reads).

    ``use_flash=None`` auto-dispatches the Pallas flash kernel on TPU
    (O(block²) score memory — the long-prompt prefill path) where the
    shapes meet its tiling (``ops.flash_attention.supports``: the
    kernel's grid is ``s // block``, so a prompt its blocks do not
    divide takes the dense einsum, as it does off-TPU); pass True/False
    to force (True off-TPU runs the kernel in interpret mode — for
    tests; True at an undivided length raises).  ``seg`` folds the
    sequence-packing block-diagonal term (``seg[i] == seg[j]``) into the
    causal mask — dense path only (the flash kernel has no segment
    hook), mirroring the training path's didactic fallback."""
    b, s, nh, hd = q.shape
    on_tpu = jax.devices()[0].platform == "tpu"
    if seg is not None:
        if use_flash:
            raise ValueError(
                "the flash prefill kernel has no segment-mask hook; "
                "segment-packed attention runs the dense path"
            )
        use_flash = False
    if use_flash is None:
        from torchgpipe_tpu.ops.flash_attention import supports

        use_flash = on_tpu and supports(q.shape, k.shape)
    if use_flash:
        from torchgpipe_tpu.ops.flash_attention import flash_attention

        out = flash_attention(
            q, k, v, causal=True, window=window, interpret=not on_tpu
        )
        return out.reshape(b, s, nh * hd)
    nkv = k.shape[2]
    r = nh // nkv
    qg = q.reshape(b, s, nkv, r, hd)
    scores = jnp.einsum(
        "bqgrd,bsgd->bgrqs", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * (hd ** -0.5)
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    valid = kpos <= qpos
    if window is not None:
        valid &= kpos > qpos - window
    valid = valid[None]                           # [1, s, s]
    if seg is not None:
        valid = valid & (seg[:, :, None] == seg[:, None, :])
    scores = jnp.where(valid[:, None, None, :, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrqs,bsgd->bqgrd", p, v.astype(jnp.float32))
    return out.reshape(b, s, nh * hd)


def prefill(
    cfg: TransformerConfig,
    params: Pytree,
    tokens: jnp.ndarray,          # [b, s] int32 prompt
    max_len: int,
    moe: Optional[Any] = None,
    use_flash: Optional[bool] = None,
    ring: bool = False,
    kv_quant: bool = False,
    chunk: int = 1,
) -> Tuple[jnp.ndarray, Any]:
    """ONE batched full-sequence pass over the prompt (MXU-friendly, no
    per-token loop): computes each block's K/V for all prompt positions,
    banks them in the cache, and returns (last-position logits
    [b, vocab], cache ready for decode at position s).  ``use_flash``
    as in :func:`_attend_full` (auto: Pallas flash kernel on TPU).

    A model that mixes layer types gets the cache of
    ``kv_cache.layer_rows``: each window layer banks its last rows into
    its own ring; ``chunk`` sizes those rings for the longest chunk a
    later :func:`decode_slots` call will write (``init_cache``'s).

    ``ring=True`` (requires ``cfg.attn_window``): the cache is a
    ``[b, attn_window, ...]`` RING per block — only the last ``W``
    prompt positions' K/V are banked (slot ``p % W``), everything a
    windowed decode can ever attend to."""
    embed_p, block_p, head_p = _split_params(cfg, params)
    b, s = tokens.shape
    if s > max_len:
        raise ValueError(f"prompt length {s} exceeds max_len {max_len}")
    _check_decodable(cfg, s)
    if ring:
        kv_cache.refuse_rings(cfg, "ring=True (one ring for all layers)")
    if ring and _window(cfg) is None:
        raise ValueError(
            "ring caches hold exactly the attention window: set "
            "cfg.attn_window to use ring=True"
        )
    if cfg.ssm is not None:
        if ring or kv_quant:
            kv_cache.refuse_state(cfg, "a ring or int8 cache")
        # A hybrid model's prompt is one decode_slots call from an empty
        # cache: the mixer layers' chunked form from a zero state.
        logits, cache, _ = decode_slots(
            cfg, params, tokens, init_cache(cfg, b, max_len),
            jnp.zeros((b,), jnp.int32), jnp.full((b,), s, jnp.int32),
            moe=moe, logits_at=jnp.full((b,), s - 1, jnp.int32))
        return logits[:, 0], cache._replace(
            length=jnp.asarray(s, jnp.int32))
    L = _window(cfg) if ring else max_len
    mlp_layer = _mlp_layer_for(cfg, moe)
    if ring or kv_quant:
        _refuse_mla(cfg, "a ring or int8 cache")
    cache = (
        init_quant_cache(cfg, b, L) if kv_quant
        else init_cache(cfg, b, L, chunk=chunk)
    )
    x = _embed(cfg, embed_p, tokens)
    new = []
    for i, (p, layer) in enumerate(zip(block_p, kv_cache.layers(cache))):
        # The prompt's own rows ARE the rows it attends: one full-
        # sequence attention a block over the s new rows, banked after.
        if cfg.mla is not None:
            h = _block_norm(cfg, p, "ln1", x)
            q_nope, q_pe, *rows = mla.project(cfg, p, h, 0)
            attn = mla.attend(cfg, p, q_nope, q_pe, *rows, 0)
        else:
            q, *rows = _block_qkv(cfg, p, x, 0, i)
            with _attn_scope(_window(cfg, i)):
                attn = _attend_full(q, *rows, _window(cfg, i), use_flash)
        x = _block_attn_out(cfg, p, x, attn, mlp_layer)
        W = layer[0].shape[1]
        if ring or (kv_cache.ring_layer(cfg, i) and s > W):
            # Slot j gets the newest prompt position congruent to j
            # (mod W); never-written slots (s < W) gather garbage that
            # the attention masks by the position a slot holds (< 0).
            jslots = jnp.arange(W)
            p_j = (s - 1) - jnp.mod((s - 1) - jslots, W)
            idx = jnp.clip(p_j, 0, s - 1)
            rows = [jnp.take(r, idx, axis=1) for r in rows]
        new.append(kv_cache.write_columns(layer, rows, 0))
    return _logits(cfg, head_p, x)[:, -1], kv_cache.rebuild(
        cache, new, jnp.asarray(s, jnp.int32))


def _generate_rows(
    cfg: TransformerConfig,
    params: Pytree,
    prompt: jnp.ndarray,                 # [b, s] int32 — this turn's tokens
    max_new_tokens: int,
    *,
    temperature: float,
    top_k: Optional[int],
    top_p: Optional[float],
    eos_id: Optional[int],
    rng: jnp.ndarray,
    moe: Optional[Any],
    cache: Any,
    row_lengths: jnp.ndarray,            # [b] int32 — per-row frontiers
    return_state: bool,
) -> Any:
    """``generate(row_lengths=...)``: multi-turn continuation with every
    row at its OWN cache frontier.  The turn's prompt is absorbed and
    each new token decoded through :func:`decode_slots` — rope, the
    causal mask, and the K/V scatter all take the per-row positions, so
    a row that finished the last turn early never attends over its
    unwritten ``[frontier, length)`` gap (the shared-scalar default
    path's failure mode, see the caveat in :func:`generate`).  Finished
    rows are TRUE no-ops (``n_valid=0`` drops their writes and freezes
    their frontiers).  Returns ``out`` or, with ``return_state``, the
    ``(out, cache, new_row_lengths)`` 3-tuple the next turn feeds back
    in."""
    b, s = prompt.shape
    rl = jnp.asarray(row_lengths, jnp.int32)
    if rl.shape != (b,):
        raise ValueError(
            f"row_lengths must hold one frontier per prompt row "
            f"([{b}]), got shape {tuple(rl.shape)}"
        )
    L = _cache_rows(cache)
    _check_decodable(cfg, L)
    if not isinstance(rl, jax.core.Tracer):
        deepest = int(jax.device_get(rl).max())
        if deepest + s + max_new_tokens > L:
            raise ValueError(
                f"cache buffers hold {L} positions but the deepest row "
                f"(frontier {deepest}) + this turn ({s} prompt + "
                f"{max_new_tokens} new) reaches "
                f"{deepest + s + max_new_tokens}; budget the first "
                "call's max_len for all turns"
            )

    # Absorb this turn's prompt (teacher-forced) at each row's frontier.
    logits_g, cache, rl = decode_slots(
        cfg, params, prompt, cache, rl, jnp.full((b,), s, jnp.int32),
        moe=moe,
    )
    logits0 = logits_g[:, -1]

    def step(carry, _):
        cache, lengths, logits, key, alive = carry
        key, sub = jax.random.split(key)
        tok = _sample(logits, sub, temperature, top_k, top_p)
        if eos_id is not None:
            tok = jnp.where(alive, tok, eos_id)
            # The finishing step's eos IS written (n_valid=1) — the
            # frontier convention row_frontiers pins; rows dead BEFORE
            # this step write nothing and their frontiers freeze.
            n_valid = alive.astype(jnp.int32)
            alive = alive & (tok != eos_id)
        else:
            n_valid = jnp.ones((b,), jnp.int32)
        logits_g, cache, lengths = decode_slots(
            cfg, params, tok[:, None], cache, lengths, n_valid, moe=moe
        )
        return (cache, lengths, logits_g[:, 0], key, alive), tok

    alive0 = jnp.ones((b,), bool)
    (cache, rl, _, rng, alive), toks = lax.scan(
        step, (cache, rl, logits0, rng, alive0), None,
        length=max_new_tokens,
    )
    out = toks.T  # [b, max_new_tokens]
    return (out, cache, rl) if return_state else out


def generate(
    cfg: TransformerConfig,
    params: Pytree,
    prompt: jnp.ndarray,                 # [b, s] int32
    max_new_tokens: int,
    *,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_id: Optional[int] = None,
    rng: Optional[jnp.ndarray] = None,
    max_len: Optional[int] = None,
    moe: Optional[Any] = None,
    cache_mode: str = "full",
    kv_quant: bool = False,
    cache: Optional[Any] = None,
    return_state: bool = False,
    early_exit: bool = False,
    row_lengths: Optional[jnp.ndarray] = None,
) -> Any:
    """Autoregressive decode: returns ``[b, max_new_tokens]`` completions.

    ``temperature=0`` is greedy argmax (no rng needed); otherwise pass
    ``rng`` for temperature/top-k/top-p (nucleus) sampling.  With ``eos_id`` set, rows
    that have emitted it keep emitting ``eos_id`` (frozen — static
    shapes; trim host-side) AND become masked no-ops: a finished row's
    K/V cache stops being written, so its state stays bit-exact at the
    row's true frontier instead of accreting eos padding (the batched-
    serving/continuation fix).  Everything compiles to ONE program:
    prefill scan + decode scan.

    ``early_exit=True`` (needs ``eos_id``) swaps the fixed-length decode
    scan for a bounded ``lax.while_loop`` that STOPS once every row has
    finished — the batch runs to its longest request, not to
    ``max_new_tokens`` (with ``return_state=True`` the returned
    ``cache.length`` shows the actual step count).  Output is identical
    to the scan path (tested); the default stays the scan so the
    single-program jaxpr contract is unchanged.

    ``cache_mode='ring'`` (requires ``cfg.attn_window``): W-slot ring
    caches instead of ``[.., total, ..]`` buffers — O(window) cache
    memory and attention reads per step, bit-equal outputs to the
    masked full-cache path (tested); the HBM-bandwidth win for long
    windowed decode.

    ``kv_quant=True``: int8 K/V storage with per-(position, head)
    symmetric scales, dequantized at the attention read — half the
    cache footprint/traffic of bf16 (a quarter of f32).  Lossy but
    tight (head_dim-wise scales); logits stay close to the fp path and
    greedy decode on well-separated models is unchanged (tested).
    Composes with both cache modes.

    Multi-turn use: ``return_state=True`` returns ``(tokens, cache)``;
    pass that cache (plus the next turn's tokens as ``prompt``) back in
    via ``cache=`` to continue the conversation — the new prompt is
    absorbed through the decode path (teacher-forced), so every cache
    mode composes.  Two-turn decode equals the one-shot run on the
    concatenated prompt (tested).  With ``cache_mode='full'`` the FIRST
    call's ``max_len`` must budget all future turns (fixed buffers;
    ring caches wrap and never run out).

    CAVEAT — continuing after ``eos_id`` finished SOME rows: a finished
    row's K/V stops at its true frontier (masked no-ops), but the
    default continuation appends at the shared scalar ``cache.length``,
    so the dense mask would attend over that row's unwritten gap
    ``[frontier, length)``.  Pass ``row_lengths=`` (per-row frontiers
    from :func:`row_frontiers`) to continue every row at its OWN
    frontier instead — the turn runs through :func:`decode_slots`
    (full caches only) and ``return_state=True`` returns ``(tokens,
    cache, new_row_lengths)``, the 3-tuple later turns feed back in."""
    b, s = prompt.shape
    if cache_mode not in ("full", "ring"):
        raise ValueError(
            f"cache_mode must be 'full' or 'ring', got {cache_mode!r}"
        )
    ring = cache_mode == "ring"
    if ring:
        kv_cache.refuse_rings(cfg, "cache_mode='ring' (one ring for all "
                              "layers)")
    if ring and _window(cfg) is None:
        raise ValueError(
            "cache_mode='ring' holds exactly the attention window: set "
            "cfg.attn_window"
        )
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature sampling needs rng=jax.random.PRNGKey")
    if temperature == 0.0:
        rng = jax.random.PRNGKey(0)  # unused; keeps the scan carry uniform

    if row_lengths is not None:
        if cache is None:
            raise ValueError(
                "row_lengths continues PER-ROW frontiers of an existing "
                "cache: pass cache= from the previous turn's "
                "return_state=True (a first turn has one shared frontier "
                "— no row_lengths needed)"
            )
        if ring:
            raise ValueError(
                "row_lengths continuation runs through decode_slots, "
                "which ring caches defeat (slot = pos % W aliases the "
                "per-row frontiers); use cache_mode='full'"
            )
        if early_exit:
            raise ValueError(
                "early_exit is not supported with row_lengths; the "
                "fixed-length scan already masks finished rows to no-ops"
            )
        if max_len is not None:
            raise ValueError(
                "max_len sizes a NEW cache; row_lengths continuation "
                "runs inside the existing cache buffers (budget the "
                "first call's max_len for all turns)"
            )
        return _generate_rows(
            cfg, params, prompt, max_new_tokens,
            temperature=temperature, top_k=top_k, top_p=top_p,
            eos_id=eos_id, rng=rng, moe=moe, cache=cache,
            row_lengths=row_lengths, return_state=return_state,
        )

    total = _total_len(s, max_new_tokens, max_len)
    _check_decodable(cfg, total)

    embed_p, block_p, head_p = _split_params(cfg, params)
    mlp_layer = _mlp_layer_for(cfg, moe)
    if cache is None:
        logits0, cache = prefill(
            cfg, params, prompt, total, moe=moe, ring=ring,
            kv_quant=kv_quant,
        )
    else:
        # Continuation: absorb this turn's tokens through the decode
        # path (teacher-forced) — exact for every cache layout.
        def absorb(cache, tok):
            x = _embed(cfg, embed_p, tok[:, None], cache.length)
            x, cache = _decode_step(cfg, block_p, x, cache, mlp_layer, ring)
            return cache, _logits(cfg, head_p, x)[:, 0]

        cache, turn_logits = lax.scan(absorb, cache, prompt.T)
        logits0 = turn_logits[-1]

    if early_exit and eos_id is None:
        raise ValueError(
            "early_exit terminates when every row has emitted eos_id; "
            "set eos_id (without it no row ever finishes early)"
        )

    def step(carry, _):
        cache, logits, key, alive = carry
        key, sub = jax.random.split(key)
        tok = _sample(logits, sub, temperature, top_k, top_p)
        if eos_id is not None:
            tok = jnp.where(alive, tok, eos_id)
            was_alive = alive
            alive = alive & (tok != eos_id)
        x = _embed(cfg, embed_p, tok[:, None], cache.length)
        x, new_cache = _decode_step(cfg, block_p, x, cache, mlp_layer, ring)
        if eos_id is not None:
            # Rows already finished BEFORE this step are masked no-ops:
            # their eos feed's K/V write is dropped.
            new_cache = kv_cache.keep_finished_rows(
                new_cache, cache, was_alive, cache.length
            )
        return (new_cache, _logits(cfg, head_p, x)[:, 0], key, alive), tok

    alive0 = jnp.ones((b,), bool)
    if early_exit:
        T = max_new_tokens
        out0 = jnp.full((b, T), eos_id, jnp.int32)

        def w_cond(carry):
            n = carry[0]
            alive = carry[4]
            return (n < T) & jnp.any(alive)

        def w_body(carry):
            n, cache, logits, key, alive, out = carry
            (cache, logits, key, alive), tok = step(
                (cache, logits, key, alive), None
            )
            out = lax.dynamic_update_slice_in_dim(
                out, tok[:, None], n, axis=1
            )
            return (n + 1, cache, logits, key, alive, out)

        n, cache, logits, rng, alive, out = lax.while_loop(
            w_cond, w_body,
            (jnp.zeros((), jnp.int32), cache, logits0, rng, alive0, out0),
        )
        return (out, cache) if return_state else out

    (cache, logits, rng, alive), toks = lax.scan(
        step, (cache, logits0, rng, alive0), None, length=max_new_tokens
    )
    out = toks.T  # [b, max_new_tokens]
    return (out, cache) if return_state else out


def beam_search(
    cfg: TransformerConfig,
    params: Pytree,
    prompt: jnp.ndarray,                 # [b, s] int32
    max_new_tokens: int,
    *,
    num_beams: int = 4,
    eos_id: Optional[int] = None,
    max_len: Optional[int] = None,
    moe: Optional[Any] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Deterministic beam decode: returns ``(tokens [b, max_new_tokens],
    log-probs [b])`` of each prompt's best beam.

    TPU-first shape discipline: beams flatten into the batch dim (the
    ``b*k`` rows decode exactly like :func:`generate`'s batch), every
    step re-orders the KV caches by parent beam with one ``jnp.take``,
    and the whole search is ONE ``lax.scan``.  With ``eos_id``, finished
    beams freeze (further steps append ``eos_id`` at zero additional
    log-prob) AND every finished hypothesis is banked in a per-prompt
    best-finished pool, so a completed sequence can never be lost by
    later beam eviction — the returned beam is the best of (surviving
    beams, banked finished hypotheses).  ``num_beams=1`` degenerates to
    greedy :func:`generate` (tested)."""
    b, s = prompt.shape
    k = num_beams
    if k < 1:
        raise ValueError(f"num_beams must be >= 1, got {k}")
    total = _total_len(s, max_new_tokens, max_len)
    _check_decodable(cfg, total)
    _refuse_mla(cfg, "beam search's reordered cache")
    kv_cache.refuse_state(cfg, "beam search's reordered cache")
    embed_p, block_p, head_p = _split_params(cfg, params)
    mlp_layer = _mlp_layer_for(cfg, moe)
    logits0, cache = prefill(cfg, params, prompt, total, moe=moe)
    vocab = logits0.shape[-1]

    # Seed: the top-k first tokens per prompt; replicate caches k-fold
    # (beam-major rows: prompt i's beams occupy rows i*k .. i*k+k-1).
    logp0 = jax.nn.log_softmax(logits0, axis=-1)          # [b, V]
    seed_lp, seed_tok = lax.top_k(logp0, k)               # [b, k]
    cache = KVCache(
        k=[jnp.repeat(a, k, axis=0) for a in cache.k],
        v=[jnp.repeat(a, k, axis=0) for a in cache.v],
        length=cache.length,
    )

    def flat_decode(cache, tok):
        x = _embed(cfg, embed_p, tok.reshape(b * k, 1), cache.length)
        x, cache = _decode_step(cfg, block_p, x, cache, mlp_layer)
        return cache, _logits(cfg, head_p, x)[:, 0]       # [b*k, V]

    cache, logits = flat_decode(cache, seed_tok)
    beam_lp = seed_lp                                      # [b, k]
    alive0 = (
        seed_tok != eos_id if eos_id is not None
        else jnp.ones((b, k), bool)
    )
    T = max_new_tokens
    hist0 = jnp.zeros((b, k, T), jnp.int32).at[..., 0].set(seed_tok)
    # Finished-hypotheses pool: the best completed sequence per prompt,
    # immune to later beam eviction.
    fin_lp0 = jnp.full((b,), -jnp.inf)
    fin_hist0 = jnp.zeros((b, T), jnp.int32)
    if eos_id is not None:
        seed_fin = jnp.where(seed_tok == eos_id, seed_lp, -jnp.inf)
        j0 = jnp.argmax(seed_fin, axis=-1)
        fin_lp0 = jnp.take_along_axis(seed_fin, j0[:, None], 1)[:, 0]
        fin_hist0 = jnp.take_along_axis(
            hist0, j0[:, None, None], axis=1
        )[:, 0]

    def step(carry, t):
        cache, logits, beam_lp, alive, hist, fin_lp, fin_hist = carry
        logp = jax.nn.log_softmax(logits, -1).reshape(b, k, vocab)
        if eos_id is not None:
            # Dead beams: only the eos continuation, at zero extra cost.
            only_eos = jnp.full((vocab,), -jnp.inf).at[eos_id].set(0.0)
            logp = jnp.where(alive[..., None], logp, only_eos)
        cand = beam_lp[..., None] + logp                   # [b, k, V]
        new_lp, flat_idx = lax.top_k(cand.reshape(b, k * vocab), k)
        parent = flat_idx // vocab                         # [b, k]
        tok = (flat_idx % vocab).astype(jnp.int32)
        # Re-order histories, caches and liveness by parent beam, then
        # record this step's choice at column t.
        rows = (jnp.arange(b)[:, None] * k + parent).reshape(b * k)
        hist = jnp.take(
            hist.reshape(b * k, -1), rows, axis=0
        ).reshape(b, k, -1)
        hist = lax.dynamic_update_slice_in_dim(
            hist, tok[..., None], t, axis=2
        )
        cache = KVCache(
            k=[jnp.take(a, rows, axis=0) for a in cache.k],
            v=[jnp.take(a, rows, axis=0) for a in cache.v],
            length=cache.length,
        )
        if eos_id is not None:
            alive = jnp.take(alive.reshape(b * k), rows).reshape(b, k)
            newly = alive & (tok == eos_id)
            alive = alive & (tok != eos_id)
            # Bank newly-finished hypotheses into the per-prompt pool.
            cand = jnp.where(newly, new_lp, -jnp.inf)      # [b, k]
            j = jnp.argmax(cand, axis=-1)
            cand_lp = jnp.take_along_axis(cand, j[:, None], 1)[:, 0]
            cand_hist = jnp.take_along_axis(
                hist, j[:, None, None], axis=1
            )[:, 0]
            better = cand_lp > fin_lp
            fin_lp = jnp.where(better, cand_lp, fin_lp)
            fin_hist = jnp.where(better[:, None], cand_hist, fin_hist)
        cache, logits = flat_decode(cache, tok)
        return (cache, logits, new_lp, alive, hist, fin_lp, fin_hist), ()

    (cache, logits, beam_lp, alive, hist, fin_lp, fin_hist), _ = lax.scan(
        step,
        (cache, logits, beam_lp, alive0, hist0, fin_lp0, fin_hist0),
        jnp.arange(1, T),
    )
    best = jnp.argmax(beam_lp, axis=-1)                    # [b]
    best_lp = jnp.take_along_axis(beam_lp, best[:, None], axis=1)[:, 0]
    out = jnp.take_along_axis(hist, best[:, None, None], axis=1)[:, 0]
    # The pool wins when a banked finished hypothesis outscores every
    # surviving beam.
    use_fin = fin_lp > best_lp
    out = jnp.where(use_fin[:, None], fin_hist, out)
    if eos_id is not None:
        # Everything after the first eos is eos (banked pool histories
        # carry zeros there; in-set frozen beams already emit eos).
        seen = jnp.cumsum((out == eos_id).astype(jnp.int32), axis=1) > 0
        prev = jnp.concatenate(
            [jnp.zeros((b, 1), bool), seen[:, :-1]], axis=1
        )
        out = jnp.where(prev, eos_id, out)
    return out, jnp.where(use_fin, fin_lp, best_lp)


class SpecStats(NamedTuple):
    """Per-row speculative-decoding accounting (see
    :func:`speculative_generate`): ``rounds`` draft-verify cycles ran,
    ``drafted`` tokens were proposed in them, ``accepted`` passed the
    target's test.  Emitted tokens = ``rounds + accepted`` (each round
    lands its accepted prefix plus one target-sampled token), so the
    per-target-pass speedup of the round trip is
    ``(rounds + accepted) / rounds``."""

    rounds: jnp.ndarray    # [b] int32
    drafted: jnp.ndarray   # [b] int32
    accepted: jnp.ndarray  # [b] int32


def speculative_generate(
    cfg: TransformerConfig,
    params: Pytree,
    draft_cfg: TransformerConfig,
    draft_params: Pytree,
    prompt: jnp.ndarray,                 # [b, s] int32
    max_new_tokens: int,
    *,
    gamma: int = 4,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_id: Optional[int] = None,
    rng: Optional[jnp.ndarray] = None,
    max_len: Optional[int] = None,
    moe: Optional[Any] = None,
    draft_moe: Optional[Any] = None,
    return_stats: bool = False,
) -> Any:
    """Speculative decoding: a cheap ``draft`` model proposes ``gamma``
    tokens per round, the target model judges them all in ONE chunked
    forward (:func:`_decode_chunk` — a single MXU matmul per block
    instead of gamma sequential cache reads), and the accepted prefix
    plus one target-sampled token land at once.  Decode on TPU is
    HBM-bandwidth-bound (every step re-reads the weights), so replacing
    gamma target steps with one chunk pass is a direct bandwidth win at
    typical acceptance rates.

    Output distribution is EXACT (Leviathan et al., arXiv:2211.17192):
    drafts are accepted with probability ``min(1, p/q)`` and rejections
    resample from the normalized residual ``(p-q)+``, so emitted tokens
    are distributed exactly as target-only sampling; with
    ``temperature=0`` both models are deterministic and the output
    equals target-only greedy decode token-for-token (tested against
    :func:`generate` with an arbitrary draft) — up to float ties: the
    chunked verify pass reassociates the same f32 sums the per-token
    path computes, so a position whose top-2 target logits differ by
    less than that reassociation error (~1e-4 relative) may resolve the
    argmax either way.  ``temperature``/
    ``top_k``/``top_p`` apply to BOTH distributions before the accept
    test, matching the filtered target distribution :func:`generate`
    samples from.

    The models may differ in every dimension but must share the
    tokenizer (``vocab``).  Full (non-ring, non-quantized) caches only:
    a rejection rolls ``cache.length`` back to the accepted frontier,
    which slot-reusing ring buffers cannot undo.  Rows are independent
    (per-row acceptance, per-row cache frontiers) via ``vmap`` over a
    batched ``lax.while_loop``.

    Returns ``[b, max_new_tokens]`` tokens, or ``(tokens, stats)`` with
    ``return_stats=True`` (:class:`SpecStats`: per-row rounds / drafted
    / accepted — ``accepted/drafted`` is the acceptance rate that
    decides whether the draft pays for itself)."""
    b, s = prompt.shape
    T = int(max_new_tokens)
    g = int(gamma)
    if g < 1:
        raise ValueError(f"gamma must be >= 1, got {gamma}")
    if cfg.vocab != draft_cfg.vocab:
        raise ValueError(
            "speculative decoding needs a shared tokenizer: target "
            f"vocab {cfg.vocab} != draft vocab {draft_cfg.vocab}"
        )
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature sampling needs rng=jax.random.PRNGKey")
    if rng is None:
        rng = jax.random.PRNGKey(0)  # deterministic path; keys unused
    total = _total_len(s, T, max_len)
    _check_decodable(cfg, total)
    _refuse_mla(cfg, "speculative decoding's rolled-back cache")
    kv_cache.refuse_state(cfg, "speculative decoding's rolled-back cache")
    _refuse_mla(draft_cfg, "speculative decoding's rolled-back cache")
    kv_cache.refuse_rings(cfg, "speculative decoding's rolled-back cache")
    kv_cache.refuse_rings(
        draft_cfg, "speculative decoding's rolled-back cache")
    # The draft decodes to the same frontier (its table clamps just as
    # silently — garbage proposals would only collapse the acceptance
    # rate, with no error).
    _check_decodable(draft_cfg, total)
    # Chunk writes run up to gamma+1 past the accepted frontier before
    # rolling back; pad the buffers so dynamic_update_slice never clamps.
    L = total + g + 1

    embed_p, block_p, head_p = _split_params(cfg, params)
    d_embed_p, d_block_p, d_head_p = _split_params(draft_cfg, draft_params)
    mlp_layer = _mlp_layer_for(cfg, moe)
    d_mlp_layer = _mlp_layer_for(draft_cfg, draft_moe)
    greedy = temperature == 0.0

    # Prefill BOTH models batched, outside the per-row loop: the prompt
    # pass stays one MXU-friendly (optionally flash) forward; only the
    # draft-verify rounds need per-row independence.
    t_logits0, tcache0 = prefill(cfg, params, prompt, L, moe=moe)
    _, dcache0 = prefill(draft_cfg, draft_params, prompt, L, moe=draft_moe)
    rng, sub = jax.random.split(rng)
    tok0_b = _sample(t_logits0, sub, temperature, top_k, top_p)    # [b]
    alive0_b = (
        jnp.ones((b,), bool) if eos_id is None else tok0_b != eos_id
    )
    out0_b = jnp.zeros((b, T), jnp.int32).at[:, 0].set(tok0_b)
    keys = jax.random.split(rng, b)

    def row(
        tok0: jnp.ndarray,       # [] int32 — this row's first token
        out: jnp.ndarray,        # [T] int32 — buffer with out[0] set
        alive: jnp.ndarray,      # [] bool
        key: jnp.ndarray,
        tc: Any,                 # this row's cache slices, batch axis stripped
        dc: Any,
    ):
        tcache = KVCache(
            k=[a[None] for a in tc.k], v=[a[None] for a in tc.v],
            length=tc.length,
        )
        dcache = KVCache(
            k=[a[None] for a in dc.k], v=[a[None] for a in dc.v],
            length=dc.length,
        )

        def cond(carry):
            return carry[0] < T

        def body(carry):
            n, tok, tcache, dcache, out, alive, key, stats = carry
            rounds, drafted, accepted = stats

            # --- draft phase: g proposals + 1 banking step ------------- #
            def dstep(c, _):
                dc, cur, k = c
                x = _embed(draft_cfg, d_embed_p, cur[None, None], dc.length)
                x, dc = _decode_step(
                    draft_cfg, d_block_p, x, dc, d_mlp_layer
                )
                ql = _logits(draft_cfg, d_head_p, x)[0, 0]    # [V]
                k, sub = jax.random.split(k)
                if greedy:
                    nxt = jnp.argmax(ql).astype(jnp.int32)
                    qf = ql
                else:
                    qf = _filter_logits(ql, temperature, top_k, top_p)
                    nxt = jax.random.categorical(sub, qf).astype(jnp.int32)
                return (dc, nxt, k), (nxt, qf)

            (dcache2, _, key), (drafts, q_logits) = lax.scan(
                dstep, (dcache, tok, key), None, length=g + 1
            )
            # drafts[0:g] are the proposals; the g+1-th feed only banked
            # drafts[g-1]'s kv (its sample/dist are never used).

            # --- target phase: ONE chunk over [tok, d_1..d_g] ---------- #
            chunk = jnp.concatenate([tok[None], drafts[:g]])   # [g+1]
            x = _embed(cfg, embed_p, chunk[None, :], tcache.length)
            x, tcache2 = _decode_chunk(cfg, block_p, x, tcache, mlp_layer)
            p_logits = _logits(cfg, head_p, x)[0]              # [g+1, V]

            # --- accept / correct -------------------------------------- #
            if greedy:
                t_argmax = jnp.argmax(p_logits, axis=-1).astype(jnp.int32)
                accs = drafts[:g] == t_argmax[:g]
            else:
                pf = _filter_logits(p_logits, temperature, top_k, top_p)
                p_probs = jax.nn.softmax(pf, axis=-1)          # [g+1, V]
                q_probs = jax.nn.softmax(q_logits, axis=-1)    # [g+1, V]
                key, sub = jax.random.split(key)
                u = jax.random.uniform(sub, (g,))
                d_idx = drafts[:g]
                p_at = jnp.take_along_axis(
                    p_probs[:g], d_idx[:, None], axis=-1
                )[:, 0]
                q_at = jnp.take_along_axis(
                    q_probs[:g], d_idx[:, None], axis=-1
                )[:, 0]
                accs = u * q_at < p_at
            n_acc = jnp.sum(jnp.cumprod(accs.astype(jnp.int32)))

            if greedy:
                last_tok = t_argmax[n_acc]
            else:
                # Bonus (all accepted): sample p[g].  Correction
                # (rejected at n_acc): sample the normalized residual
                # (p-q)+ at n_acc; if the residual vanishes numerically
                # (p≈q — a rejection there is measure-zero but floats),
                # fall back to p itself.
                p_row = p_probs[n_acc]
                q_row = q_probs[jnp.minimum(n_acc, g - 1)]
                resid = jnp.maximum(p_row - q_row, 0.0)
                rsum = jnp.sum(resid)
                corr_row = jnp.where(rsum > 1e-9, resid / rsum, p_row)
                final_row = jnp.where(n_acc == g, p_row, corr_row)
                key, sub = jax.random.split(key)
                last_tok = jax.random.categorical(
                    sub, jnp.log(jnp.maximum(final_row, 1e-38))
                ).astype(jnp.int32)

            rt = (
                jnp.concatenate([drafts[:g], jnp.zeros((1,), jnp.int32)])
                .at[n_acc].set(last_tok)
            )                                                  # [g+1]

            # --- EOS freeze inside the round --------------------------- #
            if eos_id is None:
                rt_eff, alive2 = rt, alive
            else:
                def estep(a, ti):
                    t, i = ti
                    t_eff = jnp.where(a, t, eos_id)
                    a = jnp.where(
                        i <= n_acc, a & (t_eff != eos_id), a
                    )
                    return a, t_eff

                alive2, rt_eff = lax.scan(
                    estep, alive, (rt, jnp.arange(g + 1))
                )

            # --- emit + roll both caches back to the frontier ---------- #
            ii = jnp.arange(g + 1)
            wi = jnp.where(ii <= n_acc, n + ii, T)  # T = dropped
            out = out.at[wi].set(rt_eff, mode="drop")
            frontier = tcache.length + 1 + n_acc
            tcache2 = tcache2._replace(length=frontier)
            dcache2 = dcache2._replace(length=frontier)
            stats = (rounds + 1, drafted + g, accepted + n_acc)
            return (
                n + 1 + n_acc, rt_eff[n_acc], tcache2, dcache2, out,
                alive2, key, stats,
            )

        z = jnp.zeros((), jnp.int32)
        carry = (
            jnp.ones((), jnp.int32), tok0, tcache, dcache, out, alive,
            key, (z, z, z),
        )
        n, _, _, _, out, _, _, stats = lax.while_loop(cond, body, carry)
        return out, stats

    cache_axes = KVCache(k=0, v=0, length=None)
    outs, (rounds, drafted, accepted) = jax.vmap(
        row, in_axes=(0, 0, 0, 0, cache_axes, cache_axes)
    )(tok0_b, out0_b, alive0_b, keys, tcache0, dcache0)
    if return_stats:
        return outs, SpecStats(
            rounds=rounds, drafted=drafted, accepted=accepted
        )
    return outs


def mpmd_params_for_generation(
    model: Any, params: Any, device: Any = None
) -> List[Pytree]:
    """Flatten a ``GPipe(llama(cfg))`` model's per-stage params back to the
    per-layer list :func:`generate` consumes (train with the pipeline,
    decode with the same weights — no conversion).  Stage params live on
    their pipeline devices; decode is single-device, so everything is
    gathered onto ``device`` (default: the first device)."""
    if device is None:
        device = jax.devices()[0]
    out: List[Pytree] = []
    for stage_params in params:
        out.extend(jax.device_put(list(stage_params), device))
    return out


def spmd_params_from_flat(pipe: Any, flat: Any) -> Pytree:
    """The inverse of :func:`spmd_params_for_generation`: assemble an
    ``SpmdGPipe`` params dict from a flat per-layer list (embed,
    blocks..., head) — e.g. an HF import
    (:mod:`torchgpipe_tpu.models.hf_interop`).

    Blocks are grouped into per-stage chain tuples and stacked into the
    engine's ``[n_stages, ...]`` layout (or the interleaved
    ``[n_stages, v, ...]`` round-robin layout).  The head entry lands
    under ``post`` (or ``loss`` for a parametric loss layer) with any
    tied pre-param entries STRIPPED — the engine splices those from
    ``pre`` at apply time, and a duplicated array reference would
    double-count the buffer under ``make_train_step``'s donation (XLA
    rejects donating the same buffer twice).  Returns the placed params
    (``pipe.place``)."""
    flat = list(flat)
    n, v = pipe.n_stages, getattr(pipe, "virtual_stages", 1)
    blocks = flat[1:-1]
    if len(blocks) % (n * v) != 0:
        raise ValueError(
            f"{len(blocks)} block params do not divide into "
            f"n_stages={n} x virtual_stages={v} stage chains"
        )
    per = len(blocks) // (n * v)
    tmap = jax.tree_util.tree_map
    # Global group g (path order) lives at [g % n, g // n] — the inverse
    # of spmd_params_for_generation's unstack rule.  A chain() block
    # (meta kind 'compound') stores per-stage params as a TUPLE of
    # sub-layer dicts; a bare block layer stores the dict itself —
    # mirror whichever this engine was built with.
    is_chain = (
        isinstance(pipe.block.meta, dict)
        and pipe.block.meta.get("kind") == "compound"
    )
    if not is_chain and per != 1:
        raise ValueError(
            f"engine block {pipe.block.name!r} is a single (non-chain) "
            f"layer but the flat list carries {per} blocks per stage"
        )
    groups = [
        tuple(blocks[g * per : (g + 1) * per]) if is_chain else blocks[g]
        for g in range(n * v)
    ]
    if v == 1:
        stacked = tmap(lambda *xs: jnp.stack(xs), *groups)
    else:
        per_stage = [
            tmap(
                lambda *xs: jnp.stack(xs),
                *[groups[c * n + j] for c in range(v)],
            )
            for j in range(n)
        ]
        stacked = tmap(lambda *xs: jnp.stack(xs), *per_stage)
    params: dict = {"pre": flat[0], "blocks": stacked}
    head = dict(flat[-1])
    tie_keys = pipe._tie_post if pipe.post is not None else pipe._tie_loss
    for k in tie_keys:
        head.pop(k, None)
    if pipe.post is not None:
        params["post"] = head
    else:
        params["loss"] = head
    return pipe.place(params)


def spmd_params_for_generation(
    pipe: Any, params: Any, device: Any = None
) -> List[Pytree]:
    """Per-layer list for :func:`generate` from an ``SpmdGPipe`` built via
    ``llama_spmd(cfg, n_stages)`` (optionally with ``chunked_lm_loss``):
    the stacked ``[n_stages, ...]`` block params (or the interleaved
    ``[n_stages, virtual_stages, ...]`` layout, restacked by Megatron's
    round-robin rule) unstack into the flat (embed, blocks..., head)
    order, the head coming from ``post`` or — under a parametric loss
    layer — from ``params['loss']`` (the shared ``_head_init`` schema
    makes them interchangeable).  Everything lands on ``device``
    (default: the first device) — train sharded, decode single-host with
    the same weights."""
    if device is None:
        device = jax.devices()[0]
    tmap = jax.tree_util.tree_map
    v = getattr(pipe, "virtual_stages", 1)
    out: List[Pytree] = [params["pre"]]
    n = pipe.n_stages
    for g in range(n * v):
        # Megatron round-robin: global block g lives on device g % n as
        # its chunk g // n (v=1 degenerates to plain per-stage order).
        stage = tmap(
            lambda a: a[g % n, g // n] if v > 1 else a[g % n],
            params["blocks"],
        )
        if not isinstance(stage, (tuple, list)):
            stage = (stage,)
        out.extend(stage)
    if pipe.post is not None:
        head = params["post"]
    elif "loss" in params:
        head = params["loss"]
    else:
        raise ValueError(
            "no head params: the engine has neither a post layer nor a "
            "parametric loss layer holding the lm head"
        )
    out.append(head)
    placed = [jax.device_put(p, device) for p in out]
    # Tied head (meta['tie_pre'] / TransformerConfig.tie_embeddings): hand
    # decode the same pre-param entries the engine splices at train time,
    # read from the engine's own computed key tuples so the protocol has
    # one source of truth.  Splice AFTER placement, from the placed
    # embedding dict, so the decode device holds ONE copy of the table.
    tie_keys = (
        pipe._tie_post if pipe.post is not None else pipe._tie_loss
    )
    if tie_keys:
        placed[-1] = dict(
            placed[-1], **{k: placed[0][k] for k in tie_keys}
        )
    return placed


__all__ = [
    "KVCache",
    "QuantKVCache",
    "SpecStats",
    "beam_search",
    "decode_slots",
    "init_cache",
    "init_quant_cache",
    "prefill",
    "generate",
    "mpmd_params_for_generation",
    "row_frontiers",
    "speculative_generate",
    "spmd_params_for_generation",
    "spmd_params_from_flat",
]
