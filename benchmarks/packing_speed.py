"""Sequence packing: stop paying for padding, measured.

The ragged-corpus rung ``bench.py --packing`` runs TWO comparisons over
ONE corpus of variable-length documents (~50% natural padding):

* **Training** — the SAME documents through the SAME SpmdGPipe tiny
  llama, once PADDED one-per-row (the classic layout) and once PACKED
  by ``utils.data.pack_documents`` (segment-aware attention, packed
  positions).  Packing shrinks the number of fixed ``[B, S]`` blocks by
  ~the padding fraction, so wall-clock REAL tokens/s must move toward
  the ``1 / (1 - pad_fraction)`` bound — the gate is packed tokens/s >=
  1.3x padded at ~50% padding.  Equivalence is asserted, not assumed:
  per-document losses from the packed run must match each document's
  padded-row loss within a pinned tolerance (reduction order differs
  between the two layouts; everything else is the same math — the
  bitwise version of this gate lives in tests/test_packing.py).
* **Serving** — a ragged BURSTY request mix through the serving engine
  with the prefill bucket ladder ON (``prefill_chunk=(1, 2, 4, 8)``)
  vs OFF (single max chunk), reporting TTFT/TPOT percentiles for both.
  Same documents as prompts, same compiled-program discipline — the
  ladder serves short prompts from small programs instead of the max
  chunk's FLOPs.

Usage::

    env JAX_PLATFORMS=cpu python bench.py --packing             # CPU ref
    env JAX_PLATFORMS=cpu python -m benchmarks.packing_speed --json
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
    jax.config.update("jax_platforms", "cpu")

# The pinned packed-vs-padded per-document loss tolerance: the two
# layouts run identical per-position math, but einsum reduction order
# differs between a [B, S] padded row and the packed block it lands in
# (f32 accumulation; documented in docs/tuning.md).
LOSS_TOL = 5e-4


def _corpus(rng: np.random.RandomState, n_docs: int, seq: int, vocab: int):
    """Ragged documents, uniform lengths in [seq//16, seq] — ~50%
    natural padding against one-per-row [seq] blocks."""
    lo = max(2, seq // 16)
    return [
        rng.randint(1, vocab, size=int(rng.randint(lo, seq + 1)))
        .astype(np.int32)
        for _ in range(n_docs)
    ]


def _train_side(args, out):
    import optax

    from torchgpipe_tpu.models.transformer import (
        TransformerConfig,
        llama_spmd,
        packed_cross_entropy_sum,
        per_document_losses,
    )
    from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh
    from torchgpipe_tpu.utils import data as D

    rng = np.random.RandomState(0)
    docs = _corpus(rng, args.docs, args.seq, args.vocab)
    n_real = sum(len(d) for d in docs)

    n = min(args.stages, len(jax.devices()))
    cfg = TransformerConfig(
        vocab=args.vocab, dim=args.dim, n_layers=2 * n, n_heads=4,
        n_kv_heads=2,
    )
    block, pre, post = llama_spmd(cfg, n)
    mesh = make_mesh(n, devices=jax.devices()[:n])
    pipe = SpmdGPipe(
        block, n, mesh, chunks=2, loss_fn=packed_cross_entropy_sum,
        pre=pre, post=post, checkpoint="except_last",
        loss_reduction="sum",
    )
    B = args.batch

    pk = D.pack_documents(docs, args.seq)
    packed = [
        (jax.tree_util.tree_map(jnp.asarray, x),
         jax.tree_util.tree_map(jnp.asarray, y))
        for x, y in D.packed_batches(pk, B)
    ]
    padded = [
        (jnp.asarray(x), jax.tree_util.tree_map(jnp.asarray, y))
        for x, y in D.padded_batches(docs, args.seq, B)
    ]
    out["pad_fraction"] = round(
        1.0 - n_real / (len(padded) * B * args.seq), 4
    )
    out["packed_blocks"] = pk.n_blocks
    out["padded_rows"] = len(docs)

    spec = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), packed[0][0]
    )
    params = pipe.place(pipe.init(jax.random.PRNGKey(0), spec))
    opt = optax.sgd(1e-3)
    step = pipe.make_train_step(opt, donate=False)
    opt_state = pipe.place_tree(opt.init(params))

    def run(batches, params, opt_state):
        # Warmup (compile) outside the timed window, then stream the
        # whole corpus --repeats times.
        x0, y0 = batches[0]
        l, p, s = step(params, opt_state, x0, y0)
        jax.block_until_ready(l)
        t0 = time.perf_counter()
        for _ in range(args.repeats):
            for x, y in batches:
                l, p, s = step(p, s, x, y)
        jax.block_until_ready(l)
        return time.perf_counter() - t0

    dt_packed = run(packed, params, opt_state)
    dt_padded = run(padded, params, opt_state)
    tok_s_packed = args.repeats * n_real / dt_packed
    tok_s_padded = args.repeats * n_real / dt_padded
    out["train"] = {
        "real_tokens": n_real,
        "packed_tok_s": round(tok_s_packed, 1),
        "padded_tok_s": round(tok_s_padded, 1),
        "speedup": round(tok_s_packed / tok_s_padded, 3),
        "bound": round(1.0 / (1.0 - out["pad_fraction"]), 3),
    }
    out["train"]["speedup_ok"] = out["train"]["speedup"] >= args.min_speedup

    # Matched per-document losses: packed blocks vs padded rows through
    # the SAME pipe.apply.
    max_seg = int(pk.segment_ids.max())
    packed_doc = []  # [n_blocks, max_seg] per-(row, segment) mean nll
    for x, y in packed:
        logits = pipe.apply(params, x)
        packed_doc.append(np.asarray(per_document_losses(
            logits, y, x["segment_ids"], max_seg
        )).reshape(B, max_seg))
    packed_doc = np.concatenate(packed_doc, 0)
    padded_doc = []  # per padded row: its document's mean nll
    for xt, yt in padded:
        lg = np.asarray(pipe.apply(params, xt), np.float32)
        logp = np.asarray(jax.nn.log_softmax(lg, -1))
        nll = -np.take_along_axis(
            logp, np.asarray(yt["labels"])[..., None], 2
        )[..., 0]
        w = np.asarray(yt["weights"])
        padded_doc.extend(
            (nll * w).sum(1) / np.maximum(w.sum(1), 1.0)
        )
    diffs = []
    for di, (r, off, _ln) in enumerate(pk.doc_locs):
        segnum = sum(
            1 for rr, oo, _ in pk.doc_locs if rr == r and oo <= off
        )
        diffs.append(abs(float(padded_doc[di]) - float(packed_doc[r, segnum - 1])))
    out["train"]["max_doc_loss_diff"] = float(max(diffs))
    out["train"]["loss_tol"] = LOSS_TOL
    out["train"]["equivalent"] = out["train"]["max_doc_loss_diff"] <= LOSS_TOL
    return out["train"]["equivalent"]


def _serving_side(args, out):
    from torchgpipe_tpu.layers import sequential_init
    from torchgpipe_tpu.models.transformer import TransformerConfig, llama
    from torchgpipe_tpu.serving import Engine

    cfg = TransformerConfig(
        vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2
    )
    params, _, _ = sequential_init(
        llama(cfg), jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((2, 8), jnp.int32),
    )

    def mix(seed):
        """Ragged bursty arrivals: bursts of 1-4 requests, prompt
        lengths 1..16, decode budgets 2..8."""
        r = np.random.RandomState(seed)
        bursts = []
        for _ in range(args.bursts):
            bursts.append([
                (r.randint(0, 64, (int(r.randint(1, 17)),)).astype(np.int32),
                 int(r.randint(2, 9)))
                for _ in range(int(r.randint(1, 5)))
            ])
        return bursts

    def drive(prefill_chunk):
        from torchgpipe_tpu.serving.metrics import ServingMetrics

        eng = Engine(
            cfg, params, num_slots=4, max_len=32,
            prefill_chunk=prefill_chunk,
        )
        # Warmup OUTSIDE the measured window: one request per ladder
        # bucket (served alone, so each bucket's program compiles now),
        # then fresh metrics — the comparison is steady-state TTFT/TPOT,
        # not compile stalls.
        for g in eng.prefill_buckets:
            eng.submit(np.arange(1, g + 1, dtype=np.int32), 2)
            eng.run()
        eng.metrics = ServingMetrics()
        for burst in mix(7):
            for prompt, new in burst:
                eng.submit(prompt, new)
            # Burstiness: a few engine iterations between bursts, so
            # later arrivals land in a busy engine.
            eng.run(max_steps=3)
        eng.run()
        snap = eng.metrics.snapshot()
        return {
            "programs": eng.program_count,
            "ttft_p50_ms": round(1e3 * (snap["ttft_p50"] or 0.0), 3),
            "ttft_p95_ms": round(1e3 * (snap["ttft_p95"] or 0.0), 3),
            "tpot_p50_ms": round(1e3 * (snap["tpot_p50"] or 0.0), 3),
            "tpot_p95_ms": round(1e3 * (snap["tpot_p95"] or 0.0), 3),
            "compile_stats": eng.compile_stats,
        }

    out["serving"] = {
        "ladder_off": drive(8),
        "ladder_on": drive((1, 2, 4, 8)),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--docs", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--bursts", type=int, default=8)
    ap.add_argument("--min-speedup", type=float, default=1.3)
    ap.add_argument("--gate", action="store_true",
                    help="fail (exit 1) when packed tokens/s misses "
                         "--min-speedup; equivalence always gates")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line (bench.py --packing)")
    args = ap.parse_args(argv)

    out: dict = {"bench": "packing", "platform": jax.devices()[0].platform}
    equivalent = _train_side(args, out)
    _serving_side(args, out)

    if args.json:
        print(json.dumps(out))
    else:
        print(json.dumps(out, indent=2))
    if not equivalent:
        print("FAIL: packed-vs-padded per-document losses diverge "
              f"(max diff {out['train']['max_doc_loss_diff']:.2e} > "
              f"{LOSS_TOL})")
        return 1
    if args.gate and not out["train"]["speedup_ok"]:
        print(f"FAIL: packed speedup {out['train']['speedup']} < "
              f"{args.min_speedup}")
        return 1
    return 0


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
