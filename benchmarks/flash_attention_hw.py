"""Hardware validation for the Pallas flash-attention kernels.

Runs BOTH kernel families (resident and streaming) on the live backend —
no ``interpret=True`` — checking numerics against the dense XLA oracle and
timing fwd+bwd.  This is the on-device complement to
``tests/test_flash_attention.py`` (which runs everything in interpret mode
on CPU): a Mosaic lowering difference that interpret mode cannot catch
shows up here as a numerics failure.

Usage::

    python benchmarks/flash_attention_hw.py [--seqs 2048,4096] [--iters 20]

Prints one table row per (seq, variant) with max|err| vs dense for output
and gradients, plus fwd+bwd wall time; exits non-zero on a tolerance
failure so it can gate a hardware CI lane.

Reference anchor: the reference has no fused-attention kernels (it is
CNN-oriented, CUDA streams only) — this is new TPU-native capability; the
oracle-comparison pattern mirrors its transparency tests
(reference: tests/test_transparency.py:7-42).
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp

from torchgpipe_tpu.ops.flash_attention import flash_attention
from torchgpipe_tpu.parallel.ring_attention import full_attention

# The dense oracle is the SAME full_attention the interpret-mode kernel
# tests compare against (tests/test_flash_attention.py), so the hardware
# numbers here and the CI oracle can never drift apart.
dense_attention = full_attention


def _is_oom(e: Exception) -> bool:
    msg = str(e)
    return ("RESOURCE_EXHAUSTED" in msg or "Ran out of memory" in msg
            or "Exceeded hbm capacity" in msg)


def run_case(seq, streaming, b=4, h=16, g=8, d=128, dtype=jnp.bfloat16,
             iters=20):
    """Returns (out_err, grad_err, t_flash_ms, t_dense_ms).

    The dense oracle's score matrix is O(b·h·seq²) — at the long
    sequence lengths the STREAMING kernel exists for (seq > 8k, where
    resident K/V tips past ``_STREAM_BYTES`` of VMEM) it cannot fit HBM.
    A dense-side failure therefore reports ``(nan, nan, t_flash, nan)``
    rather than failing the case: the flash row still proves the kernel
    runs (and how fast) in the regime the oracle cannot enter; numeric
    equivalence in that regime is covered by the interpret-mode CI tests
    (tests/test_flash_attention.py) and by the 2k/4k oracle rows here."""
    ks = jax.random.split(jax.random.PRNGKey(seq), 4)
    q = jax.random.normal(ks[0], (b, seq, h, d), dtype)
    k = jax.random.normal(ks[1], (b, seq, g, d), dtype)
    v = jax.random.normal(ks[2], (b, seq, g, d), dtype)
    do = jax.random.normal(ks[3], (b, seq, h, d), dtype)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, streaming=streaming)
            .astype(jnp.float32) * do.astype(jnp.float32))

    def loss_dense(q, k, v):
        return jnp.sum(
            dense_attention(q, k, v).astype(jnp.float32)
            * do.astype(jnp.float32))

    def maxerr(a, bb):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - bb.astype(jnp.float32))))

    flash_g = jax.jit(jax.value_and_grad(loss_flash, argnums=(0, 1, 2)))
    out_f = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        streaming=streaming))(q, k, v)
    _, grads_f = flash_g(q, k, v)
    jax.block_until_ready((out_f, grads_f))

    t0 = time.perf_counter()
    for _ in range(iters):
        val, grads = flash_g(q, k, v)
    jax.block_until_ready((val, grads))
    t_flash = (time.perf_counter() - t0) / iters * 1e3

    try:
        dense_g = jax.jit(jax.value_and_grad(loss_dense, argnums=(0, 1, 2)))
        out_d = jax.jit(lambda q, k, v: dense_attention(q, k, v))(q, k, v)
        _, grads_d = dense_g(q, k, v)
        jax.block_until_ready((out_d, grads_d))
    except Exception as e:  # noqa: BLE001 — only OOM may stand down
        # Only a resource failure excuses the oracle — any other error
        # (lowering regression, shape bug) must still fail the case, or
        # this script's numerics gate silently stops gating.
        if not _is_oom(e):
            raise
        return float("nan"), float("nan"), t_flash, float("nan")

    out_err = maxerr(out_f, out_d)
    grad_err = max(maxerr(gf, gd) for gf, gd in zip(grads_f, grads_d))

    try:
        t0 = time.perf_counter()
        for _ in range(iters):
            val, grads = dense_g(q, k, v)
        jax.block_until_ready((val, grads))
        t_dense = (time.perf_counter() - t0) / iters * 1e3
    except Exception as e:  # noqa: BLE001 — same OOM excuse as above
        if not _is_oom(e):
            raise
        t_dense = float("nan")  # numerics landed; only the timing OOM'd

    return out_err, grad_err, t_flash, t_dense


def run_decode_case(S, pos0, window, b=8, h=16, g=8, d=128,
                    dtype=jnp.bfloat16, iters=50, chain=256,
                    interpret=False):
    """Decode-kernel row: numerics vs the dense cache read + per-step
    latency at live length ``pos0`` (flash cost should FOLLOW pos0 —
    its K-block loop is length-bounded — while dense streams all S rows
    regardless).

    Every measurement ends on a result fetched to the HOST.  A single
    decode step costs less than one dispatch plus fetch, so each
    measured program CHAINS ``chain`` data-dependent steps in one
    ``lax.scan`` — the per-step cost is the host-fetched total over
    ``chain``, which amortizes the host's share to total/chain."""
    import numpy as np

    from torchgpipe_tpu.models.generation import _attend_chunk
    from torchgpipe_tpu.ops.flash_attention import flash_decode_attention

    ks = jax.random.split(jax.random.PRNGKey(S + pos0), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), dtype)
    ck = jax.random.normal(ks[1], (b, S, g, d), dtype)
    cv = jax.random.normal(ks[2], (b, S, g, d), dtype)

    flash = jax.jit(lambda qq, p: flash_decode_attention(
        qq, ck, cv, p, window=window, interpret=interpret))
    dense = jax.jit(lambda qq, p: _attend_chunk(
        qq, ck, cv, p, window, use_flash=False))

    p0 = jnp.int32(pos0)
    out_f = flash(q, p0)
    out_d = dense(q, p0)
    err = float(jnp.max(jnp.abs(out_f - out_d)))

    def chained(attend):
        # The next step's queries depend on this step's output, so no
        # backend can overlap or elide steps; same shapes throughout.
        def body(c, _):
            o = attend(c, p0)
            c2 = (c + 1e-6 * o.reshape(c.shape)).astype(c.dtype)
            return c2, ()

        def many(qq):
            c, _ = jax.lax.scan(body, qq, None, length=chain)
            return c

        return jax.jit(many)

    def clock(fn):
        best = float("inf")
        for i in range(iters):
            q_i = q * (1.0 + 1e-3 * i)
            t0 = time.perf_counter()
            np.asarray(jax.device_get(fn(q_i)))
            best = min(best, time.perf_counter() - t0)
        return best * 1e3 / chain

    flash_n, dense_n = chained(
        lambda qq, p: flash_decode_attention(
            qq, ck, cv, p, window=window, interpret=interpret)
    ), chained(
        lambda qq, p: _attend_chunk(qq, ck, cv, p, window, use_flash=False)
    )
    np.asarray(jax.device_get(flash_n(q)))  # compile
    np.asarray(jax.device_get(dense_n(q)))
    return err, clock(flash_n), clock(dense_n)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="2048,4096")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--decode", action="store_true",
                    help="run the DECODE kernel rows instead (single-query "
                         "cache attention: numerics + per-step latency at "
                         "1/4, 1/2 and full live length)")
    ap.add_argument("--chain", type=int, default=None,
                    help="decode steps chained per timed program: one "
                         "dispatch plus host fetch adds its cost/chain to "
                         "every per-step number, so the chain must be deep "
                         "enough that the kernel's own sub-ms cost shows "
                         "through (default 256 on TPU; default 1 off-TPU, "
                         "where the kernel runs in interpret mode and a "
                         "256-step scan of it would take minutes)")
    ap.add_argument("--batch", type=int, default=4,
                    help="batch size (drop to 1 for long-seq cases so the "
                         "dense oracle's O(seq^2) scores have a chance)")
    # bf16 inputs with f32 accumulation: output tolerance scales with the
    # bf16 ulp at the magnitudes involved; gradients accumulate over seq.
    ap.add_argument("--tol-out", type=float, default=0.08)
    ap.add_argument("--tol-grad", type=float, default=0.5)
    args = ap.parse_args()

    dev = jax.devices()[0]
    print(f"backend: {dev.platform} ({getattr(dev, 'device_kind', '?')})")
    if args.chain is None:
        # Off-TPU the kernel runs in interpret mode: chaining 256
        # interpreted steps per timed program would take minutes.
        args.chain = 256 if dev.platform == "tpu" else 1
    failed = False
    if args.decode:
        print(f"{'S':>6} {'pos0':>6} {'window':>7} {'out err':>9} "
              f"{'flash ms':>9} {'dense ms':>9}")
        for seq in [int(s) for s in args.seqs.split(",")]:
            for pos0 in (seq // 4, seq // 2, seq - 1):
                for window in (None, 1024):
                    try:
                        err, tf, td = run_decode_case(
                            seq, pos0, window, b=args.batch,
                            iters=args.iters, chain=args.chain,
                            interpret=dev.platform != "tpu")
                    except Exception as e:  # noqa: BLE001 — report, continue
                        print(f"{seq:>6} {pos0:>6} {str(window):>7} "
                              f"FAILED: {type(e).__name__}: {str(e)[:100]}")
                        failed = True
                        continue
                    ok = err <= args.tol_out
                    failed |= not ok
                    print(f"{seq:>6} {pos0:>6} {str(window):>7} "
                          f"{err:>9.4f} {tf:>9.3f} {td:>9.3f}  "
                          f"{'ok' if ok else 'TOLERANCE-FAIL'}")
        sys.exit(1 if failed else 0)
    print(f"{'seq':>6} {'variant':>9} {'out err':>9} {'grad err':>9} "
          f"{'flash ms':>9} {'dense ms':>9}")
    for seq in [int(s) for s in args.seqs.split(",")]:
        for streaming in (False, True):
            name = "streaming" if streaming else "resident"
            try:
                oe, ge, tf, td = run_case(seq, streaming, b=args.batch,
                                          iters=args.iters)
            except Exception as e:  # noqa: BLE001 — report and continue
                print(f"{seq:>6} {name:>9} FAILED: {type(e).__name__}: "
                      f"{str(e)[:120]}")
                failed = True
                continue
            if td != td:  # dense oracle OOM'd: flash-only row, not a failure
                print(f"{seq:>6} {name:>9} {'n/a':>9} {'n/a':>9} "
                      f"{tf:>9.2f} {'OOM':>9}  ok (oracle infeasible)")
                continue
            ok = oe <= args.tol_out and ge <= args.tol_grad
            failed |= not ok
            print(f"{seq:>6} {name:>9} {oe:>9.4f} {ge:>9.4f} "
                  f"{tf:>9.2f} {td:>9.2f}  {'ok' if ok else 'TOLERANCE-FAIL'}")
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
