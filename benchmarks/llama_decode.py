"""Decode throughput for the KV-cache generator (tokens/sec).

No reference counterpart (the reference is training-only) — this is the
measurement surface for :mod:`torchgpipe_tpu.models.generation`: one
compiled prefill+decode program, steady-state timed.  On TPU the decode
scan is HBM-bandwidth-bound (weights re-read per token); batch rows are
the lever, exactly like production decode servers.

Usage::

    env JAX_PLATFORMS=cpu python -m benchmarks.llama_decode --preset tiny
    python -m benchmarks.llama_decode --preset 1b --batch 8   # on TPU
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
    jax.config.update("jax_platforms", "cpu")

from torchgpipe_tpu.layers import sequential_init
from torchgpipe_tpu.models.generation import generate
from torchgpipe_tpu.models.transformer import TransformerConfig, llama
from torchgpipe_tpu.utils.hw import chip_peak_bf16_flops

PRESETS = {
    # dim, n_layers, n_heads, n_kv_heads, vocab
    "tiny": (128, 4, 4, 2, 512),
    "small": (512, 8, 8, 4, 8192),
    "1b": (2048, 16, 32, 8, 128256),
}


def _host_fetch(out: object) -> None:
    """Materialize generated tokens on the host: the end of every timed
    region here is a value the host holds, which cannot exist before the
    program ran.  The array is tiny ([batch, new_tokens] int32)."""
    import numpy as np

    tokens = out[0] if isinstance(out, tuple) else out
    np.asarray(jax.device_get(tokens))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=sorted(PRESETS), default="tiny")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--window", type=int, default=None,
                    help="sliding-window attention width")
    ap.add_argument("--ring", action="store_true",
                    help="ring KV caches (needs --window): O(window) "
                         "cache memory and per-step reads")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV caches (half the bf16 footprint)")
    ap.add_argument("--draft", choices=sorted(PRESETS), default=None,
                    help="speculative decoding: preset of the DRAFT model "
                         "(untrained weights — greedy acceptance then "
                         "reflects draft/target agreement by luck only, so "
                         "the interesting column is ms/token at a GIVEN "
                         "acceptance; --self-draft shows the ceiling)")
    ap.add_argument("--self-draft", action="store_true",
                    help="speculative decoding with draft == target: 100%% "
                         "acceptance, the per-round overhead ceiling")
    ap.add_argument("--gamma", type=int, default=4,
                    help="drafts per speculative round")
    ap.add_argument("--w8", action="store_true",
                    help="weight-only int8 (models.quant): halve the "
                         "bf16 weight read traffic decode is bound by")
    args = ap.parse_args()

    dim, n_layers, nh, nkv, vocab = PRESETS[args.preset]
    cfg = TransformerConfig(
        vocab=vocab, dim=dim, n_layers=n_layers, n_heads=nh, n_kv_heads=nkv,
        dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        attn_window=args.window,
    )
    b, s, new = args.batch, args.prompt_len, args.new_tokens
    spec = jax.ShapeDtypeStruct((b, s), jnp.int32)
    params, _, _ = sequential_init(llama(cfg), jax.random.PRNGKey(0), spec)
    if args.w8:
        from torchgpipe_tpu.models.quant import (
            quantize_params_int8, quantized_bytes,
        )

        params = quantize_params_int8(cfg, params)
        qb, fb = quantized_bytes(params, cfg.dtype)
        print(f"w8: projection weights {qb / 2**20:.1f} MiB int8 "
              f"(vs {fb / 2**20:.1f} MiB {jnp.dtype(cfg.dtype).name})",
              flush=True)
    prompt = jnp.mod(jnp.arange(b * s).reshape(b, s), vocab).astype(jnp.int32)

    mode = "ring" if args.ring else "full"
    spec_tag = ""
    acc_line = ""
    if args.self_draft or args.draft:
        if args.ring or args.kv_quant:
            raise SystemExit(
                "--draft/--self-draft use full fp caches: speculative "
                "rollback resets the cache frontier, which ring slot "
                "reuse cannot undo and int8 rows would re-quantize; "
                "drop --ring/--kv-quant"
            )
        from torchgpipe_tpu.models.generation import speculative_generate

        if args.self_draft:
            dcfg, dparams = cfg, params
            spec_tag = f", speculative self-draft g{args.gamma}"
        else:
            ddim, dnl, dnh, dnkv, dvocab = PRESETS[args.draft]
            dcfg = TransformerConfig(
                vocab=vocab, dim=ddim, n_layers=dnl, n_heads=dnh,
                n_kv_heads=dnkv, dtype=cfg.dtype, attn_window=args.window,
            )
            dparams, _, _ = sequential_init(
                llama(dcfg), jax.random.PRNGKey(1), spec
            )
            spec_tag = f", speculative draft={args.draft} g{args.gamma}"
        run = jax.jit(
            lambda p, dp, t: speculative_generate(
                cfg, p, dcfg, dp, t, new, gamma=args.gamma,
                return_stats=True,
            )
        )
        out, stats = run(params, dparams, prompt)
        jax.block_until_ready(out)  # compile
        best = float("inf")
        for _ in range(args.steps):
            t0 = time.perf_counter()
            out, stats = run(params, dparams, prompt)
            _host_fetch(out)  # inside the timed region
            best = min(best, time.perf_counter() - t0)
        import numpy as np

        drafted = int(np.sum(np.asarray(stats.drafted)))
        accepted = int(np.sum(np.asarray(stats.accepted)))
        rounds = int(np.sum(np.asarray(stats.rounds)))
        acc_line = (
            f"  acceptance {accepted}/{drafted} "
            f"({100 * accepted / max(drafted, 1):.0f}%), "
            f"{rounds} target passes for {b * new} tokens "
            f"({b * new / max(rounds, 1):.2f} tokens/pass)"
        )
    else:
        run = jax.jit(
            lambda p, t: generate(
                cfg, p, t, max_new_tokens=new, cache_mode=mode,
                kv_quant=args.kv_quant,
            )
        )
        jax.block_until_ready(run(params, prompt))  # compile
        best = float("inf")
        for _ in range(args.steps):
            t0 = time.perf_counter()
            _host_fetch(run(params, prompt))  # inside the timed region
            best = min(best, time.perf_counter() - t0)
    toks = b * new
    wtag = (f", window {args.window} ({mode} cache)"
            if args.window else "")
    wtag += ", int8-kv" if args.kv_quant else ""
    wtag += ", int8-weights" if args.w8 else ""
    wtag += spec_tag
    # Measurement-integrity gate (the decode twin of bench.py's mfu>1
    # check): generating toks tokens costs at least ~2·n_params·toks
    # matmul FLOPs (weights applied once per token per row; speculative
    # runs cost MORE — draft + verify), so a time below that at the
    # chip's published bf16 peak is not a measurement.  Refuse to print it.
    peak = chip_peak_bf16_flops(jax.devices()[0])
    if peak is not None:
        n_params = sum(
            l.size for l in jax.tree_util.tree_leaves(params)
            if hasattr(l, "size")
        )
        # The input embedding's per-token cost is a gather (no matmul
        # FLOPs) — exclude its table so the floor stays a true lower
        # bound (also correct under tied heads, where excluding the
        # shared table merely lowers the floor further).
        n_params = max(n_params - cfg.vocab * cfg.dim, 0)
        floor_s = 2.0 * n_params * toks / peak
        if best < floor_s:
            raise SystemExit(
                f"IMPLAUSIBLE: measured {best * 1e3:.2f} ms for {toks} "
                f"tokens, below the {floor_s * 1e3:.2f} ms physical floor "
                f"(2·{n_params:.3g} params·{toks} tokens at chip peak "
                f"{peak:.3g} FLOP/s) — the timed region cannot have held "
                "the whole decode; not printing"
            )
    print(
        f"{args.preset}{wtag}: batch {b}, prompt {s}, {new} new tokens -> "
        f"{toks / best:.1f} tokens/sec "
        f"({best * 1e3 / new:.2f} ms/token/batch, "
        f"platform {jax.devices()[0].platform})",
        flush=True,
    )
    if acc_line:
        print(acc_line, flush=True)


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
