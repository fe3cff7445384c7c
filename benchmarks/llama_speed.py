"""Llama pipeline speed benchmark — the BASELINE.json north-star config
("Llama-3-8B as nn.Sequential of transformer blocks, 8-stage pipeline").

Two engines over the same model family:

* ``--engine mpmd`` (default): :class:`torchgpipe_tpu.gpipe.GPipe` over the
  flat ``llama()`` layer list — heterogeneous embed/blocks/head stages, any
  balance.
* ``--engine spmd``: :class:`torchgpipe_tpu.spmd.SpmdGPipe` — the whole
  schedule as one compiled program on a ``pp`` mesh axis (needs
  ``n_stages`` devices and ``n_layers % n_stages == 0``).

``--preset llama3-8b`` selects the real Llama-3-8B shape (dim 4096, 32
blocks, 32 heads / 8 KV heads, vocab 128256); the default preset is a
scaled-down shape so the grid runs on small hosts/chips.  The causal-LM
objective shifts tokens by one position.
"""

from __future__ import annotations

import click
import jax
import jax.numpy as jnp

from benchmarks.common import even_balance, run_speed
from torchgpipe_tpu.gpipe import GPipe
from torchgpipe_tpu.models.transformer import (
    TransformerConfig,
    cross_entropy,
    llama,
)

# name -> (n_stages, batch, chunks)
EXPERIMENTS = {
    "pipeline-1": (1, 8, 4),
    "pipeline-2": (2, 16, 4),
    "pipeline-4": (4, 32, 8),
    "pipeline-8": (8, 64, 8),
}

PRESETS = {
    # dim, n_layers, n_heads, n_kv_heads, vocab, mlp_ratio.
    # TransformerConfig.mlp_hidden applies the SwiGLU 2/3 factor, so
    # hidden = 2*ratio*dim/3 (rounded to 128): the published Llama hidden
    # sizes need ratio 5.25 (8B: 14336 = 2*5.25*4096/3) and 6.0
    # (3.2-1B: 8192 = 2*6*2048/3).
    "tiny": (256, 8, 8, 4, 1024, 4.0),
    # ~200M params: big enough for meaningful attention/window timings at
    # long seq, small enough to compile quickly.
    "small": (1024, 12, 16, 8, 32000, 4.0),
    "1b": (2048, 16, 32, 8, 128256, 6.0),
    "llama3-8b": (4096, 32, 32, 8, 128256, 5.25),
}


def causal_lm_loss(out, tokens):
    # Shifted causal objective: predict token t+1 from prefix <= t.
    logits = out[:, :-1, :]
    labels = tokens[:, 1:]
    return cross_entropy(logits, labels)


@click.command()
@click.argument("experiment", type=click.Choice(sorted(EXPERIMENTS)))
@click.option("--preset", type=click.Choice(sorted(PRESETS)), default="tiny")
@click.option("--engine", type=click.Choice(["mpmd", "spmd"]), default="mpmd")
@click.option("--seq", default=1024)
@click.option("--batch", default=None, type=int)
@click.option("--epochs", default=3)
@click.option("--steps", default=10)
@click.option("--bf16/--no-bf16", default=True,
              help="bfloat16 block compute (TransformerConfig.dtype)")
@click.option("--checkpoint", default="except_last",
              type=click.Choice(["always", "except_last", "never"]))
@click.option("--moe-experts", default=0,
              help="replace the dense MLP with a top-k routed MoE of this "
                   "many experts (0 = dense)")
@click.option("--moe-top-k", default=2)
@click.option("--ep", default=1,
              help="expert-parallel mesh axis size (spmd engine; needs "
                   "n_stages*dp*ep*tp devices)")
@click.option("--tp", default=1,
              help="tensor-parallel mesh axis size (spmd engine; needs "
                   "n_stages*dp*ep*tp devices)")
@click.option("--dp", default=1,
              help="data-parallel mesh axis size (spmd engine)")
@click.option("--schedule",
              type=click.Choice(["fill_drain", "1f1b", "interleaved", "zb"]),
              default="fill_drain",
              help="spmd engine schedule: 1f1b runs PipeDream-flush with "
                   "O(n) activation memory; interleaved adds Megatron "
                   "virtual pipeline stages (--virtual-stages chunks per "
                   "device, ~v x smaller bubble); zb splits the backward "
                   "into dx-only B cells + weight-grad W cells that "
                   "back-fill bubbles (checkpoint never|always)")
@click.option("--virtual-stages", default=2,
              help="model chunks per device for --schedule interleaved")
@click.option("--fsdp/--no-fsdp", default=False,
              help="ZeRO-3-style parameter sharding over the dp axis "
                   "(spmd engine; needs --dp > 1)")
@click.option("--moe-dispatch",
              type=click.Choice(["auto", "dense", "sparse", "dropless"]),
              default="auto",
              help="MoE token dispatch: capacity-based one-hot einsums "
                   "(dense), sort-based scatter/gather (sparse), or "
                   "capacity-free ragged grouped matmuls (dropless; needs "
                   "local experts, i.e. --ep 1)")
@click.option("--moe-router", type=click.Choice(["topk", "expert_choice"]),
              default="topk",
              help="routing direction: tokens pick experts (topk) or "
                   "experts pick tokens (expert_choice — perfectly "
                   "balanced by construction; needs --ep 1)")
@click.option("--fused-ce/--no-fused-ce", default=False,
              help="fuse the LM head into a chunked-vocab cross-entropy "
                   "loss layer (both engines): the [tokens, vocab] logits "
                   "are never materialized — the big-vocab memory fix "
                   "(needs --tp 1; dense model only on mpmd)")
@click.option("--attn-window", default=None, type=int,
              help="sliding-window attention: attend iff 0 <= qpos - kpos "
                   "< N (Mistral-style); compute in the flash kernels "
                   "scales with the window, not the sequence length")
@click.option("--autotune/--no-autotune", default=False,
              help="run the static step autotuner (torchgpipe_tpu.tune) "
                   "before timing: sweeps remat policy x micro-batch "
                   "count x CE chunk, prints the frontier, and times the "
                   "best HBM-feasible candidate instead of the CLI flags' "
                   "checkpoint/chunks (spmd engine, fill_drain)")
@click.option("--hbm-budget-gib", default=15.75,
              help="per-chip HBM budget for --autotune feasibility "
                   "(default: the v5e AOT limit)")
def main(experiment, preset, engine, seq, batch, epochs, steps, bf16,
         checkpoint, moe_experts, moe_top_k, ep, tp, dp, schedule,
         virtual_stages, fsdp, moe_dispatch, moe_router, fused_ce,
         attn_window, autotune, hbm_budget_gib):
    n, bsz, chunks = EXPERIMENTS[experiment]
    bsz = batch or bsz
    dim, n_layers, n_heads, n_kv, vocab, mlp_ratio = PRESETS[preset]
    cfg = TransformerConfig(
        vocab=vocab, dim=dim, n_layers=n_layers, n_heads=n_heads,
        n_kv_heads=n_kv, mlp_ratio=mlp_ratio,
        dtype=jnp.bfloat16 if bf16 else jnp.float32,
        tp_axis="tp" if tp > 1 else None,
        attn_window=attn_window,
    )
    if ep > 1 and engine != "spmd":
        raise click.UsageError(
            "--ep needs the spmd engine (expert-parallel mesh axis); the "
            "mpmd engine runs all experts locally"
        )
    if ep > 1 and not moe_experts:
        raise click.UsageError("--ep without --moe-experts has no effect")
    if tp > 1 and engine != "spmd":
        raise click.UsageError(
            "--tp needs the spmd engine (tensor-parallel mesh axis)"
        )
    if (dp > 1 or fsdp) and engine != "spmd":
        raise click.UsageError("--dp/--fsdp need the spmd engine")
    if schedule != "fill_drain" and engine != "spmd":
        raise click.UsageError(
            "--schedule selects the spmd engine's schedule; the mpmd "
            "engine takes GPipe(schedule=...) via its own driver path"
        )
    if fsdp and dp <= 1:
        raise click.UsageError("--fsdp shards over the dp lanes: pass --dp > 1")
    if fused_ce and engine == "mpmd" and moe_experts:
        raise click.UsageError("--fused-ce with the mpmd engine supports "
                               "the dense model only")
    if fused_ce and tp > 1:
        raise click.UsageError("--fused-ce uses local head weights; with "
                               "--tp use the vocab-parallel CE path instead")
    moe = None
    if moe_experts:
        from torchgpipe_tpu.models.moe import MoEConfig

        moe = MoEConfig(
            n_experts=moe_experts, top_k=moe_top_k,
            ep_axis="ep" if ep > 1 else None,
            dispatch=moe_dispatch,
            router=moe_router,
        )
    x = jnp.zeros((bsz, seq), jnp.int32)

    if autotune and (engine != "spmd" or schedule != "fill_drain"):
        raise click.UsageError(
            "--autotune models the spmd engine's fill_drain schedule "
            "(tune_step); pass --engine spmd without --schedule"
        )
    if engine == "spmd":
        tput = _run_spmd(
            cfg, n, chunks, x, epochs, steps, checkpoint, experiment, moe,
            ep, tp, dp, fsdp, schedule,
            virtual_stages if schedule == "interleaved" else 1,
            fused_ce, autotune=autotune, hbm_budget_gib=hbm_budget_gib,
        )
    elif fused_ce:
        # Headless model + parametric chunked-CE loss layer: the head
        # matmul and cross-entropy fuse, [tokens, vocab] logits never
        # materialize (GPipe.value_and_grad_with_loss_params).
        from benchmarks.common import run_epoch_loop
        from torchgpipe_tpu.models.transformer import chunked_lm_loss

        layers = llama(cfg, head=False)
        model = GPipe(
            layers, even_balance(len(layers), n), chunks=chunks,
            checkpoint=checkpoint,
        )
        loss_layer = chunked_lm_loss(cfg)
        in_spec = jax.ShapeDtypeStruct(x.shape, x.dtype)
        params, state = model.init(jax.random.PRNGKey(0), in_spec)
        loss_params, _ = loss_layer.init(jax.random.PRNGKey(2), in_spec)
        carry = {"params": params, "loss_params": loss_params,
                 "state": state}
        inputs, targets = x[:, :-1], x[:, 1:]
        rng = jax.random.PRNGKey(1)

        def step_fn(global_step):
            key = jax.random.fold_in(rng, global_step)
            loss, grads, lgrads, new_state, _ = (
                model.value_and_grad_with_loss_params(
                    carry["params"], carry["loss_params"], carry["state"],
                    inputs, targets, loss_layer, rng=key,
                )
            )
            carry["params"] = tuple(
                jax.tree_util.tree_map(lambda p, g: p - 1e-4 * g, ps, gs)
                for ps, gs in zip(carry["params"], grads)
            )
            carry["loss_params"] = jax.tree_util.tree_map(
                lambda p, g: p - 1e-4 * g, carry["loss_params"], lgrads
            )
            carry["state"] = new_state
            return loss, carry["params"]

        tput = run_epoch_loop(
            step_fn, x.shape[0], epochs=epochs, steps_per_epoch=steps,
            label=experiment,
        )

        from benchmarks.common import (
            analytic_flops, distinct_chips, print_mfu,
        )
        from torchgpipe_tpu.layers import sequential_apply

        flat_p = [p for stage in params for p in stage]
        flat_s = [s for stage in state for s in stage]

        def _plain_step(fp, lp, xx, yy):
            def loss_of(ps):
                fp2, lp2 = ps
                out, _ = sequential_apply(
                    layers, fp2, flat_s, xx, rng=rng, train=True
                )
                l, _ = loss_layer.apply(lp2, (), (out, yy), rng=None,
                                        train=True)
                return l

            return jax.value_and_grad(loss_of)((fp, lp))

        print_mfu(
            lambda: analytic_flops(_plain_step, flat_p, loss_params,
                                   inputs, targets),
            tput, x.shape[0], experiment, n_chips=distinct_chips(model),
            device=model.devices[0],
        )
    else:
        if moe is not None:
            from torchgpipe_tpu.models.moe import llama_moe

            layers = llama_moe(cfg, moe)
        else:
            layers = llama(cfg)
        model = GPipe(
            layers, even_balance(len(layers), n), chunks=chunks,
            checkpoint=checkpoint,
        )

        def after(params, state):
            if moe is None:
                return
            # Router balance of the first MoE block on the final batch's
            # embeddings (layer 0 = token_embedding on stage 0).
            del state
            h, _ = layers[0].apply(params[0][0], (), x[:, :-1],
                                   rng=None, train=False)
            _print_router_stats(params, h, moe)

        tput = run_speed(
            model, x, x, causal_lm_loss,
            epochs=epochs, steps_per_epoch=steps, label=experiment,
            after=after,
        )
    kind = f"moe{moe_experts}" if moe_experts else "dense"
    print(
        f"FINAL | llama-speed {experiment} [{preset}, {engine}, {kind}]: "
        f"{tput:.1f} samples/sec"
    )


def _print_router_stats(params, h, moe):
    """Balance metrics of the first router found in ``params`` against
    hidden states ``h`` (router_stats: load/importance/Switch penalty)."""
    from torchgpipe_tpu.models.moe import find_routers, router_stats

    routers = find_routers(params)
    if not routers:
        return
    load, imp, bal = router_stats(routers[0], h, moe)
    # The hidden states are the token EMBEDDINGS of the last batch — an
    # input-distribution proxy for block 0's true router input (which sees
    # normed post-attention states); say so in the output.
    print(
        f"router[block0, embedding-proxy] | balance={float(bal):.3f} "
        f"(1.0=perfect) "
        f"load[min/max]={float(load.min()):.3f}/{float(load.max()):.3f} "
        f"importance[min/max]={float(imp.min()):.3f}/{float(imp.max()):.3f}",
        flush=True,
    )


def _run_spmd(cfg, n, chunks, x, epochs, steps, checkpoint, label, moe=None,
              ep=1, tp=1, dp=1, fsdp=False, schedule="fill_drain",
              virtual_stages=1, fused_ce=False, autotune=False,
              hbm_budget_gib=15.75):
    from benchmarks.common import run_epoch_loop
    from torchgpipe_tpu.models.transformer import llama_spmd
    from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh

    # Interleaved: the model is cut into n*v thinner blocks (device j owns
    # chunks c*n+j), so the block builder sees the virtual stage count.
    n_blocks = n * virtual_stages
    if moe is not None:
        from torchgpipe_tpu.models.moe import llama_moe_spmd

        block, pre, post = llama_moe_spmd(cfg, moe, n_blocks)
    else:
        block, pre, post = llama_spmd(cfg, n_blocks)
    mesh = make_mesh(n, dp=dp, ep=ep, tp=tp)
    if fused_ce:
        # Chunked-vocab CE loss layer replaces the lm_head post: the
        # [tokens, vocab] logits are never materialized (the big-vocab
        # memory fix; see models.transformer.chunked_lm_loss).
        from torchgpipe_tpu.models.transformer import chunked_lm_loss

        loss_fn, post = chunked_lm_loss(cfg), None
    else:
        loss_fn = cross_entropy
    pipe = SpmdGPipe(
        block, n, mesh, chunks=chunks, loss_fn=loss_fn,
        pre=pre, post=post, checkpoint=checkpoint,
        dp_axis="dp" if dp > 1 else None,
        ep_axis="ep" if ep > 1 else None,
        tp_axis="tp" if tp > 1 else None,
        fsdp=fsdp,
        schedule=schedule,
        virtual_stages=virtual_stages,
    )
    # SpmdGPipe shards data over the mesh; the causal shift happens on the
    # host so inputs/targets ride the same sharding specs.
    inputs, targets = x[:, :-1], x[:, 1:]
    if autotune:
        # Static sweep BEFORE any compile: pick the point on the
        # recompute/memory curve instead of the CLI's checkpoint/chunks
        # (the hand-walked rung replacement; docs/tuning.md).
        from torchgpipe_tpu import tune

        report = tune.tune_step(
            pipe, jax.ShapeDtypeStruct(inputs.shape, inputs.dtype),
            hbm_budget_bytes=int(hbm_budget_gib * 2 ** 30),
        )
        print(report.table(), flush=True)
        best = report.best
        if best is None:
            raise SystemExit(
                "autotune: no candidate fits the "
                f"{hbm_budget_gib} GiB budget (see the table above)"
            )
        print(
            f"autotune | timing checkpoint={best.checkpoint!r} "
            f"policy={best.policy or '-'} chunks={best.chunks}"
            + (f" ce_chunk={best.ce_chunk}" if best.ce_chunk else ""),
            flush=True,
        )
        pipe = tune.apply_candidate(pipe, best)
    carry = {
        "params": pipe.init(
            jax.random.PRNGKey(0),
            jax.ShapeDtypeStruct(inputs.shape, inputs.dtype),
        )
    }

    # Batches stream through the double-buffered sharding-aware
    # prefetcher: batch k+1's host→device copy (committed to the mesh's
    # data sharding) overlaps step k's compute — the hot path consumes
    # utils.data.prefetch_to_pipe instead of re-uploading per step.
    from itertools import repeat

    from torchgpipe_tpu.utils.data import prefetch_to_pipe

    batches = prefetch_to_pipe(repeat((inputs, targets)), pipe, size=2)

    def step_fn(global_step):
        del global_step
        xb, yb = next(batches)
        loss, grads = pipe.train_step(carry["params"], xb, yb)
        carry["params"] = jax.tree_util.tree_map(
            lambda p, g: p - 1e-4 * g, carry["params"], grads
        )
        return loss, carry["params"]

    tput = run_epoch_loop(
        step_fn, x.shape[0], epochs=epochs, steps_per_epoch=steps, label=label
    )

    # MFU for the spmd engine too (same convention as the mpmd branches:
    # the numerator is the UN-pipELINED model's fwd+loss+bwd, costed from
    # a plain sequential step over the stacked block params).  Configs
    # whose block graph needs mesh collectives at trace time (tp/ep)
    # cannot lower as a plain step, so they print no MFU line rather
    # than a wrong numerator.
    from benchmarks.common import analytic_flops, print_mfu

    def _plain_step(ps):
        def loss_of(ps):
            h = inputs
            if pre is not None:
                h, _ = pre.apply(ps["pre"], (), h, rng=None, train=True)

            def body(hh, bp):
                out, _ = block.apply(bp, (), hh, rng=None, train=True)
                return out, None

            h, _ = jax.lax.scan(body, h, ps["blocks"])
            if post is not None:
                h, _ = post.apply(ps["post"], (), h, rng=None, train=True)
            if "loss" in ps:
                l, _ = loss_fn.apply(
                    ps["loss"], (), (h, targets), rng=None, train=True
                )
            else:
                l = loss_fn(h, targets)
            return l

        return jax.value_and_grad(loss_of)(ps)

    if tp == 1 and ep == 1:
        print_mfu(
            lambda: analytic_flops(_plain_step, carry["params"]),
            tput, x.shape[0], label,
            n_chips=int(mesh.devices.size),
            device=mesh.devices.flat[0],
        )
    if moe is not None and pre is not None:
        # Router balance of stage 0's first MoE block on the final batch.
        stage0 = jax.tree_util.tree_map(
            lambda a: a[0], carry["params"]["blocks"]
        )
        h, _ = pre.apply(carry["params"]["pre"], (), inputs,
                         rng=None, train=False)
        _print_router_stats(stage0, h, moe)
    return tput


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
