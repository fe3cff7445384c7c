"""U-Net timeline / overlap-ablation benchmark.

Reference: benchmarks/unet-timeline/main.py:22-75 — ablates the engine's
concurrency features (dependencies, copy streams, portals) by
monkey-patching, sampling GPU utilization from a side process.  TPU-native
redesign: the engine's own :class:`~torchgpipe_tpu.utils.tracing.Timeline`
records per-cell intervals; the ``serialized`` experiment forces every cell
to completion before the next dispatch (no cross-stage overlap — the
ablation), and the busy/bubble fractions are compared against the
analytic GPipe bubble (n-1)/(m+n-1).
"""

from __future__ import annotations

import time

import click
import jax
import jax.numpy as jnp

from benchmarks.common import build_gpipe, mse
from torchgpipe_tpu.models import unet
from torchgpipe_tpu.utils.tracing import Timeline, simulate_pipeline


@click.command()
@click.option("--stages", default=4)
@click.option("--chunks", default=8)
@click.option("--image", default=64)
@click.option("--batch", default=16)
@click.option("--depth", default=3)
@click.option("--num-convs", default=2)
@click.option("--base-channels", default=16)
@click.option("--steps", default=5)
def main(stages, chunks, image, batch, depth, num_convs, base_channels, steps):
    layers = unet(
        depth=depth, num_convs=num_convs, base_channels=base_channels,
        output_channels=1,
    )
    x = jnp.zeros((batch, image, image, 3), jnp.float32)
    y = jnp.zeros((batch, image, image, 1), jnp.float32)
    in_spec = jax.ShapeDtypeStruct(x.shape, x.dtype)

    results = {}
    for mode in ("pipelined", "serialized"):
        tracer = Timeline(sync=(mode == "serialized"))
        model = build_gpipe(
            layers, None, stages, chunks, "except_last", tracer=tracer
        )
        params, state = model.init(jax.random.PRNGKey(0), in_spec)
        # Warm-up compile.
        loss, grads, state, _ = model.value_and_grad(
            params, state, x, y, mse, rng=jax.random.PRNGKey(1)
        )
        jax.block_until_ready(grads)
        tracer.reset()
        t0 = time.perf_counter()
        for s in range(steps):
            loss, grads, state, _ = model.value_and_grad(
                params, state, x, y, mse, rng=jax.random.PRNGKey(2 + s)
            )
        jax.block_until_ready(grads)
        dt = time.perf_counter() - t0
        results[mode] = batch * steps / dt
        print(f"--- {mode}: {results[mode]:.1f} samples/sec")
        print(tracer.summary())
        if mode == "serialized":
            # From true per-cell times, project the overlap-perfect makespan
            # and its bubble; gap vs the analytic (n-1)/(m+n-1) is stage
            # imbalance.
            sim = simulate_pipeline(tracer.events, stages)
            if sim is not None:
                makespan, busy, bubble = sim
                ideal_bubble = (stages - 1) / (chunks + stages - 1)
                print(
                    f"    projected pipelined makespan {makespan * 1e3:.1f}ms/"
                    f"step-pair, bubble {bubble:.2f} "
                    f"(analytic GPipe bubble {ideal_bubble:.2f})"
                )
    speedup = results["pipelined"] / results["serialized"]
    print(f"FINAL | unet-timeline: overlap speedup {speedup:.2f}x")


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
