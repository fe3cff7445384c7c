"""U-Net memory benchmark: grow (num_convs B, base_channels C) with the
pipeline and report parameter count + per-device peak memory.

Reference: benchmarks/unet-memory/main.py:19-87 — the model grows with the
partition count to show pipeline+checkpointing memory scaling
(docs/benchmarks.rst:41-49: 15.82B params on pipeline-8 vs 362.2M baseline).
"""

from __future__ import annotations

import click
import jax.numpy as jnp

from benchmarks.common import build_gpipe, mse, run_memory
from torchgpipe_tpu.models import unet

# name -> (n_stages, (num_convs B, base_channels C))
EXPERIMENTS = {
    "baseline": (1, (6, 72)),
    "pipeline-1": (1, (11, 128)),
    "pipeline-2": (2, (24, 128)),
    "pipeline-4": (4, (24, 160)),
    "pipeline-8": (8, (48, 160)),
}


@click.command()
@click.argument("experiment", type=click.Choice(sorted(EXPERIMENTS)))
@click.option("--image", default=192)
@click.option("--batch", default=32)
@click.option("--chunks", default=4)
@click.option("--depth", default=5)
@click.option("--num-convs", default=None, type=int, help="override grid B")
@click.option("--base-channels", default=None, type=int, help="override grid C")
def main(experiment, image, batch, chunks, depth, num_convs, base_channels):
    n, (convs, channels) = EXPERIMENTS[experiment]
    convs = num_convs or convs
    channels = base_channels or channels
    layers = unet(
        depth=depth, num_convs=convs, base_channels=channels, output_channels=1
    )
    model = build_gpipe(layers, None, n, chunks, "always")
    x = jnp.zeros((batch, image, image, 3), jnp.float32)
    y = jnp.zeros((batch, image, image, 1), jnp.float32)
    run_memory(model, x, y, mse, label=f"unet-memory {experiment} B={convs} C={channels}")


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
