"""U-Net (depth 5, 64 channels) speed benchmark.

Reference: benchmarks/unet-speed/main.py:22-78 — baseline + pipeline-1/2/4/8
on a (5, 64) U-Net with 192x192 inputs, MSE-style segmentation loss.
"""

from __future__ import annotations

import click
import jax.numpy as jnp

from benchmarks.common import bf16_option, build_gpipe, mse, run_speed
from torchgpipe_tpu.models import unet

EXPERIMENTS = {
    "baseline": (1, 40, 1),
    "pipeline-1": (1, 80, 2),
    "pipeline-2": (2, 160, 8),
    "pipeline-4": (4, 320, 16),
    "pipeline-8": (8, 640, 32),
}


@click.command()
@click.argument("experiment", type=click.Choice(sorted(EXPERIMENTS)))
@click.option("--epochs", default=3)
@click.option("--steps", default=10)
@click.option("--image", default=192)
@click.option("--batch", default=None, type=int)
@click.option("--depth", default=5)
@click.option("--num-convs", default=5)
@click.option("--base-channels", default=64)
@bf16_option
def main(experiment, epochs, steps, image, batch, depth, num_convs, base_channels, bf16):
    n, bsz, chunks = EXPERIMENTS[experiment]
    bsz = batch or bsz
    layers = unet(
        depth=depth, num_convs=num_convs, base_channels=base_channels,
        output_channels=1,
    )
    model = build_gpipe(layers, None, n, chunks, "except_last", bf16=bf16)
    x = jnp.zeros((bsz, image, image, 3), jnp.float32)
    y = jnp.zeros((bsz, image, image, 1), jnp.float32)
    tput = run_speed(
        model, x, y, mse, epochs=epochs, steps_per_epoch=steps, label=experiment
    )
    print(f"FINAL | unet-speed {experiment}: {tput:.1f} samples/sec")


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
