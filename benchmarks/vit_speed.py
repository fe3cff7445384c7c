"""Sequential ViT speed benchmark.

No reference counterpart (the reference zoo is conv-only); this driver
mirrors the zoo's speed-driver shape (reference:
benchmarks/resnet101-speed/main.py:21-77 — experiment table, fake data,
samples/sec) for the transformer vision model, where the MXU fraction
is far higher than the conv nets': one patchify matmul + dense
attention/MLP blocks.
"""

from __future__ import annotations

import click
import jax
import jax.numpy as jnp

from benchmarks.common import bf16_option, build_gpipe, run_speed, softmax_xent
from torchgpipe_tpu.models import vit

# name -> (n_stages, batch, chunks)
EXPERIMENTS = {
    "baseline": (1, 128, 1),
    "pipeline-1": (1, 256, 4),
    "pipeline-2": (2, 512, 8),
    "pipeline-4": (4, 1024, 16),
    "pipeline-8": (8, 2048, 32),
}


@click.command()
@click.argument("experiment", type=click.Choice(sorted(EXPERIMENTS)))
@click.option("--epochs", default=3)
@click.option("--steps", default=10)
@click.option("--image", default=224)
@click.option("--patch", default=16)
@click.option("--dim", default=384, help="ViT-S/16 width")
@click.option("--depth", default=12)
@click.option("--heads", default=6)
@click.option("--batch", default=None, type=int)
@bf16_option
def main(experiment, epochs, steps, image, patch, dim, depth, heads,
         batch, bf16):
    n, bsz, chunks = EXPERIMENTS[experiment]
    bsz = batch or bsz
    layers = vit(
        image_size=image, patch_size=patch, dim=dim, depth=depth,
        n_heads=heads, num_classes=1000,
    )
    model = build_gpipe(layers, None, n, chunks, "except_last", bf16=bf16)
    x = jnp.zeros((bsz, image, image, 3), jnp.float32)
    y = jax.random.randint(jax.random.PRNGKey(0), (bsz,), 0, 1000)
    tput = run_speed(
        model, x, y, softmax_xent,
        epochs=epochs, steps_per_epoch=steps, label=experiment,
    )
    print(f"FINAL | vit-speed {experiment}: {tput:.1f} samples/sec")


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
