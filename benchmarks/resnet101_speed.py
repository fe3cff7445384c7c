"""Sequential ResNet-101 speed benchmark.

Reference: benchmarks/resnet101-speed/main.py:21-77 — baseline (no pipeline)
plus pipeline-1/2/4/8 with hand-tuned batch/chunks/balance, fake data,
samples/sec.  Balances default to an even split (the reference's hand
balances are tuned to P40s; retune with ``torchgpipe_tpu.balance``).
"""

from __future__ import annotations

import click
import jax
import jax.numpy as jnp

from benchmarks.common import bf16_option, build_gpipe, run_speed, softmax_xent
from torchgpipe_tpu.models import resnet101

# name -> (n_stages, batch, chunks)
EXPERIMENTS = {
    "baseline": (1, 118, 1),
    "pipeline-1": (1, 220, 2),
    "pipeline-2": (2, 512, 16),
    "pipeline-4": (4, 1024, 64),
    "pipeline-8": (8, 2048, 64),
}


@click.command()
@click.argument("experiment", type=click.Choice(sorted(EXPERIMENTS)))
@click.option("--epochs", default=3)
@click.option("--steps", default=10)
@click.option("--image", default=224)
@click.option("--batch", default=None, type=int)
@click.option("--base-width", default=64)
@bf16_option
def main(experiment, epochs, steps, image, batch, base_width, bf16):
    n, bsz, chunks = EXPERIMENTS[experiment]
    bsz = batch or bsz
    layers = resnet101(num_classes=1000, base_width=base_width)
    model = build_gpipe(layers, None, n, chunks, "except_last", bf16=bf16)
    x = jnp.zeros((bsz, image, image, 3), jnp.float32)
    y = jax.random.randint(jax.random.PRNGKey(0), (bsz,), 0, 1000)
    tput = run_speed(
        model, x, y, softmax_xent,
        epochs=epochs, steps_per_epoch=steps, label=experiment,
    )
    print(f"FINAL | resnet101-speed {experiment}: {tput:.1f} samples/sec")


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
