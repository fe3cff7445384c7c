"""AmoebaNet-D (18, 256) speed benchmark — the reference's headline grid.

Reference: benchmarks/amoebanetd-speed/main.py:33-109 — experiments
n∈{2,4,8} × m∈{1,4,32} with hand-tuned batch sizes and balances;
``checkpoint='always'`` when m=1 else ``'except_last'``.  The hand balances
below are re-derived defaults (AmoebaNet cells are heterogeneous; pass
``--balance`` or use ``torchgpipe_tpu.balance`` to retune for your chips).
"""

from __future__ import annotations

import click
import jax
import jax.numpy as jnp

from benchmarks.common import bf16_option, build_gpipe, run_speed, softmax_xent
from torchgpipe_tpu.models import amoebanetd

# name -> (n_stages, batch, chunks, balance, checkpoint); layer count is
# 3 + num_layers + 3 = 24 for num_layers=18 (stem + cells + classify).
EXPERIMENTS = {
    "n1m1": (1, 64, 1, None, "always"),
    "n1m8": (1, 128, 8, None, "except_last"),
    "n2m1": (2, 96, 1, [7, 17], "always"),
    "n2m4": (2, 256, 4, [9, 15], "except_last"),
    "n2m32": (2, 1280, 32, [9, 15], "except_last"),
    "n4m1": (4, 160, 1, [3, 4, 5, 12], "always"),
    "n4m4": (4, 360, 4, [3, 6, 7, 8], "except_last"),
    "n4m32": (4, 1152, 32, [3, 6, 7, 8], "except_last"),
    "n8m1": (8, 196, 1, [2, 2, 2, 2, 2, 3, 4, 7], "always"),
    "n8m4": (8, 480, 4, [2, 2, 2, 3, 3, 4, 4, 4], "except_last"),
    "n8m32": (8, 1280, 32, [2, 2, 2, 3, 3, 4, 4, 4], "except_last"),
}


@click.command()
@click.argument("experiment", type=click.Choice(sorted(EXPERIMENTS)))
@click.option("--epochs", default=3, help="timed epochs (first is warm-up)")
@click.option("--steps", default=10, help="steps per epoch")
@click.option("--num-layers", default=18)
@click.option("--num-filters", default=256)
@click.option("--image", default=224, help="input image size")
@click.option("--batch", default=None, type=int, help="override batch size")
@bf16_option
def main(experiment, epochs, steps, num_layers, num_filters, image, batch, bf16):
    n, bsz, chunks, balance, ckpt = EXPERIMENTS[experiment]
    bsz = batch or bsz
    layers = amoebanetd(
        num_classes=1000, num_layers=num_layers, num_filters=num_filters
    )
    if balance is not None and sum(balance) != len(layers):
        balance = None  # model size changed; fall back to even split
    model = build_gpipe(layers, balance, n, chunks, ckpt, bf16=bf16)
    x = jnp.zeros((bsz, image, image, 3), jnp.float32)
    y = jax.random.randint(jax.random.PRNGKey(0), (bsz,), 0, 1000)
    tput = run_speed(
        model, x, y, softmax_xent,
        epochs=epochs, steps_per_epoch=steps, label=experiment,
    )
    print(f"FINAL | amoebanetd-speed {experiment}: {tput:.1f} samples/sec")


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
