"""AmoebaNet-D memory benchmark: grow (num_layers L, num_filters D) with the
pipeline and report parameter count + per-device peak memory.

Reference: benchmarks/amoebanetd-memory/main.py:20-84
(docs/benchmarks.rst:69-83: (72, 512) = 1.84B params on pipeline-8).
"""

from __future__ import annotations

import click
import jax
import jax.numpy as jnp

from benchmarks.common import build_gpipe, run_memory, softmax_xent
from torchgpipe_tpu.models import amoebanetd

# name -> (n_stages, (num_layers L, num_filters D))
EXPERIMENTS = {
    "baseline": (1, (18, 208)),
    "pipeline-1": (1, (18, 416)),
    "pipeline-2": (2, (18, 544)),
    "pipeline-4": (4, (36, 544)),
    "pipeline-8": (8, (72, 512)),
}


@click.command()
@click.argument("experiment", type=click.Choice(sorted(EXPERIMENTS)))
@click.option("--image", default=224)
@click.option("--batch", default=32)
@click.option("--chunks", default=4)
def main(experiment, image, batch, chunks):
    n, (num_layers, num_filters) = EXPERIMENTS[experiment]
    layers = amoebanetd(
        num_classes=1000, num_layers=num_layers, num_filters=num_filters
    )
    model = build_gpipe(layers, None, n, chunks, "always")
    x = jnp.zeros((batch, image, image, 3), jnp.float32)
    y = jax.random.randint(jax.random.PRNGKey(0), (batch,), 0, 1000)
    run_memory(
        model, x, y, softmax_xent,
        label=f"amoebanetd-memory {experiment} L={num_layers} D={num_filters}",
    )


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
