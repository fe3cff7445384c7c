"""Multi-host SPMD pipeline training, runnable WITHOUT a pod.

Launches itself twice (two OS processes, 4 virtual CPU devices each) and
joins them into ONE global 8-device mesh via ``jax.distributed`` — the
same topology as two TPU hosts over DCN.  The self-launch is CPU ONLY:
both ranks pin the CPU backend, because a chip belongs to one process at
a time and two ranks on one host cannot share it (the launching parent
never imports jax).  Each process then:

* builds a dp-outermost ``(dp, pp)`` mesh so it owns a whole data slice,
* feeds ONLY its own rows of the global batch
  (``utils.data.global_batch_from_local`` — no host holds the full batch),
* runs the compiled pipelined training step — the ``pp`` ppermute
  hand-offs and the ``dp`` gradient pmean cross the process boundary,
* checkpoints with ``save_sharded`` (rank-0-gated atomic swap).

On a real pod: drop the self-launch, call ``jax.distributed.initialize()``
(TPU auto-detection) on every host, and keep everything else identical.
See docs/multihost.md for the full recipe.

Run: ``python examples/multihost_llama.py``
"""

import os
import subprocess
import sys

PORT = os.environ.get("MULTIHOST_EXAMPLE_PORT", "29471")


def launch_both() -> None:
    import time

    procs = []
    codes = []
    deadline = time.monotonic() + 540  # overall, not per rank
    try:
        for rank in range(2):
            env = dict(os.environ, MULTIHOST_EXAMPLE_RANK=str(rank))
            procs.append(
                subprocess.Popen([sys.executable, __file__], env=env)
            )
        for p in procs:
            codes.append(p.wait(timeout=max(1, deadline - time.monotonic())))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    if any(codes):
        raise SystemExit(f"rank exit codes: {codes}")
    print("multihost example: both ranks OK")


def build_for_lint():
    """Static-analysis entrypoint (tools/pipeline_lint.py): the same
    (dp, pp) topology run_rank() builds across two processes, constructed
    on this process's 8 virtual devices — the linter only needs the traced
    program, which is identical either way."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from torchgpipe_tpu.models.transformer import (
        TransformerConfig,
        cross_entropy,
        llama_spmd,
    )
    from torchgpipe_tpu.spmd import SpmdGPipe

    pp, dp, m = 4, 2, 4
    cfg = TransformerConfig(
        vocab=256, dim=64, n_layers=pp, n_heads=4, n_kv_heads=2
    )
    block, pre, post = llama_spmd(cfg, pp)
    mesh = Mesh(np.array(jax.devices()[: dp * pp]).reshape(dp, pp),
                ("dp", "pp"))
    pipe = SpmdGPipe(
        block, pp, mesh, chunks=m, loss_fn=cross_entropy,
        pre=pre, post=post, dp_axis="dp",
    )
    x = jax.ShapeDtypeStruct((m * dp * 2, 16), jnp.int32)
    return pipe, x


def run_rank(rank: int) -> None:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{PORT}",
        num_processes=2,
        process_id=rank,
    )

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from torchgpipe_tpu.models.transformer import (
        TransformerConfig,
        cross_entropy,
        llama_spmd,
    )
    from torchgpipe_tpu.spmd import SpmdGPipe
    from torchgpipe_tpu.utils.data import global_batch_from_local

    pp, dp, m = 4, 2, 4
    cfg = TransformerConfig(
        vocab=256, dim=64, n_layers=pp, n_heads=4, n_kv_heads=2
    )
    block, pre, post = llama_spmd(cfg, pp)
    # dp OUTERMOST: process r owns dp slice r, so it feeds only its rows.
    mesh = Mesh(np.array(jax.devices()).reshape(dp, pp), ("dp", "pp"))
    pipe = SpmdGPipe(
        block, pp, mesh, chunks=m, loss_fn=cross_entropy,
        pre=pre, post=post, dp_axis="dp",
    )

    B = m * dp * 2  # global batch
    params = pipe.init(
        jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((B, 16), jnp.int32),
    )

    rows0 = rank * (B // 2)  # this process's first global row
    n_rows = B // 2
    for step in range(5):
        # Each process materializes ONLY its own rows of the (virtual)
        # global batch — the arange is offset by the global row index, so
        # no host ever holds the full [B, 16] array.
        local = (
            np.arange(rows0 * 16, (rows0 + n_rows) * 16, dtype=np.int32)
            .reshape(n_rows, 16)
            + step
        ) % 256
        tokens = global_batch_from_local(mesh, P("dp"), local)
        labels = global_batch_from_local(mesh, P("dp"), (local + 1) % 256)
        loss, grads = pipe.train_step(params, tokens, labels)
        params = jax.tree_util.tree_map(
            lambda p, g: p - 0.1 * g, params, grads
        )
        if rank == 0:
            print(f"step {step}: loss {float(loss):.4f}", flush=True)

    # Sharded checkpoint: every process calls save_sharded; the atomic
    # directory swap is process-0-gated (utils/serialization.py).
    try:
        from torchgpipe_tpu.utils.serialization import save_sharded

        # Per-run path (keyed by the coordinator port) so concurrent
        # runs cannot race inside save_sharded's atomic swap.
        path = os.path.join(
            os.environ.get("TMPDIR", "/tmp"), f"multihost_llama_ckpt_{PORT}"
        )
        save_sharded(path, params)
        if rank == 0:
            print(f"checkpoint saved to {path}", flush=True)
    except ModuleNotFoundError:
        pass  # orbax not installed — checkpointing is optional here


if __name__ == "__main__":
    r = os.environ.get("MULTIHOST_EXAMPLE_RANK")
    if r is None:
        launch_both()
    else:
        run_rank(int(r))
