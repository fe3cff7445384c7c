"""Multi-process distributed pipeline training driver.

Reference: benchmarks/distributed/accuracy/main.py:106-204, 347-368 — one OS
process per rank joined over RPC (``--rank/--world/--master``), training a
sequential model split across ranks.  Here ranks join over
:class:`~torchgpipe_tpu.distributed.TcpTransport` (host-staged sockets, like
the reference's RPC transport); for single-host multi-device runs prefer the
in-process engine, and for pod-scale runs the SPMD engine (SURVEY.md §2.3).

Example (two shells)::

    python -m benchmarks.distributed_accuracy --rank 0 --world 2 \
        --master 127.0.0.1 --port-base 29500
    python -m benchmarks.distributed_accuracy --rank 1 --world 2 \
        --master 127.0.0.1 --port-base 29500
"""

from __future__ import annotations

import os
import time

import click
import jax
import jax.numpy as jnp

from benchmarks.common import hr_time, softmax_xent
from torchgpipe_tpu.balance import balance_by_time
from torchgpipe_tpu.distributed import (
    DistributedGPipe,
    DistributedGPipeDataLoader,
    TcpTransport,
)
from torchgpipe_tpu.layers import sequential_init
from torchgpipe_tpu.models import resnet50, vgg16
from torchgpipe_tpu.models.transformer import TransformerConfig, llama

def _mlp(classes):
    from torchgpipe_tpu.ops import dense, flatten, relu

    return [
        flatten(), dense(64, name="fc1"), relu("r1"),
        dense(64, name="fc2"), relu("r2"), dense(classes, name="fc3"),
    ]


MODELS = {
    # The reference's distributed accuracy bench trains sequential
    # resnet101/vgg16 over RPC ranks (benchmarks/distributed/accuracy/
    # {resnet,vgg}); scaled-width counterparts of both are here.
    "resnet50": lambda classes: resnet50(num_classes=classes, base_width=16),
    "vgg16": lambda classes: vgg16(
        num_classes=classes, base_width=16, head_width=256
    ),
    "llama-small": lambda classes: llama(
        TransformerConfig(vocab=classes, dim=128, n_layers=4, n_heads=4)
    ),
    "mlp": _mlp,  # tiny smoke-test model
}


@click.command()
@click.option("--rank", required=True, type=int)
@click.option("--world", required=True, type=int)
@click.option("--master", default="127.0.0.1")
@click.option("--port-base", default=29500)
@click.option("--model", "model_name", default="resnet50",
              type=click.Choice(sorted(MODELS)))
@click.option("--balance", default=None, type=str,
              help="comma-separated per-rank layer counts; default: profiled "
                   "balance_by_time on rank 0's layer costs (reference: "
                   "benchmarks/distributed/accuracy/main.py balance_by_time "
                   "fallback)")
@click.option("--chunks", default=4)
@click.option("--batch-size", default=32)
@click.option("--epochs", default=2)
@click.option("--steps", default=8)
@click.option("--classes", default=10)
@click.option("--image", default=32)
@click.option("--recv-timeout", default=None, type=float,
              help="bound every cross-rank receive; a dead peer surfaces as "
                   "a TimeoutError naming the missing channel instead of a "
                   "hang (leave unset when stage compile times are unknown)")
@click.option("--connect-timeout", default=120.0, type=float,
              help="rendezvous budget for dialing a peer's listener")
@click.option("--checkpoint-dir", default=None, type=str,
              help="crash recovery: each rank saves its partition params/"
                   "state here after every epoch and resumes from the last "
                   "completed epoch on restart (the reference's RPC mode "
                   "has neither failure detection nor recovery)")
def main(rank, world, master, port_base, model_name, balance, chunks,
         batch_size, epochs, steps, classes, image, recv_timeout,
         connect_timeout, checkpoint_dir):
    layers = MODELS[model_name](classes)
    workers = [f"rank{r}" for r in range(world)]
    # Each rank listens on port_base + rank; peers dial the master host.
    addresses = {f"rank{r}": (master, port_base + r) for r in range(world)}
    addresses[f"rank{rank}"] = ("0.0.0.0", port_base + rank)
    transport = TcpTransport(
        f"rank{rank}", addresses, connect_timeout=connect_timeout
    )

    if model_name == "llama-small":
        x0 = jnp.zeros((batch_size, 64), jnp.int32)

        def make_batch(key):
            # Next-token LM objective: labels are the inputs shifted by one.
            tokens = jax.random.randint(key, x0.shape, 0, classes)
            return tokens, jnp.roll(tokens, -1, axis=1)
    else:
        shape = (
            (batch_size, image, image, 3)
            if model_name in ("resnet50", "vgg16")
            else (batch_size, 16)
        )
        x0 = jnp.zeros(shape, jnp.float32)

        def make_batch(key):
            kx, ky = jax.random.split(key)
            return (
                jax.random.normal(kx, x0.shape),
                jax.random.randint(ky, (batch_size,), 0, classes),
            )
    in_spec = jax.ShapeDtypeStruct(x0.shape, x0.dtype)

    if balance:
        balance = [int(v) for v in balance.split(",")]
    elif rank == 0:
        # Profile on rank 0 only and broadcast: wall-clock profiling on every
        # rank independently could disagree on the balance and deadlock the
        # pipe with mismatched stage ownership.
        params0, states0, _ = sequential_init(
            layers, jax.random.PRNGKey(0), in_spec
        )
        balance = balance_by_time(
            world, layers, params0, states0, x0, timeout=0.5
        )
        print(f"[rank 0] profiled balance: {balance}", flush=True)
        for r in range(1, world):
            transport.send(f"rank{r}", "balance", 0, balance)
    else:
        balance = list(transport.mailbox.get("balance", 0, timeout=600))

    pipe = DistributedGPipe(
        layers, rank, workers, balance, chunks=chunks,
        transport=transport, mailbox=transport.mailbox,
        recv_timeout=recv_timeout,
    )
    params, state = pipe.init(jax.random.PRNGKey(0), in_spec)

    # Crash recovery: each rank persists ITS partition after every epoch;
    # on restart, resume from the last epoch every rank completed.  The
    # checkpoint records (model, world, balance, ...) and every leaf shape
    # is validated against the fresh init, so a restart with a different
    # partitioning fails loudly instead of loading the wrong weights.
    ckpt_path = (
        os.path.join(checkpoint_dir, f"rank{rank}.npz")
        if checkpoint_dir
        else None
    )
    ckpt_meta = (
        f"{model_name}|world={world}|rank={rank}|balance={balance}|"
        f"classes={classes}|image={image}|chunks={chunks}"
    )
    start_epoch = 0
    if ckpt_path and os.path.exists(ckpt_path):
        params, state, start_epoch = _load_rank_checkpoint(
            ckpt_path, params, state, ckpt_meta, checkpoint_dir
        )
        print(f"[rank {rank}] resumed from epoch {start_epoch}", flush=True)
    if checkpoint_dir:
        os.makedirs(checkpoint_dir, exist_ok=True)
        # Every rank reports its epoch to rank 0, which broadcasts either
        # the agreed value or an abort sentinel — so a torn checkpoint set
        # (crash between per-rank saves) makes EVERY rank exit with the
        # same didactic message instead of some ranks hanging in the pipe
        # waiting for a peer that aborted.
        if rank == 0:
            seen = {0: start_epoch}
            for r in range(1, world):
                seen[r] = int(
                    transport.mailbox.get("epoch_report", r, timeout=600)
                )
            torn = len(set(seen.values())) != 1
            agreed = -1 if torn else start_epoch
            for r in range(1, world):
                transport.send(f"rank{r}", "resume_epoch", 0, agreed)
            if torn:
                raise SystemExit(
                    f"[rank 0] checkpoint epochs disagree across ranks "
                    f"({seen}); delete {checkpoint_dir} and restart from "
                    "scratch"
                )
        else:
            transport.send("rank0", "epoch_report", rank, start_epoch)
            agreed = int(transport.mailbox.get("resume_epoch", 0, timeout=600))
            if agreed < 0:
                raise SystemExit(
                    f"[rank {rank}] checkpoint epochs disagree across "
                    f"ranks; delete {checkpoint_dir} and restart from "
                    "scratch"
                )

    # Only rank 0 feeds data (the loader ships targets to the last rank).
    data = (
        [make_batch(jax.random.PRNGKey(100 + s)) for s in range(steps)]
        if rank == 0
        else None
    )
    loader = DistributedGPipeDataLoader(
        data, rank, workers,
        transport=transport, mailbox=transport.mailbox, num_batches=steps,
        recv_timeout=recv_timeout,
    )

    t0 = time.time()
    for epoch in range(start_epoch, epochs):
        for step, (xb, yb) in enumerate(loader):
            key = jax.random.fold_in(jax.random.PRNGKey(7), epoch * steps + step)
            outs = pipe.forward(params, state, xb, rng=key)
            if pipe.is_last:
                loss, gys, _ = pipe.loss_grads(outs, yb, softmax_xent)
                grads, state = pipe.backward(gys)
                print(
                    f"{hr_time(time.time() - t0)} | epoch {epoch + 1} "
                    f"step {step + 1}: loss {float(loss):.4f}",
                    flush=True,
                )
            else:
                grads, state = pipe.backward(None)
            params = jax.tree_util.tree_map(
                lambda p, g: p - 0.05 * g, params, list(grads)
            )
        if ckpt_path:
            _save_rank_checkpoint(
                ckpt_path, params, state, epoch + 1, ckpt_meta
            )
    transport.close()
    print(f"[rank {rank}] done", flush=True)


def _save_rank_checkpoint(path, params, state, epoch: int, meta: str) -> None:
    """Atomically persist this rank's partition (write-then-rename), tagged
    with the run configuration so a mismatched restart is caught on load."""
    import numpy as np

    from torchgpipe_tpu.utils.serialization import save

    leaves_p = jax.tree_util.tree_leaves(params)
    leaves_s = jax.tree_util.tree_leaves(state)
    payload = {f"p{i}": np.asarray(l) for i, l in enumerate(leaves_p)}
    payload.update({f"s{i}": np.asarray(l) for i, l in enumerate(leaves_s)})
    payload["epoch"] = np.asarray(epoch)
    payload["meta"] = np.asarray(meta)
    tmp = path + ".tmp.npz"  # savez appends .npz unless already suffixed
    save(tmp, payload)
    os.replace(tmp, path)


def _load_rank_checkpoint(path, params, state, meta: str, ckpt_dir: str):
    """Restore params/state into the freshly-initialized tree structure,
    validating run configuration and every leaf shape/dtype first."""
    from torchgpipe_tpu.utils.serialization import load

    d = load(path)
    if str(d.get("meta")) != meta:
        raise SystemExit(
            f"checkpoint {path} was written by a different run "
            f"configuration:\n  saved: {d.get('meta')}\n  now:   {meta}\n"
            f"delete {ckpt_dir} and restart from scratch"
        )
    init_p = jax.tree_util.tree_leaves(params)
    init_s = jax.tree_util.tree_leaves(state)
    want = {f"p{i}" for i in range(len(init_p))}
    want |= {f"s{i}" for i in range(len(init_s))}
    have = set(d) - {"epoch", "meta"}
    if have != want:
        raise SystemExit(
            f"checkpoint {path} leaf set mismatch (saved {len(have)}, "
            f"expected {len(want)}); delete {ckpt_dir} and restart"
        )
    leaves_p = [d[f"p{i}"] for i in range(len(init_p))]
    leaves_s = [d[f"s{i}"] for i in range(len(init_s))]
    for got, ref in zip(leaves_p + leaves_s, init_p + init_s):
        if got.shape != ref.shape or got.dtype != ref.dtype:
            raise SystemExit(
                f"checkpoint {path} leaf {got.shape}/{got.dtype} does not "
                f"match the model's {ref.shape}/{ref.dtype}; delete "
                f"{ckpt_dir} and restart"
            )
    params = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params), leaves_p
    )
    state = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(state), leaves_s
    )
    return params, state, int(d["epoch"])


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
