"""Rank entry point for the real-process TcpTransport tests
(``test_real_processes.py``): one OS process per rank joined over
:class:`~torchgpipe_tpu.distributed.TcpTransport` (host-staged sockets,
like the reference's RPC transport: benchmarks/distributed/accuracy/
main.py:106-204, 347-368), training a small MLP split across the ranks
with per-epoch checkpoints and bounded receives.

Usage: ``python tcp_rank.py --rank R --world W --port-base P --balance a,b,c``
"""

from __future__ import annotations

import os
import time

import click
import jax
import jax.numpy as jnp

from torchgpipe_tpu.distributed import (
    DistributedGPipe,
    DistributedGPipeDataLoader,
    TcpTransport,
)
from torchgpipe_tpu.ops import dense, flatten, relu


def softmax_xent(out, tgt):
    logits = out.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits.reshape(-1, logits.shape[-1]))
    return -jnp.mean(logp[jnp.arange(logp.shape[0]), tgt.reshape(-1)])


@click.command()
@click.option("--rank", required=True, type=int)
@click.option("--world", required=True, type=int)
@click.option("--master", default="127.0.0.1")
@click.option("--port-base", default=29500)
@click.option("--balance", required=True, type=str,
              help="comma-separated per-rank layer counts")
@click.option("--chunks", default=4)
@click.option("--batch-size", default=32)
@click.option("--epochs", default=2)
@click.option("--steps", default=8)
@click.option("--classes", default=10)
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
def main(rank, world, master, port_base, balance, chunks, batch_size, epochs,
         steps, classes, recv_timeout, connect_timeout, checkpoint_dir):
    layers = [
        flatten(), dense(64, name="fc1"), relu("r1"),
        dense(64, name="fc2"), relu("r2"), dense(classes, name="fc3"),
    ]
    workers = [f"rank{r}" for r in range(world)]
    # Each rank listens on port_base + rank; peers dial the master host.
    addresses = {f"rank{r}": (master, port_base + r) for r in range(world)}
    addresses[f"rank{rank}"] = ("0.0.0.0", port_base + rank)
    transport = TcpTransport(
        f"rank{rank}", addresses, connect_timeout=connect_timeout
    )
    in_spec = jax.ShapeDtypeStruct((batch_size, 16), jnp.float32)

    def make_batch(key):
        kx, ky = jax.random.split(key)
        return (
            jax.random.normal(kx, in_spec.shape),
            jax.random.randint(ky, (batch_size,), 0, classes),
        )

    balance = [int(v) for v in balance.split(",")]
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
        f"mlp|world={world}|rank={rank}|balance={balance}|"
        f"classes={classes}|chunks={chunks}"
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
                    f"{time.time() - t0:7.1f}s | epoch {epoch + 1} "
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
    main()
