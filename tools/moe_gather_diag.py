"""Why PR 37's gather form of the dropless expert sum trains wrong on the chip.

Under ``jax.grad`` the form that moves the expert rows by gathers only (both
directions, and both transposes) made ``mellum2.train-4x8192`` report a first
step loss of 10.476 where its ``eval_loss`` and PR 36's step read 10.613,
with the gradients as PR 36's (``PERF.md`` section 7, "Since PR 37").  The tree
keeps that form for the served forward alone.  This driver prints, one JSON line
a variant, the cell's first three step losses (its own step and feed) and, for
``none``, the forward-only loss at the initial weights:

    none        the tree it runs in, as it is
    fwdzero     the forward zeroes every grouped product's rows of no group
    oldcombine  the gather into expert order, PR 36's scatter-add combine
    oldgather   PR 36's plain gather (autodiff transpose), the gather combine

Every variant but ``none`` needs the gather form, which
``tools/moe_gather_form.patch`` restores over ``models/moe.py``::

    c=$(git log -1 --format=%H -- tools/moe_gather_form.patch)  # its moe.py
    mkdir -p scratch/g && git archive $c | tar -x -C scratch/g
    (cd scratch/g && git apply tools/moe_gather_form.patch)
    cd scratch/g && python3 tools/moe_gather_diag.py none oldcombine oldgather fwdzero

On the chip (about a minute a variant, warm).  ``DIAG_TOY=1`` runs the cell's
toy size on the CPU, where every variant reads its ``eval_loss``.
"""

import functools
import json
import os
import sys
import time
from typing import Any

sys.path.insert(0, ".")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402
from jax import lax  # noqa: E402

from chipbench import run as run_mod  # noqa: E402
from chipbench import weights_mellum2  # noqa: E402
from chipbench.builders import spmd_train_moe  # noqa: E402
from torchgpipe_tpu.models import moe as M  # noqa: E402
from torchgpipe_tpu.models.moe import llama_moe_spmd  # noqa: E402
from torchgpipe_tpu.models.transformer import cross_entropy  # noqa: E402
from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh  # noqa: E402

ORIG = {"_expert_sum": M._expert_sum}


def variant_sum(xf: Any, w_gate: Any, w_up: Any, w_down: Any, gates: Any,
                order: Any, inv: Any, group_sizes: Any, zero: bool = False,
                combine: str = "new", gather: str = "new") -> Any:
    """The gather form's ``_expert_sum`` with one piece swapped back."""
    t, d = xf.shape
    k = order.shape[0] // t
    held = jnp.sum(group_sizes)
    in_group = (jnp.arange(order.shape[0]) < held)[:, None]

    def grouped(x: Any, w: Any) -> Any:
        if not zero:
            return lax.ragged_dot(x, w, group_sizes)
        x = jnp.where(in_group, x, 0.0)
        return jnp.where(in_group, lax.ragged_dot(x, w, group_sizes), 0.0)

    if gather == "new":
        xs = M._permute_rows(k, xf, order % t, inv)
    else:
        xs = xf[order % t]
    h = jax.nn.silu(grouped(xs, w_gate)) * grouped(xs, w_up)
    ys = grouped(h, w_down)
    if combine == "new":
        ys = M._permute_rows(1, ys, inv, order)
        ys = jnp.where((inv < held)[:, None], ys, 0.0)
        y = ys.astype(jnp.float32) * gates.astype(jnp.float32)[:, None]
        return jnp.sum(y.reshape(k, t, d), axis=0).astype(ys.dtype)
    gate_sorted = jnp.where(inv < held, gates, 0.0)[order]
    ys = jnp.where(gate_sorted[:, None] != 0.0, ys, 0.0)
    return jnp.zeros(xf.shape, ys.dtype).at[order % t].add(
        ys * gate_sorted.astype(ys.dtype)[:, None])


def apply_variant(v: str) -> None:
    M._expert_sum = ORIG["_expert_sum"]
    if v == "none":
        return
    if not hasattr(M, "_permute_rows"):
        raise SystemExit(f"variant {v!r} needs tools/moe_gather_form.patch applied")
    M._expert_sum = {
        "fwdzero": functools.partial(ORIG["_expert_sum"], zero=True),
        "oldcombine": functools.partial(variant_sum, combine="old"),
        "oldgather": functools.partial(variant_sum, gather="old"),
    }[v]


def main() -> None:
    run_mod.enable_compile_cache()
    patch = None
    if os.environ.get("DIAG_TOY"):
        sys.path.insert(0, "chipbench/tests")
        from test_mellum2 import TOY as patch
    cell = run_mod.make_cell("mellum2.train-4x8192", 3700005001, 1.0, False, patch)
    m, tr = cell.config, cell.config["train"]
    pool = weights_mellum2.token_batches(m, cell.traffic, cell.seed, tr["batch"], tr["seq"])
    batches = [(jnp.asarray(x[:, :-1]), jnp.asarray(x[:, 1:])) for x in pool]
    for v in sys.argv[1:] or ["none"]:
        t0 = time.time()
        apply_variant(v)
        cfg, moe = spmd_train_moe.program_config(m)
        block, pre, post = llama_moe_spmd(cfg, moe, 1)
        pipe = SpmdGPipe(block, 1, make_mesh(1, devices=jax.devices()[:1]),
                         chunks=tr["chunks"], loss_fn=cross_entropy, pre=pre, post=post)
        params = pipe.place(weights_mellum2.stack_for_stages(
            weights_mellum2.make_flat(m, cell.seed), 1))
        out: dict = {"variant": v, "cwd": os.path.basename(os.getcwd())}
        if v == "none":
            out["eval_loss0"] = float(pipe.eval_loss(params, *batches[0]))
        opt = optax.adamw(**tr["optimizer"])
        opt_state = pipe.place_tree(opt.init(params))
        step = pipe.make_train_step(opt)
        losses = []
        for i in range(3):
            loss, params, opt_state, counts = step(
                params, opt_state, *batches[i % len(batches)])
            losses.append(float(loss))
            if i == 0:
                out["counts_step0"] = int(np.asarray(counts).sum())
        out["losses"] = losses
        out["seconds"] = round(time.time() - t0, 1)
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
