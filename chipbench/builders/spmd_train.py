"""Builder ``spmd-train``: the program's ``SpmdGPipe`` fused train step, one
pipeline stage per chip, driven by the ``train-fixed`` mix.

Set-up builds ONE object (the compiled step with its state), drives it
through its first steps from the seed on the window's own feed, reads what
the reference will be compared with, and hands the same object to the
window.  The reference runs after the window, once the peak has been read
and the program's state is freed.
"""

from __future__ import annotations

import dataclasses
import gc
import time
import types
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from chipbench import weights
from chipbench.common import (Cell, Check, Outcome, peak_memory_bytes, process_age_s, resolve,
                              worst_leaf_gap)
from chipbench.reference import TrainReference

# The program under test.
from torchgpipe_tpu.models.hf_interop import config_from_hf
from torchgpipe_tpu.models.transformer import cross_entropy, llama_spmd
from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh

HF_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
           "num_key_value_heads", "num_hidden_layers", "vocab_size",
           "sliding_window", "rope_theta", "rms_norm_eps",
           "tie_word_embeddings")


def program_config(m: Dict[str, Any]) -> Any:
    hf = types.SimpleNamespace(**{k: m[k] for k in HF_KEYS})
    return dataclasses.replace(config_from_hf(hf), dtype=weights.DTYPES[m["torch_dtype"]])


@jax.jit
def _stacked_norms(tree: Dict[str, Any], base: Any = None) -> Dict[str, Any]:
    """Per-leaf norms of a {'pre','blocks','post'} tree (of its difference
    from ``base`` where given); a stacked block leaf gives one per stage."""
    def norm(a, b, keep_first):
        a = a.astype(jnp.float32)
        if b is not None:
            a = a - b.astype(jnp.float32)
        axes = tuple(range(1, a.ndim)) if keep_first else None
        return jnp.sqrt(jnp.sum(jnp.square(a), axis=axes))

    def group(name, keep_first, index=None):
        got = tree[name] if index is None else tree[name][index]
        was = None if base is None else (
            base[name] if index is None else base[name][index])
        return {k: norm(v, None if was is None else was[k], keep_first)
                for k, v in got.items()}

    return {
        "pre": group("pre", False),
        "blocks": tuple(group("blocks", True, i) for i in range(len(tree["blocks"]))),
        "post": group("post", False),
    }


def flat_order(norms: Dict[str, Any], n_stages: int) -> List[float]:
    """Stacked per-stage norms in the flat list's leaf order."""
    norms = jax.device_get(norms)
    per = len(norms["blocks"])
    out = [float(norms["pre"][k]) for k in sorted(norms["pre"])]
    for layer in range(n_stages * per):
        b = norms["blocks"][layer % per]
        out += [float(b[k][layer // per]) for k in sorted(b)]
    return out + [float(norms["post"][k]) for k in sorted(norms["post"])]


def compare(got: Dict[str, Any], ref: Dict[str, Any], limits: Dict[str, float]) -> List[Check]:
    """The numbers that decide ``correct`` for a training cell."""
    losses = max(abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"]))
    # Leaves whose reference gradient is nought to rounding move under Adam
    # by round-off alone: left out of the change by a rule on the gradient.
    grads = np.asarray(ref["grad_norms"])
    dead = grads < 1e-3 * np.median(grads)
    return [
        Check("loss_rel_gap", losses, limits["loss_rel_gap"]),
        Check("grad_norm_gap", worst_leaf_gap(got["grad_norms"], ref["grad_norms"]),
              limits["grad_norm_gap"]),
        Check("change_norm_gap",
              worst_leaf_gap(got["change_norms"], ref["change_norms"], dead),
              limits["change_norm_gap"]),
    ]


def reference_readings(m: Dict[str, Any], seed: int, batches: np.ndarray, steps: int,
                       opt: Dict[str, float], devices: Sequence[Any],
                       low: bool = False) -> Dict[str, Any]:
    """The reference's losses, first gradient norms and change after ``steps``."""
    ref = TrainReference(m, weights.make_flat(m, seed), opt, low=low, devices=devices)
    losses, first = [], None
    for i in range(steps):
        loss, norms = ref.step(batches[i % len(batches)])
        losses.append(loss)
        first = norms if first is None else first
    change = ref.change_norms(weights.make_flat(m, seed))
    return {"losses": losses, "grad_norms": first, "change_norms": change}


def run(cell: Cell) -> Outcome:
    m, tr = cell.config, cell.config["train"]
    devices = jax.devices()[:cell.chips]
    n_stages, opt_cfg, ref_steps = tr["stages"], tr["optimizer"], tr["reference_steps"]
    if len(devices) != n_stages:
        raise RuntimeError(f"{n_stages} stages need {n_stages} chips, have {len(devices)}")
    cfg = program_config(m)
    block, pre, post = llama_spmd(cfg, n_stages)
    pipe = SpmdGPipe(block, n_stages, make_mesh(n_stages, devices=devices),
                     chunks=tr["chunks"], loss_fn=cross_entropy, pre=pre, post=post)
    params = pipe.place(weights.stack_for_stages(weights.make_flat(m, cell.seed), n_stages))
    opt = optax.adamw(**opt_cfg)
    opt_state = pipe.place_tree(opt.init(params))
    step = cell.tap("train_step", pipe.make_train_step(opt))

    rows, seq = tr["batch"], tr["seq"]
    pool = resolve(cell.traffic["generator"])(
        cell.traffic, cell.seed, rows, seq, m["vocab_size"])
    batches = [cell.tap("batch", (jnp.asarray(b[:, :-1]), jnp.asarray(b[:, 1:])))
               for b in pool]

    def feed(i: int) -> Tuple[jax.Array, jax.Array]:
        return batches[i % len(batches)]

    # The first steps, through the window's own call and feed.
    got: Dict[str, Any] = {"losses": []}
    for i in range(ref_steps):
        loss, params, opt_state = step(params, opt_state, *feed(i))
        got["losses"].append(float(loss))
        if i == 0:
            mu = next(s for s in opt_state if hasattr(s, "mu")).mu
            got["grad_norms"] = [
                g / (1.0 - opt_cfg["b1"])
                for g in flat_order(_stacked_norms(mu), n_stages)]
            del mu
    start = pipe.place(weights.stack_for_stages(weights.make_flat(m, cell.seed), n_stages))
    got["change_norms"] = flat_order(_stacked_norms(params, start), n_stages)
    del start

    # The window: one step in flight, every loss fetched, ends on a fetch.
    programs = cell.meter.programs
    setup_s = process_age_s()
    t0 = time.perf_counter()
    done, i, pending, losses = 0, ref_steps, None, []
    while True:
        with jax.profiler.TraceAnnotation("cb.train_step"):
            loss, params, opt_state = step(params, opt_state, *feed(i))
        i += 1
        if pending is not None:
            losses.append(float(pending))
            done += 1
            if time.perf_counter() - t0 >= cell.seconds:
                break
        pending = loss
    losses.append(float(loss))
    done += 1
    elapsed = time.perf_counter() - t0
    compiled_in_window = cell.meter.programs - programs

    if cell.trace:
        jax.profiler.start_trace(str(cell.trace_dir))
        for _ in range(tr["trace_steps"]):
            with jax.profiler.TraceAnnotation("cb.train_step"):
                loss, params, opt_state = step(params, opt_state, *feed(i))
            i += 1
        float(loss)
        jax.profiler.stop_trace()

    peak = peak_memory_bytes(devices)
    del params, opt_state, step, pipe, batches, loss, pending
    gc.collect()
    jax.clear_caches()
    gc.collect()

    ref = reference_readings(m, cell.seed, pool, ref_steps, opt_cfg, devices)
    checks = compare(got, ref, tr["limits"])
    finite = bool(np.isfinite(losses).all())
    checks.append(Check("nonfinite_losses", 0.0 if finite else 1.0, 0.0))
    checks.append(Check("compiled_in_window", float(compiled_in_window), 0.0))
    tokens = done * rows * seq
    return Outcome(
        attempted=done, failed=0 if finite else done,
        end_to_end={"train_tokens_per_s": tokens / elapsed, "setup_s": setup_s},
        checks=checks,
        facts={"steps": done, "tokens": tokens, "elapsed_s": elapsed,
               "rows": rows, "seq": seq, "depth": m["num_hidden_layers"],
               "chunks": tr["chunks"], "stages": n_stages,
               "first_losses": got["losses"], "reference_losses": ref["losses"]},
        memory_peak_bytes=peak,
    )
