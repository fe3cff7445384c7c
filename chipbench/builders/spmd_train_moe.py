"""Builder ``spmd-train-moe``: the program's ``SpmdGPipe`` fused train step
over ``llama_moe_spmd`` (attention described per layer, routed experts on a
chip's share), driven by the ``train-fixed`` mix.

As ``spmd_train``: set-up builds ONE object (the compiled step with its
state), drives it through its first steps from the seed on the window's own
feed, reads what the reference will be compared with, and hands the same
object to the window; the reference runs after the window, once the peak has
been read and the program's state is freed.  The step of a block with expert
layers returns the held experts' token counts ``[stages, layers, held]``
fourth; they are fetched with each loss and the expert metrics read their sums.
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

from chipbench import weights_mellum2
from chipbench.common import (Cell, Check, Outcome, peak_memory_bytes, process_age_s,
                              worst_leaf_gap)
from chipbench.reference_mellum2 import TrainReference
from chipbench.weights import DTYPES
from chipbench.weights_axk1 import published

# The program under test.
from torchgpipe_tpu.models.hf_interop import config_from_hf_mixed_moe
from torchgpipe_tpu.models.moe import llama_moe_spmd
from torchgpipe_tpu.models.transformer import cross_entropy
from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh

HF_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads",
           "head_dim", "num_hidden_layers", "vocab_size", "rms_norm_eps", "tie_word_embeddings",
           "attention_bias", "hidden_act", "layer_types", "mlp_layer_types", "sliding_window",
           "rope_parameters", "num_experts_per_tok", "moe_intermediate_size", "norm_topk_prob")


def program_config(m: Dict[str, Any]) -> Tuple[Any, Any]:
    """The program's two config objects from the published record: the
    router keeps the published expert count, the layer holds the file's."""
    hf = types.SimpleNamespace(**{k: m[k] for k in HF_KEYS},
                               num_experts=published(m, "num_experts"))
    cfg, moe = config_from_hf_mixed_moe(hf, held=(m["held_first"], m["num_experts"]))
    return dataclasses.replace(cfg, dtype=DTYPES[m["torch_dtype"]]), moe


@jax.jit
def _leaf_norms(tree: Dict[str, Any], base: Any = None) -> Dict[str, Any]:
    """Per-leaf norms of a {'pre','blocks','post'} tree (of its difference
    from ``base`` where given), each group's leaves in ``jax.tree_util``'s
    order; a stacked block leaf gives one norm a stage."""
    f32 = lambda a: a.astype(jnp.float32)    # noqa: E731
    if base is not None:
        tree = jax.tree_util.tree_map(lambda a, b: f32(a) - f32(b), tree, base)

    def norms(group, keep_first):
        return [jnp.sqrt(jnp.sum(jnp.square(f32(a)),
                                 axis=tuple(range(1, a.ndim)) if keep_first else None))
                for a in jax.tree_util.tree_leaves(group)]

    return {"pre": norms(tree["pre"], False),
            "blocks": [norms(b, True) for b in tree["blocks"]],
            "post": norms(tree["post"], False)}


def flat_order(norms: Dict[str, Any], n_stages: int) -> List[float]:
    """Stacked per-stage norms in the flat list's leaf order."""
    norms = jax.device_get(norms)
    per = len(norms["blocks"])
    out = [float(n) for n in norms["pre"]]
    for layer in range(n_stages * per):
        out += [float(n[layer // per]) for n in norms["blocks"][layer % per]]
    return out + [float(n) for n in norms["post"]]


def reference_readings(m: Dict[str, Any], seed: int, batches: np.ndarray, steps: int,
                       opt: Dict[str, float], low: bool = False) -> Dict[str, Any]:
    """The reference's losses, first gradient norms and change after ``steps``."""
    ref = TrainReference(m, weights_mellum2.make_flat(m, seed), opt, low=low)
    losses, first = [], None
    for i in range(steps):
        loss, norms = ref.step(batches[i % len(batches)])
        losses.append(loss)
        first = norms if first is None else first
    change = ref.change_norms(weights_mellum2.make_flat(m, seed))
    return {"losses": losses, "grad_norms": first, "change_norms": change}


def leaf_names(m: Dict[str, Any]) -> List[str]:
    """The name of every leaf, in the order of every per-leaf list here."""
    return weights_mellum2.leaf_names(jax.eval_shape(lambda: weights_mellum2.make_flat(m, 0)))


def compare(m: Dict[str, Any], got: Dict[str, Any], ref: Dict[str, Any],
            limits: Dict[str, float]) -> List[Check]:
    """The numbers that decide ``correct``: ``spmd_train.compare``'s, with
    the ROUTERS' gradient norms compared on their own.  A top-k near-tie
    that flips between bfloat16 and float32 hidden states moves a token from
    one expert to another, and the routers' gradients feel every such flip;
    under one limit their rounding would hide a fault ten times its size in
    any other leaf."""
    losses = max(abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"]))
    router = np.asarray([name.endswith("router") for name in leaf_names(m)])
    grads = np.asarray(ref["grad_norms"])
    dead = grads < 1e-3 * np.median(grads)     # as spmd_train.compare: none here
    return [
        Check("loss_rel_gap", losses, limits["loss_rel_gap"]),
        Check("grad_norm_gap", worst_leaf_gap(got["grad_norms"], ref["grad_norms"], router),
              limits["grad_norm_gap"]),
        Check("router_grad_norm_gap",
              worst_leaf_gap(got["grad_norms"], ref["grad_norms"], ~router),
              limits["router_grad_norm_gap"]),
        Check("change_norm_gap", worst_leaf_gap(got["change_norms"], ref["change_norms"], dead),
              limits["change_norm_gap"]),
    ]


def load_facts(counts: Sequence[np.ndarray], routed_a_step: int) -> Dict[str, Any]:
    """The held experts' load over steps from each step's counts ``[stages,
    layers, held]``: the assignments that fell on a held expert beside all
    the routers made, and the fullest and the mean held expert, each layer's
    summed."""
    per_layer = np.stack(counts).reshape(len(counts), -1, counts[0].shape[-1])
    held = per_layer.sum(axis=(1, 2))
    return {"moe_routed_assignments": routed_a_step * len(counts),
            "moe_held_assignments": int(held.sum()),
            "moe_expert_tokens_max": int(per_layer.max(axis=2).sum()),
            "moe_expert_tokens_mean": float(per_layer.mean(axis=2).sum()),
            # The load is the step's work: the first and the last step's
            # share say whether the window was steady.
            "held_share_pct": [100.0 * int(held[i]) / routed_a_step for i in (0, -1)]}


def run(cell: Cell) -> Outcome:
    m, tr = cell.config, cell.config["train"]
    devices = jax.devices()[:cell.chips]
    n_stages, opt_cfg, ref_steps = tr["stages"], tr["optimizer"], tr["reference_steps"]
    if len(devices) != n_stages:
        raise RuntimeError(f"{n_stages} stages need {n_stages} chips, have {len(devices)}")
    cfg, moe = cell.tap("program_config", program_config(m))
    block, pre, post = llama_moe_spmd(cfg, moe, n_stages)
    pipe = SpmdGPipe(block, n_stages, make_mesh(n_stages, devices=devices),
                     chunks=tr["chunks"], loss_fn=cross_entropy, pre=pre, post=post)

    def fresh() -> Dict[str, Any]:
        return pipe.place(weights_mellum2.stack_for_stages(
            weights_mellum2.make_flat(m, cell.seed), n_stages))

    params = fresh()
    opt = optax.adamw(**opt_cfg)
    opt_state = pipe.place_tree(opt.init(params))
    step = cell.tap("train_step", pipe.make_train_step(opt))

    rows, seq = tr["batch"], tr["seq"]
    pool = weights_mellum2.token_batches(m, cell.traffic, cell.seed, rows, seq)
    batches = [cell.tap("batch", (jnp.asarray(b[:, :-1]), jnp.asarray(b[:, 1:])))
               for b in pool]

    def feed(i: int) -> Tuple[jax.Array, jax.Array]:
        return batches[i % len(batches)]

    # The first steps, through the window's own call and feed.
    got: Dict[str, Any] = {"losses": []}
    for i in range(ref_steps):
        loss, params, opt_state, *_ = step(params, opt_state, *feed(i))
        got["losses"].append(float(loss))
        if i == 0:
            mu = next(s for s in opt_state if hasattr(s, "mu")).mu
            got["grad_norms"] = [g / (1.0 - opt_cfg["b1"])
                                 for g in flat_order(_leaf_norms(mu), n_stages)]
            del mu
    start = fresh()
    got["change_norms"] = flat_order(_leaf_norms(params, start), n_stages)
    del start

    # The window: one step in flight, every loss fetched, ends on a fetch.
    programs = cell.meter.programs
    setup_s = process_age_s()
    t0 = time.perf_counter()
    done, i, pending, losses, loads = 0, ref_steps, None, [], []
    while True:
        with jax.profiler.TraceAnnotation("cb.train_step"):
            loss, params, opt_state, counts = step(params, opt_state, *feed(i))
        i += 1
        if pending is not None:
            losses.append(float(pending[0]))
            loads.append(np.asarray(pending[1]))
            done += 1
            if time.perf_counter() - t0 >= cell.seconds:
                break
        pending = (loss, counts)
    losses.append(float(loss))
    loads.append(np.asarray(counts))
    done += 1
    elapsed = time.perf_counter() - t0
    compiled_in_window = cell.meter.programs - programs

    traced_loads = []
    if cell.trace:
        jax.profiler.start_trace(str(cell.trace_dir))
        for _ in range(tr["trace_steps"]):
            with jax.profiler.TraceAnnotation("cb.train_step"):
                loss, params, opt_state, counts = step(params, opt_state, *feed(i))
            traced_loads.append(counts)
            i += 1
        float(loss)
        jax.profiler.stop_trace()

    peak = peak_memory_bytes(devices)
    tokens = done * rows * seq
    routed_a_step = rows * seq * m["num_experts_per_tok"] * m["num_hidden_layers"]
    facts: Dict[str, Any] = {
        "steps": done, "tokens": tokens, "elapsed_s": elapsed, "rows": rows, "seq": seq,
        "depth": m["num_hidden_layers"], "chunks": tr["chunks"], "stages": n_stages}
    facts.update(load_facts(loads, routed_a_step))
    if traced_loads:
        traced = load_facts([np.asarray(c) for c in traced_loads], routed_a_step)
        facts["moe_traced_rows_per_product"] = traced["moe_held_assignments"] / (
            tr["trace_steps"] * m["num_hidden_layers"] * tr["chunks"])
    del params, opt_state, step, pipe, batches, loss, pending, counts, traced_loads
    gc.collect()
    jax.clear_caches()
    gc.collect()

    t_ref = time.perf_counter()
    ref = reference_readings(m, cell.seed, pool, ref_steps, opt_cfg)
    reference_s = time.perf_counter() - t_ref
    checks = compare(m, got, ref, tr["limits"])
    finite = bool(np.isfinite(losses).all())
    checks.append(Check("nonfinite_losses", 0.0 if finite else 1.0, 0.0))
    checks.append(Check("compiled_in_window", float(compiled_in_window), 0.0))
    facts.update(first_losses=got["losses"], reference_losses=ref["losses"],
                 notes={"step_ms": 1e3 * elapsed / done, "reference_s": reference_s,
                        "held_share_pct_first_last": facts.get("held_share_pct"),
                        "worst_leaves": worst_leaves(m, got, ref)})
    return Outcome(
        attempted=done, failed=0 if finite else done,
        end_to_end={"train_tokens_per_s": tokens / elapsed, "setup_s": setup_s},
        checks=checks, facts=facts, memory_peak_bytes=peak,
    )


def worst_leaves(m: Dict[str, Any], got: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, str]:
    """Which leaf reads the widest gradient and change gap (for a reader of
    the result line; the comparison itself is ``compare``'s)."""
    names = leaf_names(m)
    out = {}
    for key in ("grad_norms", "change_norms"):
        a, b = np.asarray(got[key], np.float64), np.asarray(ref[key], np.float64)
        gaps = np.abs(a - b) / np.maximum(b, np.median(b))
        out[key] = f"{names[int(np.argmax(gaps))]} {float(np.max(gaps)):.3g}"
    return out
