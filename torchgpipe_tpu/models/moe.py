"""Mixture-of-experts feed-forward with expert parallelism over an ``ep``
mesh axis.

New TPU-native capability — the reference has no expert parallelism at all
(SURVEY.md §2.2: "Expert parallelism (EP / MoE): ABSENT").  Design is
MXU/ICI-first, after the public Switch-Transformer / Mesh-TensorFlow token
dispatch formulation (Fedus et al., arXiv:2101.03961; Lepikhin et al., GShard,
arXiv:2006.16668 — implemented here from the math):

* **Routing** is a dense softmax over experts with top-k selection and a
  static per-expert *capacity*; dispatch/combine are one-hot einsums, so the
  whole layer is batched matmuls (no gather/scatter, MXU-friendly, static
  shapes).  Tokens overflowing an expert's capacity are dropped — the
  residual connection around the MLP carries them through unchanged
  (standard capacity-factor semantics).
* **Expert parallelism**: expert weights ``[E, ...]`` are sharded over the
  ``ep`` mesh axis (E/ep experts per lane) and the *batch* is sharded over
  ``ep`` too (the engine treats ep as an extra data axis).  A tiled
  ``lax.all_to_all`` carries each lane's dispatched token buffers to the
  lanes owning their experts and a second one brings the results home —
  on TPU both ride ICI.  Gradients transpose through the all_to_alls
  automatically; the engine's grad reduction keeps expert-leaf grads
  lane-local (see SpmdGPipe ep handling).
* Outside a bound ep axis (single device, MPMD engine, init-time shape
  inference) every expert is local and the all_to_alls vanish — one code
  path serves both.

Load balancing: with ``MoEConfig.balance_weight > 0`` the layer injects the
Switch/GShard balance penalty's GRADIENT directly — `add_aux_grad` plants a
custom-vjp identity on the layer output whose backward adds
``balance_weight * aux_scale * d(penalty)`` to the parameter cotangents
(``aux_scale`` is the engines' per-micro-batch weighting, see
:mod:`torchgpipe_tpu.auxgrad`).  The engines' scalar *loss value* stays a
pure function of the model output (no auxiliary term ever shows up in the
reported loss); the optimizer still sees exactly the gradients of
``task_loss + balance_weight * mean_over_microbatches(penalty)``
(asserted by ``tests/test_moe.py::
test_balance_weight_injects_exact_aux_gradient``).  With
``balance_weight == 0`` (default) nothing is injected; `router_stats`
returns the same balance/importance metrics from a forward's hidden states
for monitoring or for a hand-rolled balance term in a custom loop.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

Pytree = Any

from torchgpipe_tpu.auxgrad import current_aux_scale
from torchgpipe_tpu.layers import Layer, chain
from torchgpipe_tpu.models.transformer import (
    TransformerConfig,
    _normal,
    layers_per_stage,
    lm_head,
    token_embedding,
    transformer_block,
)
from torchgpipe_tpu.ops.grouped_matmul import grouped_matmul
from torchgpipe_tpu.ops.grouped_matmul import tiles as grouped_matmul_tiles
from torchgpipe_tpu.parallel.ring_attention import axis_bound


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Expert-layer hyperparameters.

    ``capacity_factor`` scales the per-expert token budget.  For the
    default token-choice router:
    ``capacity = ceil(capacity_factor * top_k * tokens / n_experts)`` per
    lane — 1.0 is an exactly-balanced budget; >1 tolerates imbalance; a
    large value (≥ n_experts/top_k) guarantees no token is ever dropped.
    For ``router='expert_choice'`` the paper's formula applies instead
    (``top_k`` plays no role):
    ``capacity = min(tokens, ceil(capacity_factor * tokens / n_experts))``.

    ``balance_weight`` > 0 trains the router against the Switch balance
    penalty ``E * sum(load * importance)`` with that coefficient.  The
    pipeline engines' loss is a pure function of the model output, so the
    penalty's *gradient* is injected at the layer (:func:`add_aux_grad`):
    optimization follows ``task_loss + balance_weight * aux`` exactly,
    while the reported loss value stays the task loss (monitor the penalty
    itself via :func:`router_stats`).
    """

    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    ep_axis: Optional[str] = None
    balance_weight: float = 0.0
    # Token-dispatch implementation: 'dense' builds the classic one-hot
    # [t, E, C] combine/dispatch einsum tensors (all-matmul, best for small
    # routing problems); 'sparse' assigns slots by a stable sort and moves
    # tokens with scatter/gather — O(t*k + E*C*d) memory, the scalable path
    # for large t*E*C (8k tokens x 64 experts would put the dense tensors
    # in the hundreds of MB).  'dropless' removes the capacity concept
    # entirely (megablocks-style, Gale et al. arXiv:2211.15841): tokens are
    # sorted by expert and the expert MLP runs as grouped matmuls over the
    # ragged expert segments (``lax.ragged_dot``) — NO token is ever
    # dropped, and per-step work is exactly ``k*t`` rows regardless of
    # router balance.  Requires ``ep_axis=None``: every expert local, or
    # ``held`` (below) naming this chip's share of them, run without the
    # exchange; with an ep axis the all_to_all needs the static per-lane
    # buffers only the capacity paths provide.  'auto' picks dense or
    # sparse by the dense tensor's size (dropless under ``held``).
    dispatch: str = "auto"
    # Routing direction: 'topk' (default — each token picks its top-k
    # experts; Switch/GShard) or 'expert_choice' (each EXPERT picks its
    # top-capacity tokens; Zhou et al. arXiv:2202.09368).  Expert choice
    # is perfectly load-balanced BY CONSTRUCTION — every expert processes
    # exactly ``capacity`` tokens — so no balance penalty is needed
    # (``balance_weight`` must stay 0); tokens may be served by several
    # experts or by none (the residual around the MLP carries unserved
    # tokens).  Selection looks across the whole (local) batch, so use it
    # for encoder/training workloads, not autoregressive decoding.
    # Requires local experts (``ep_axis=None``); ``dispatch`` and
    # ``top_k`` are ignored (the EC gather/scatter is its own path and
    # ``capacity`` plays top_k's role).
    router: str = "topk"
    # ---- the sigmoid-routed, shared-expert family (DeepSeek-V3 class) -- #
    # Router score: 'softmax' over the experts (Switch/GShard/Mixtral) or
    # 'sigmoid' of each logit on its own.
    scoring: str = "softmax"
    # Normalise the k selected scores to sum 1.  None keeps the historical
    # rule (normalise when top_k > 1; a single choice keeps its raw
    # probability so the router still gets gradient).
    norm_topk: Optional[bool] = None
    # Factor on the (normalised) combine weights: routed_scaling_factor.
    route_scale: float = 1.0
    # Shared experts: a dense SwiGLU of width n_shared * expert width that
    # every token takes, added to the routed sum (params under 'shared').
    n_shared: int = 0
    # Width of an expert's SwiGLU; None -> cfg.mlp_hidden.
    expert_hidden: Optional[int] = None
    # (first, count): this layer HOLDS experts [first, first + count) of
    # the n_experts the router scores — one chip's share of an expert-
    # parallel deployment, run without its exchange.  The router keeps all
    # n_experts outputs and the normalisation runs over all top_k
    # selected; the routed sum runs over the selected experts that are
    # held, the shared expert is computed whole, and what absent experts
    # would add is left out.  Expert params have ``count`` leading rows.
    # Needs the dropless path ('dropless', or 'auto' which then picks it)
    # and no ep_axis: no token is dropped whatever the routing.  None
    # holds every expert.
    held: Optional[Tuple[int, int]] = None
    # How the top_k are chosen from the scores (the published
    # ``topk_method``): 'none' / 'greedy' take the k largest over all
    # experts; 'bias' takes the k largest of ``score + b`` (``b`` the
    # layer's ``router_bias`` [n_experts], a buffer that balancing moves
    # and no gradient does) and weighs them by the scores WITHOUT ``b``
    # (DeepSeek-V3's bias-corrected selection, arXiv:2412.19437 section
    # 2.1.2).  A grouped rule is one more entry of ``_SELECT``.
    select: str = "none"
    # An expert's feed-forward: 'swiglu' (``down(silu(gate u) * up u)``)
    # or 'relu2', ungated (``down(relu(up u) ** 2)``: Nemotron-H's; no
    # ``w_gate``), for the routed and the shared experts alike.  'relu2'
    # is computed on the dropless path.
    act: str = "swiglu"
    # Width of the shared expert; None -> n_shared * the expert width.
    shared_hidden: Optional[int] = None

    @property
    def held_range(self) -> Tuple[int, int]:
        return self.held if self.held is not None else (0, self.n_experts)


@jax.custom_vjp
def _aux_inject(
    y: jnp.ndarray,
    aux: jnp.ndarray,
    scaled_weight: jnp.ndarray,
) -> jnp.ndarray:
    del aux, scaled_weight
    return y


def _aux_inject_fwd(
    y: jnp.ndarray,
    aux: jnp.ndarray,
    scaled_weight: jnp.ndarray,
) -> Tuple[jnp.ndarray, Tuple]:
    # scaled_weight is a traced INPUT recorded at the primal call site, so
    # the engine's aux scale is baked in no matter when the vjp rule is
    # elaborated (custom_vjp traces fwd lazily, at linearization time —
    # reading trace-time context here would see the default again).
    return y, scaled_weight


def _aux_inject_bwd(
    res: Tuple,
    g: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    return g, res, jnp.zeros_like(res)


_aux_inject.defvjp(_aux_inject_fwd, _aux_inject_bwd)


def add_aux_grad(
    y: jnp.ndarray,
    aux: jnp.ndarray,
    weight: float,
) -> jnp.ndarray:
    """Identity on ``y`` whose backward adds ``weight * aux_scale`` to
    ``aux``'s cotangent (``aux_scale`` is the engines' trace-time
    micro-batch weighting, :mod:`torchgpipe_tpu.auxgrad`, captured here at
    the call site).

    Differentiating a seed-1 loss ``L(y)`` through this yields the
    gradients of ``L + weight * mean_over_microbatches(aux)`` without
    threading an auxiliary scalar through the engine's loss plumbing.  The
    mechanism behind ``MoEConfig.balance_weight``.  Note the injection is
    relative to a unit cotangent seed (what the engines' ``value_and_grad``
    uses); differentiating ``c * L`` scales task gradients by ``c`` but not
    the injected term.
    """
    scaled = jnp.asarray(weight, jnp.float32) * current_aux_scale()
    return _aux_inject(y, aux, scaled)


def _balance_penalty(
    probs: jnp.ndarray,
    n_experts: int,
    top_k: int = 1,
) -> jnp.ndarray:
    """Switch/GShard balance penalty from router probabilities ``[t, E]``:
    ``(load, importance, E * sum(load * importance))`` — 1.0 iff perfectly
    balanced.  Single source for both the training-time injection
    (``balance_weight``) and the :func:`router_stats` monitoring metric.

    ``load`` is the fraction of routing *assignments* per expert over ALL
    ``top_k`` selection rounds (the same iterative-argmax selection the
    dispatcher uses), so with k=2 a lopsided second choice is penalized
    too, not just the top-1 (Switch's k=1 formulation is the special
    case).  Selections are counted pre-capacity: capacity drops depend on
    token order and would make the penalty discontinuous in it.
    """
    remaining = probs
    sel = jnp.zeros((n_experts,), jnp.float32)
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)
        mask = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)
        sel = sel + jnp.mean(mask, axis=0)
        remaining = remaining * (1.0 - mask)
    load = sel / top_k
    importance = jnp.mean(probs, axis=0)
    return load, importance, n_experts * jnp.sum(load * importance)


def _top_k_select(
    probs: jnp.ndarray,
    k: int,
    bias: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Iterative-argmax top-k routing selection shared by both dispatch
    implementations: per round the highest remaining expert is chosen and
    masked out.  Returns per-round expert indices ``[k, t]``, one-hot masks
    (list of ``[t, E]``) and gate values ``[k, t]`` (raw softmax probs).
    With ``bias [E]`` the choice is made on ``probs + bias`` and the gate
    values are still read from ``probs``: the bias moves WHICH experts a
    token takes, never how much of each."""
    remaining = probs if bias is None else probs + bias
    idxs: List[jnp.ndarray] = []
    masks: List[jnp.ndarray] = []
    gates: List[jnp.ndarray] = []
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)
        mask = jax.nn.one_hot(idx, probs.shape[-1], dtype=probs.dtype)
        idxs.append(idx)
        gates.append(jnp.sum(probs * mask, axis=-1))  # [t]
        masks.append(mask)
        # Scores are positive, so a chosen one is zeroed out of the
        # running; a biased score may not be, and goes to -inf.
        remaining = (remaining * (1.0 - mask) if bias is None
                     else jnp.where(mask > 0, -jnp.inf, remaining))
    return jnp.stack(idxs), masks, jnp.stack(gates)


def _gate_denom(gates: jnp.ndarray, k: int,
                norm: Optional[bool] = None) -> jnp.ndarray:
    # k>1: normalize combine weights over the k selections (GShard).  k=1
    # keeps the raw softmax probability as the gate (Switch) — normalizing
    # would pin it to ~1.0 and starve the router of gradient entirely.
    # ``norm`` (MoEConfig.norm_topk) overrides that rule either way.
    if norm is None:
        norm = k > 1
    return jnp.sum(gates, axis=0) + 1e-9 if norm else jnp.ones(())


# MoEConfig.select -> selection rule over the scores [t, E]: returns
# (indices [k, t], one-hot masks, raw gate values [k, t]).  The rules of
# ``_BIASED`` take the layer's ``router_bias`` third.
_SELECT = {"none": _top_k_select, "greedy": _top_k_select,
           "bias": _top_k_select}
_BIASED = ("bias",)


def _scores(moe: MoEConfig, logits: jnp.ndarray) -> jnp.ndarray:
    if moe.scoring == "sigmoid":
        return jax.nn.sigmoid(logits)
    return jax.nn.softmax(logits, axis=-1)


def _route(
    probs: jnp.ndarray, k: int, moe: Optional[MoEConfig] = None,
    bias: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, List[jnp.ndarray], jnp.ndarray]:
    """Selection and combine weights under ``moe`` (None: the historical
    plain top-k, normalised when k > 1): per-round expert indices
    ``[k, t]``, one-hot masks, and the weights ``[k, t]`` — the selected
    scores, normalised over the k (``norm_topk``) and scaled
    (``route_scale``)."""
    rule = moe.select if moe is not None else "none"
    idxs, masks, raw = _SELECT[rule](
        probs, k, *((bias,) if rule in _BIASED else ()))
    gates = raw / _gate_denom(raw, k, None if moe is None else moe.norm_topk)
    if moe is not None and moe.route_scale != 1.0:
        gates = gates * moe.route_scale
    return idxs, masks, gates


def _top_k_dispatch(
    probs: jnp.ndarray,
    k: int,
    capacity: int,
    moe: Optional[MoEConfig] = None,
    bias: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense dispatch/combine tensors from router probabilities.

    probs: ``[t, E]`` f32.  Returns ``combine [t, E, C]`` (gate weights at
    the token's buffer slot, zero where dropped) and ``dispatch`` (its
    boolean support).  Slots are assigned first-come-first-served in token
    order, k-th choices after all (k-1)-th choices (Switch/GShard order).
    """
    t, E = probs.shape
    _, masks, gates_kt = _route(probs, k, moe, bias)
    gates = [gates_kt[kk] for kk in range(k)]

    combine = jnp.zeros((t, E, capacity), probs.dtype)
    counts = jnp.zeros((E,), probs.dtype)
    for kk in range(k):
        mask = masks[kk]
        pos_in_e = jnp.cumsum(mask, axis=0) - 1.0 + counts  # [t, E]
        counts = counts + jnp.sum(mask, axis=0)
        pos = jnp.sum(pos_in_e * mask, axis=-1).astype(jnp.int32)  # [t]
        keep = (pos < capacity) & (jnp.sum(mask, axis=-1) > 0)
        gate_k = jnp.where(keep, gates[kk], 0.0)
        slot = jax.nn.one_hot(pos, capacity, dtype=probs.dtype)  # [t, C]
        combine = combine + (
            mask[:, :, None] * slot[:, None, :] * gate_k[:, None, None]
        )
    dispatch = combine > 0.0
    return combine, dispatch


def _flat_assignment(
    probs: jnp.ndarray,
    k: int,
    moe: Optional[MoEConfig] = None,
    bias: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Shared routing prologue for the sort-based dispatch paths.

    Flattens the top-k routing into per-assignment arrays of length
    ``k*t`` in k-major order (assignment ``i`` = choice round ``i // t``
    of token ``i % t``) and expert-sorts them: returns ``experts`` (int32
    expert id, unsorted), ``gates`` (normalized combine weight, unsorted),
    ``order`` (the stable expert sort — token order preserved within an
    expert, round kk strictly after round kk-1) and ``counts [E]`` (tokens
    per expert).  Both the capacity ('sparse') and capacity-free
    ('dropless') paths build on exactly this — their equivalence to the
    dense one-hot path is load-bearing and oracle-tested.
    """
    idxs, _, gates_kt = _route(probs, k, moe, bias)
    experts = idxs.reshape(-1).astype(jnp.int32)  # [kt], k-major
    gates = gates_kt.reshape(-1)
    order = jnp.argsort(experts, stable=True)
    counts = jnp.bincount(experts, length=probs.shape[1])
    return experts, gates, order, counts


def _sparse_assignment(
    probs: jnp.ndarray,
    k: int,
    capacity: int,
    moe: Optional[MoEConfig] = None,
    bias: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sort-based slot assignment — identical FCFS semantics to
    :func:`_top_k_dispatch` (token order within a choice round, round kk
    strictly after round kk-1) with O(t*k) bookkeeping instead of the dense
    ``[t, E, C]`` tensors.

    Returns flat per-assignment arrays of length ``k*t`` in k-major order:
    ``experts`` (int32 expert id), ``gates`` (normalized combine weight),
    ``keep`` (bool, False where the expert's capacity overflowed) and
    ``slot`` (int32 position in the expert buffer, 0 where dropped).
    """
    t = probs.shape[0]
    kt = k * t
    experts, gates, order, counts = _flat_assignment(probs, k, moe, bias)
    sorted_e = experts[order]
    starts = jnp.cumsum(counts) - counts  # segment start per expert
    # Position within the expert group IS the dense path's slot number.
    pos_sorted = (jnp.arange(kt) - starts[sorted_e]).astype(jnp.int32)
    pos = jnp.zeros((kt,), jnp.int32).at[order].set(pos_sorted)
    keep = pos < capacity
    slot = jnp.where(keep, pos, 0)
    return experts, gates, keep, slot


def _dropless_assignment(
    probs: jnp.ndarray,
    k: int,
    moe: Optional[MoEConfig] = None,
    bias: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Expert-sorted token assignment for the dropless path.

    Returns ``(order, tok_sorted, group_sizes, gates)`` where
    ``tok_sorted`` maps expert-sorted rows back to source tokens,
    ``group_sizes [E]`` are the ragged segment lengths, and ``gates`` are
    the normalized combine weights in *unsorted* k-major order."""
    t = probs.shape[0]
    _, gates, order, counts = _flat_assignment(probs, k, moe, bias)
    tok = jnp.arange(k * t) % t
    return order, tok[order], counts.astype(jnp.int32), gates


def _expert_ffn(expert_in: jnp.ndarray, params: Pytree) -> jnp.ndarray:
    """Batched per-expert SwiGLU on ``[E, C, d]`` buffers (MXU einsums) —
    the one expert-compute block shared by every dispatch path that uses
    rectangular expert buffers (the dropless path's ragged twin is
    :func:`_expert_sum`)."""
    h = jax.nn.silu(
        jnp.einsum("ecd,edh->ech", expert_in, params["w_gate"])
    ) * jnp.einsum("ecd,edh->ech", expert_in, params["w_up"])
    return jnp.einsum("ech,ehd->ecd", h, params["w_down"])


def _inverse_order(key: jnp.ndarray, n: int) -> jnp.ndarray:
    """The inverse of ``argsort(key, stable=True)`` for keys in ``[0, n]``
    (assignment -> its expert-sorted position) with no second sort and no
    scatter: where key ``key[j]``'s group starts plus the number of earlier
    assignments with that key, a cumulative sum over the key's one-hot."""
    hit = key[:, None] == jnp.arange(n + 1)  # [kt, n + 1]
    rank = jnp.cumsum(hit, axis=0, dtype=jnp.int32)
    starts = jnp.cumsum(rank[-1]) - rank[-1]
    return jnp.sum(jnp.where(hit, rank - 1 + starts, 0), axis=1)


def _ffn(x: jnp.ndarray, w_gate: Optional[jnp.ndarray], w_up: jnp.ndarray,
         w_down: jnp.ndarray, product: Any = jnp.matmul) -> jnp.ndarray:
    """One expert's feed-forward through ``product`` (a plain or a
    grouped product): the SwiGLU, or with no ``w_gate`` the ungated
    ``down(relu(up x) ** 2)`` (``MoEConfig.act='relu2'``)."""
    if w_gate is None:
        return product(jnp.square(jax.nn.relu(product(x, w_up))), w_down)
    return product(
        jax.nn.silu(product(x, w_gate)) * product(x, w_up), w_down)


def _on_tpu() -> bool:
    """Whether the served grouped products compile the Pallas kernel (off
    a TPU they are ``lax.ragged_dot``; a test patches this to run the
    kernel interpreted)."""
    return jax.devices()[0].platform == "tpu"


def _served_product(x: jnp.ndarray, w: jnp.ndarray,
                    group_sizes: jnp.ndarray) -> jnp.ndarray:
    """The served expert sum's grouped product: on a TPU, where it tiles
    the shape, :func:`~torchgpipe_tpu.ops.grouped_matmul.grouped_matmul`
    (tiles from the shape, each bank read in the layout the chip stores
    it); else ``lax.ragged_dot``.  Either leaves the rows of no group as
    they come."""
    itemsize = jnp.dtype(jnp.result_type(x, w)).itemsize
    if _on_tpu() and grouped_matmul_tiles(
            *x.shape, w.shape[2], w.shape[0], itemsize) is not None:
        return grouped_matmul(
            x, w, group_sizes,
            interpret=jax.devices()[0].platform != "tpu")
    return lax.ragged_dot(x, w, group_sizes)


def _expert_sum(
    xf: jnp.ndarray, w_gate: Optional[jnp.ndarray], w_up: jnp.ndarray,
    w_down: jnp.ndarray, gate_sorted: jnp.ndarray, tok_sorted: jnp.ndarray,
    group_sizes: jnp.ndarray, zero: Optional[str] = None,
    inv: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """The dropless path's expert sum ``[t, d]``: the expert-sorted rows
    ``xf[tok_sorted]`` through the SwiGLU as three grouped products over
    ``group_sizes`` and, times their gates, added back to their tokens:
    by a scatter-add, or, given ``inv`` (:func:`_inverse_order` of the
    k-major assignments' sort), by a gather back to assignment order and
    a float32 sum of the k choices (the served forward only: 1.59 ->
    0.12 ms a layer at A.X-K1's prefill, chip run, PR 37; under
    differentiation it read a wrong loss in ``mellum2.train-4x8192``,
    ``PERF.md`` section 7).

    Which grouped product runs: given ``inv`` (the served forward, never
    differentiated) :func:`_served_product`, on a TPU the Pallas kernel
    ``grouped_matmul``; otherwise, every product under ``jax.grad`` among
    them, ``lax.ragged_dot``, the compiler's grouped product.

    Rows behind the last group (``held``: an absent expert's; a masked
    position's) belong to no group and have gate 0.  The TPU's grouped
    product leaves such rows of its RESULT as they were in memory, in the
    forward and in its transposes alike (the CPU lowering writes zeros):
    ``zero='result'`` zeroes them in the last product's result, which is
    all a forward needs; ``zero='all'`` on both sides of every product,
    which the transposes need (a NaN there reaches every token's
    gradient through the transposed gather: chip run, PR 32)."""
    in_group = (
        jnp.arange(tok_sorted.shape[0]) < jnp.sum(group_sizes))[:, None]

    def grouped(x, w):
        if inv is not None:
            return _served_product(x, w, group_sizes)
        if zero != "all":
            return lax.ragged_dot(x, w, group_sizes)
        x = jnp.where(in_group, x, 0.0)
        return jnp.where(in_group, lax.ragged_dot(x, w, group_sizes), 0.0)

    xs = xf[tok_sorted]  # [kt, d] expert-sorted
    ys = _ffn(xs, w_gate, w_up, w_down, grouped)
    if inv is not None:  # the rows of no group are selected out, as below
        gates = gate_sorted[inv][:, None]
        y = jnp.where(gates != 0.0, ys[inv], 0.0).astype(jnp.float32) * gates
        return y.reshape(-1, *xf.shape).sum(0).astype(ys.dtype)
    if zero == "result":
        ys = jnp.where(gate_sorted[:, None] != 0.0, ys, 0.0)
    return (
        jnp.zeros(xf.shape, ys.dtype)
        .at[tok_sorted]
        .add(ys * gate_sorted.astype(ys.dtype)[:, None])
    )


@jax.custom_vjp
def _held_expert_sum(xf, w_gate, w_up, w_down, gate_sorted, tok_sorted,
                     group_sizes, key):
    """:func:`_expert_sum` where rows lie in no group, with its own
    backward: the forward zeroes what it must and keeps nothing but its
    arguments; the backward recomputes the sum with every product zeroed
    on both sides and transposes that.  Recomputed because under the
    pipeline's stage-wide recomputation every layer's gathered rows
    (``[k*t, d]``, three quarters of them in no group at a quarter of the
    experts held, and their products) would otherwise be alive at once:
    19.4 of 15.75 GiB at 8 layers x 65,536 rows (described-chip compile,
    PR 32).  Undifferentiated (served) it combines by gathers, by ``key``'s
    inverse order, and its grouped products are :func:`_served_product`'s
    (on a TPU the Pallas kernel); differentiated, forward and backward
    scatter, and every product is ``lax.ragged_dot``."""
    inv = _inverse_order(key, group_sizes.shape[0])
    return _expert_sum(xf, w_gate, w_up, w_down, gate_sorted, tok_sorted,
                       group_sizes, zero="result", inv=inv)


def _held_expert_sum_fwd(*args):
    args = args[:-1]  # the key: only the served combine reads it
    return _expert_sum(*args, zero="result"), args


def _held_expert_sum_bwd(args, g):
    # The barrier ties the recomputation to the cotangent's arrival, as
    # jax.checkpoint's does: without it the compiler merges it with the
    # forward's own products and keeps every layer's rows alive after all.
    args, g = lax.optimization_barrier((args, g))
    *diff, tok_sorted, group_sizes = args
    _, vjp = jax.vjp(
        lambda *d: _expert_sum(*d, tok_sorted, group_sizes, zero="all"),
        *diff)
    return (*vjp(g), None, None, None)


_held_expert_sum.defvjp(_held_expert_sum_fwd, _held_expert_sum_bwd)


def moe_mlp(cfg: TransformerConfig, moe: MoEConfig, *, name: str = "moe") -> Layer:
    """Top-k routed expert SwiGLU feed-forward on ``[b, s, dim]`` states.

    Plug into :func:`~torchgpipe_tpu.models.transformer.transformer_block`
    via its ``mlp=`` argument; params: f32 ``router [dim, E]`` plus expert
    weights ``w_gate/w_up [E, dim, hidden]``, ``w_down [E, hidden, dim]``
    (sharded over ``moe.ep_axis`` when set).
    """
    dim, hidden = cfg.dim, moe.expert_hidden or cfg.mlp_hidden
    E, K = moe.n_experts, moe.top_k
    dt = cfg.dtype
    if K > E:
        raise ValueError(f"top_k={K} exceeds n_experts={E}")
    if moe.scoring not in ("softmax", "sigmoid"):
        raise ValueError(
            f"MoEConfig.scoring={moe.scoring!r}: expected 'softmax' or "
            "'sigmoid'"
        )
    if moe.act not in ("swiglu", "relu2"):
        raise ValueError(
            f"MoEConfig.act={moe.act!r}: expected 'swiglu' or 'relu2'"
        )
    if moe.select not in _SELECT:
        raise ValueError(
            f"MoEConfig.select={moe.select!r}: the selection rules "
            f"computed here are {sorted(_SELECT)}"
        )
    first, n_held = moe.held_range
    if moe.held is not None:
        if not (0 <= first and n_held >= 1 and first + n_held <= E):
            raise ValueError(
                f"held={moe.held} is not a range [first, first + count) "
                f"of the {E} experts"
            )
        if (moe.dispatch not in ("auto", "dropless")
                or moe.ep_axis is not None
                or moe.router != "topk"):
            raise ValueError(
                "held=(first, count) computes this chip's experts' part "
                "through the dropless path, without the exchange: it "
                "needs dispatch='dropless' (or 'auto'), ep_axis=None and "
                "router='topk'"
            )
    dropless = moe.dispatch == "dropless" or moe.held is not None
    if moe.act == "relu2" and not dropless:
        raise ValueError(
            "act='relu2' experts are computed on the dropless path: "
            "dispatch='dropless' or held=(first, count)"
        )
    gated = moe.act == "swiglu"
    if moe.dispatch not in ("auto", "dense", "sparse", "dropless"):
        raise ValueError(
            "MoEConfig.dispatch must be 'auto'|'dense'|'sparse'|'dropless'"
        )
    if moe.dispatch == "dropless" and moe.ep_axis is not None:
        raise ValueError(
            "dispatch='dropless' needs local experts (ep_axis=None): the "
            "ragged expert segments have data-dependent sizes, but the ep "
            "all_to_all exchanges static per-lane buffers — use the "
            "capacity paths ('auto'/'dense'/'sparse') with ep, or shard "
            "the expert weights over tp instead"
        )
    if moe.router not in ("topk", "expert_choice"):
        raise ValueError(
            "MoEConfig.router must be 'topk' or 'expert_choice'"
        )
    if moe.router == "expert_choice":
        if moe.ep_axis is not None:
            raise ValueError(
                "router='expert_choice' needs local experts "
                "(ep_axis=None): each expert selects its top-capacity "
                "tokens over the whole local batch, which with sharded "
                "experts would need a cross-lane token gather the "
                "capacity all_to_all does not provide"
            )
        if moe.balance_weight > 0.0:
            raise ValueError(
                "router='expert_choice' is perfectly balanced by "
                "construction (every expert takes exactly `capacity` "
                "tokens); set balance_weight=0"
            )

    def init(rng, in_spec):
        del in_spec
        ks = jax.random.split(rng, 4)
        std = dim ** -0.5
        params = {
            # f32 router: routing decisions are argmaxes over near-ties;
            # keeping them out of bf16 avoids batch-dependent flips.
            "router": _normal(ks[0], (dim, E), std, jnp.float32),
            **({"router_bias": jnp.zeros((E,), jnp.float32)}
               if moe.select in _BIASED else {}),
            "w_gate": _normal(ks[1], (n_held, dim, hidden), std, dt),
            "w_up": _normal(ks[2], (n_held, dim, hidden), std, dt),
            "w_down": _normal(
                ks[3], (n_held, hidden, dim), hidden ** -0.5, dt),
        }
        if moe.n_shared:
            sh = moe.shared_hidden or moe.n_shared * hidden
            kss = jax.random.split(ks[0], 4)
            params["shared"] = {
                "w_gate": _normal(kss[1], (dim, sh), std, dt),
                "w_up": _normal(kss[2], (dim, sh), std, dt),
                "w_down": _normal(kss[3], (sh, dim), sh ** -0.5, dt),
            }
        if not gated:
            del params["w_gate"]
            params.get("shared", {}).pop("w_gate", None)
        return params, ()

    def apply(params, state, x, *, rng=None, train=True):
        del rng
        return forward(params, x, train=train)[0], state

    def forward(params, x, valid=None, *, train=False):
        """``(y, counts)``: the layer's output and, int32 ``[held]``,
        the tokens routed to each held expert (positions where ``valid
        [b, s]`` is False left out of the count; their rows are still
        computed, as every masked row of a step is)."""
        b, s, d = x.shape
        t = b * s
        xf = x.reshape(t, d)

        ep_active = axis_bound(moe.ep_axis)
        # Per-lane capacity from the *local* token count (static shape).
        if moe.router == "expert_choice":
            # EC paper formula: capacity = c * t / E (top_k plays no role);
            # clamp to t — an expert cannot take more tokens than exist.
            capacity = min(t, max(1, math.ceil(moe.capacity_factor * t / E)))
        else:
            capacity = max(1, math.ceil(moe.capacity_factor * K * t / E))

        with jax.named_scope("moe.route"):
            logits = xf.astype(jnp.float32) @ params["router"]  # [t, E]
            probs = _scores(moe, logits)
            # The selection's bias (``select='bias'``): a buffer, not a
            # weight, so no gradient reaches it.
            bias = (lax.stop_gradient(params["router_bias"])
                    if moe.select in _BIASED else None)

        def _finish(y, counts=None):
            """Shared epilogue: the shared expert, reshape + optional
            balance-penalty gradient injection (see add_aux_grad /
            MoEConfig.balance_weight)."""
            if moe.n_shared:
                with jax.named_scope("moe.shared"):
                    sp = params["shared"]
                    y = y + _ffn(xf, sp.get("w_gate"), sp["w_up"],
                                 sp["w_down"])
            y = y.reshape(b, s, d).astype(x.dtype)
            if moe.balance_weight > 0.0 and train:
                _, _, aux = _balance_penalty(probs, E, K)
                y = add_aux_grad(y, aux, moe.balance_weight)
            return y, counts

        if moe.router == "expert_choice":
            # Expert-choice routing (Zhou et al. arXiv:2202.09368): each
            # expert takes its top-`capacity` tokens by router score —
            # perfect static load balance, no drops by overflow (a token
            # simply may not be chosen; the block's residual carries it).
            # score^T [E, t] -> per-expert top-C token ids + gates.
            gates_ec, idx_ec = lax.top_k(probs.T, capacity)  # [E, C]
            expert_in = xf[idx_ec]  # [E, C, d] gather
            out = _expert_ffn(expert_in, params)
            y = (
                jnp.zeros((t, d), out.dtype)
                .at[idx_ec.reshape(-1)]
                .add((out * gates_ec[..., None].astype(out.dtype))
                     .reshape(-1, d))
            )
            return _finish(y)

        if dropless:
            # Megablocks-style dropless experts: sort the k*t assignments
            # by expert and run the SwiGLU as grouped matmuls over the
            # ragged segments (``_expert_sum``: lax.ragged_dot, or served
            # on a TPU the Pallas grouped_matmul).  No capacity, no drops,
            # no [E, C, d] buffers — work is exactly k*t rows however
            # unbalanced the router is.
            # Under ``held`` the sort key is the LOCAL expert id, with
            # every assignment to an absent expert (or of a masked
            # position) keyed past the last held one: the held experts'
            # segments come first and are the only groups; the rows
            # behind them belong to no group and their gates are 0.
            with jax.named_scope("moe.route"):
                experts, gates, _, _ = _flat_assignment(
                    probs, K, moe, bias)
                tok = jnp.arange(K * t) % t
                local = experts - first
                mine = (local >= 0) & (local < n_held)
                if valid is not None:
                    mine = mine & valid.reshape(t)[tok]
                key = jnp.where(mine, local, n_held)
                order = jnp.argsort(key, stable=True)
                group_sizes = jnp.bincount(
                    key, length=n_held + 1)[:n_held].astype(jnp.int32)
                tok_sorted = tok[order]
                gate_sorted = jnp.where(mine, gates, 0.0)[order]
            ragged = moe.held is not None or valid is not None
            args = (xf, params.get("w_gate"), params["w_up"],
                    params["w_down"], gate_sorted, tok_sorted, group_sizes)
            with jax.named_scope("moe.experts"):
                y = _held_expert_sum(*args, key) if ragged else _expert_sum(
                    *args)
            return _finish(y, group_sizes)
        # Dense one-hot einsum dispatch materializes [t, E, C] tensors; past
        # ~16M elements (64MB f32) the sort-based scatter/gather path wins on
        # memory by orders of magnitude (8k tokens x 64 experts: ~670MB vs
        # ~O(t*k) indices).  Both produce bit-equal outputs.
        use_sparse = moe.dispatch == "sparse" or (
            moe.dispatch == "auto" and t * E * capacity > 1 << 24
        )
        if use_sparse:
            experts, gates, keep, slot = _sparse_assignment(
                probs, K, capacity, moe, bias)
            tok = jnp.arange(K * t) % t
            contrib = xf[tok] * keep[:, None].astype(xf.dtype)
            expert_in = (
                jnp.zeros((E, capacity, d), xf.dtype)
                .at[experts, slot].add(contrib)
            )
        else:
            combine, dispatch = _top_k_dispatch(
                probs, K, capacity, moe, bias)
            # Dispatch: [t, E, C] one-hot x [t, d] -> expert buffers [E, C, d].
            expert_in = jnp.einsum(
                "tec,td->ecd", dispatch.astype(xf.dtype), xf
            )
        if ep_active:
            # Route buffers to the lanes owning their experts: split the
            # expert dim, concat received blocks along capacity.
            # [E, C, d] -> [E/ep, ep*C, d]; one ICI all_to_all.
            expert_in = lax.all_to_all(
                expert_in, moe.ep_axis, split_axis=0, concat_axis=1, tiled=True
            )
        # Local expert compute: batched per-expert SwiGLU (MXU einsums).
        out = _expert_ffn(expert_in, params)
        if ep_active:
            # Bring results home: inverse all_to_all.
            out = lax.all_to_all(
                out, moe.ep_axis, split_axis=1, concat_axis=0, tiled=True
            )
        if use_sparse:
            # Gather each kept assignment's result row and fold the k
            # choices back per token (k-major layout: reshape + sum).
            picked = out[experts, slot] * (
                gates * keep.astype(gates.dtype)
            )[:, None].astype(out.dtype)
            y = jnp.sum(picked.reshape(K, t, d), axis=0)
        else:
            y = jnp.einsum("tec,ecd->td", combine.astype(out.dtype), out)
        return _finish(y)

    def validate_mesh(mesh):
        ax = moe.ep_axis
        if ax is None or ax not in mesh.axis_names:
            return
        size = mesh.shape[ax]
        if E % size != 0:
            raise ValueError(
                f"n_experts={E} is not divisible by the ep mesh axis size "
                f"{size}; expert parallelism places whole experts on lanes"
            )

    ep = moe.ep_axis
    return Layer(
        name=name,
        init=init,
        apply=apply,
        meta={
            "kind": "moe_mlp",
            # (params, x, valid=None) -> (y, held experts' token counts);
            # the counts exist on the dropless path (None elsewhere),
            # ``counts_held`` of them.
            "forward_counts": forward,
            "counts_held": n_held if dropless else None,
            "balance_weight": moe.balance_weight,
            "ep_axis": ep,
            "validate_mesh": validate_mesh,
            "param_specs": None if ep is None else {
                "router": P(),
                **({"router_bias": P()} if moe.select in _BIASED else {}),
                "w_gate": P(ep),
                "w_up": P(ep),
                "w_down": P(ep),
            },
            # Static hyperparameters for the analysis stack: the expert
            # all_to_all is gated on a BOUND ep axis, so the planner's
            # block trace (outside shard_map) never sees it — the comm /
            # memory / capacity-overflow models reconstruct the sparse
            # dispatch analytically from this record instead.
            "moe": {
                "n_experts": E,
                "top_k": K,
                "capacity_factor": float(moe.capacity_factor),
                "dispatch": moe.dispatch,
                "router": moe.router,
                "ep_axis": ep,
                "dim": dim,
                "hidden": hidden,
                "itemsize": jnp.dtype(dt).itemsize,
            },
        },
    )


def router_stats(
    params_router: jnp.ndarray,
    x: jnp.ndarray,
    moe: MoEConfig,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Standard router monitoring metrics from hidden states ``[b, s, dim]``:
    ``(load, importance, balance_loss)`` — per-expert assignment fractions
    over all ``top_k`` selection rounds, per-expert mean probabilities, and
    the Switch-style balance penalty ``E * sum(load * importance)``
    (1.0 = perfectly balanced).

    Under ``router='expert_choice'`` the token-choice selection metrics do
    not apply: every expert takes exactly ``capacity`` tokens by
    construction, so ``load`` is reported uniform (1/E) and the penalty is
    exactly 1.0; ``importance`` (mean router probability per expert) stays
    the meaningful dispersion signal."""
    t = x.shape[0] * x.shape[1]
    logits = x.reshape(t, -1).astype(jnp.float32) @ params_router
    probs = jax.nn.softmax(logits, axis=-1)
    if moe.router == "expert_choice":
        E = moe.n_experts
        load = jnp.full((E,), 1.0 / E, jnp.float32)
        importance = jnp.mean(probs, axis=0)
        return load, importance, jnp.float32(1.0)
    return _balance_penalty(probs, moe.n_experts, moe.top_k)


def find_routers(params: Pytree) -> List[jnp.ndarray]:
    """All router matrices in a params pytree, depth-first — lets drivers
    monitor :func:`router_stats` without knowing the nesting (e.g. the
    first MoE block of a GPipe stage list or an SPMD stacked-blocks tree)."""
    out: List[jnp.ndarray] = []

    def walk(p):
        if isinstance(p, dict):
            r = p.get("router")
            if r is not None and hasattr(r, "shape"):
                out.append(r)
            for v in p.values():
                walk(v)
        elif isinstance(p, (list, tuple)):
            for v in p:
                walk(v)

    walk(params)
    return out


def moe_transformer_block(
    cfg: TransformerConfig, moe: MoEConfig, *, name: str = "moe_block",
    layer: int = 0,
) -> Layer:
    """Pre-norm block with routed-expert feed-forward (attention from
    :func:`transformer_block`, of layer ``layer``'s type; MoE in the MLP
    slot)."""
    return transformer_block(
        cfg, name=name, mlp=moe_mlp(cfg, moe), layer=layer)


def _moe_blocks(
    cfg: TransformerConfig, moe: MoEConfig, count: int, prefix: str
) -> List[Layer]:
    """Blocks ``0 .. count-1``, each of its own layer's attention type."""
    return [
        moe_transformer_block(cfg, moe, name=f"{prefix}{i}", layer=i)
        for i in range(count)
    ]


def llama_moe(cfg: TransformerConfig, moe: MoEConfig) -> List[Layer]:
    """Flat sequential layer list (embed, MoE blocks, head) for the MPMD
    GPipe engine — the Mixtral-style every-block-MoE shape."""
    if cfg.tie_embeddings:
        raise ValueError(
            "tie_embeddings is an SPMD-engine feature (same constraint "
            "as models.transformer.llama): the MPMD layer list places "
            "the embedding and the head on different stage devices.  Use "
            "llama_moe_spmd(cfg, moe, n) + SpmdGPipe, or set "
            "tie_embeddings=False"
        )
    return [
        token_embedding(cfg),
        *_moe_blocks(cfg, moe, cfg.n_layers, "moe_block"),
        lm_head(cfg),
    ]


def _counted_stage(blocks: List[Layer]) -> Layer:
    """One SPMD stage of expert blocks.  Where every block counts its
    held experts' tokens (``meta['apply_counts']``), so does the stage:
    ``apply_counts(params, x, rng=, train=) -> (y, int32 [layers, held])``
    — what ``SpmdGPipe``'s train step hands out beside the loss."""
    stage = chain(blocks, name="stage")
    applies = [b.meta.get("apply_counts") for b in blocks]
    if not all(applies):
        return stage

    def apply_counts(params, x, *, rng=None, train=True):
        counts = []
        for i, (fn, p) in enumerate(zip(applies, params)):
            key = None if rng is None else jax.random.fold_in(rng, i)
            x, c = fn(p, x, rng=key, train=train)
            counts.append(c)
        return x, jnp.stack(counts)

    return dataclasses.replace(
        stage, meta=dict(stage.meta, apply_counts=apply_counts))


def llama_moe_spmd(
    cfg: TransformerConfig, moe: MoEConfig, n_stages: int,
    *, gather_logits: bool = True
) -> Tuple[Layer, Layer, Layer]:
    """(block, pre, post) for the SPMD engine: each stage runs
    ``n_layers // n_stages`` MoE blocks, block ``i`` of a stage of layer
    ``i``'s attention type (a stage holds whole periods of
    ``cfg.attn_period``: :func:`~torchgpipe_tpu.models.transformer.layers_per_stage`).

    ``gather_logits`` as in :func:`~torchgpipe_tpu.models.transformer.llama_spmd`:
    pass ``False`` under ``cfg.tp_axis`` (with
    ``loss_fn=vocab_parallel_cross_entropy(cfg.tp_axis)``) for 1/tp logits
    memory."""
    per = layers_per_stage(cfg, n_stages)
    return (
        _counted_stage(_moe_blocks(cfg, moe, per, "b")),
        token_embedding(cfg),
        lm_head(cfg, gather_logits=gather_logits),
    )
