"""Builder ``engine-nemotron``: the program's ``serving.Engine`` serving a
Nemotron-H (``model_type: nemotron_h``) configuration with experts, whose
mixer layers keep a recurrent state a slot beside the attention layer's rows,
driven as ``builders/engine.py`` drives a dense one.

What it adds to that builder: the two program configs from the published keys
(``hf_interop.config_from_hf_nemotron_h``; the engine is given ``moe=`` and no
other argument a dense engine lacks), this chip's share of the experts
(``held_first`` and the cut ``n_routed_experts`` of the configuration file),
weights from ``weights_nemotron`` (ONE draw, the configuration file's, which a
run's seed relabels, and the token ids with it: every seed does the same
work), the comparison against ``reference_nemotron``, and as facts for the
layer readers: the engine's expert counters of the window
(``engine_latent_moe.expert_counters``), the bytes a decode step must move
(``peaks_nemotron.decode_step_bytes``, from the held experts the decode steps
gave a token, the recurrent-state bytes they read and wrote and the attention
rows they read, block-rounded, each as the engine counted it), and the pool's
live and reserved bytes by kind (``full``: the attention layer's rows;
``state``: the mixers' tails and states of the slots in use).  The measured
window (``drive``) and its reduction (``measure``) are ``builders/engine.py``'s.

``correct`` is decided as ``engine_trinity.py`` decides it: of the served
tokens' logit gaps below the plain reference's best, the 99th percentile and
the mean against limits, and ``compiled_in_window`` 0.  The sample is the
longest finished request, then finished requests that were served from a
RECYCLED slot (a slot another request held before, whose state the new tenant
must not see: ``recycled_missing`` counts those short of two and fails the
run), then requests drawn from the seed.
"""

from __future__ import annotations

import dataclasses
import gc
import types
from typing import Any, Dict, List, Set, Tuple

import jax
import numpy as np

from chipbench import peaks_nemotron as pk
from chipbench import traffic as traffic_mod
from chipbench import weights_nemotron
from chipbench.builders.engine import drive, measure
from chipbench.builders.engine_latent_moe import expert_counters
from chipbench.common import Cell, Check, Outcome, peak_memory_bytes, resolve
from chipbench.reference_nemotron import ServeReference
from chipbench.weights import DTYPES
from chipbench.weights_axk1 import published

# The program under test.  What this PR adds to it is imported here, at the
# top, so that a tree without it fails at once and does not reach the chip.
from torchgpipe_tpu.models import ssm  # noqa: F401
from torchgpipe_tpu.models.hf_interop import config_from_hf_nemotron_h
from torchgpipe_tpu.serving import Engine
from torchgpipe_tpu.utils.tracing import default_timeline

RECYCLED = 2            # checked requests that have to come from a recycled slot
ACTIONS = ("engine.prefill", "engine.decode")


def program_config(cell: Cell) -> Tuple[Any, Any]:
    """(TransformerConfig, MoEConfig): the published keys with the router at
    its published width, and the experts this chip holds."""
    m = cell.config
    hf = dict(m, n_routed_experts=published(m, "n_routed_experts"))
    cfg, moe = config_from_hf_nemotron_h(
        types.SimpleNamespace(**hf), held=(m["held_first"], m["n_routed_experts"]))
    cfg = dataclasses.replace(cfg, dtype=DTYPES[m["torch_dtype"]])
    return cell.tap("program_config", (cfg, moe))


def build(cell: Cell) -> Any:
    """(engine, weights): the engine as the configuration sizes it, both of
    its programs warmed on a request of their own."""
    m, sv = cell.config, cell.config["serve"]
    flat = weights_nemotron.make_flat(m, cell.seed)
    cfg, moe = program_config(cell)
    eng = Engine(cfg, cell.tap("weights", flat), moe=moe, num_slots=sv["num_slots"],
                 max_len=sv["max_len"], prefill_chunk=sv["prefill_chunk"], donate=sv["donate"])
    eng.submit(np.arange(sv["prefill_chunk"] + 3, dtype=np.int32) % m["vocab_size"], 3, rid="warm")
    if eng.run() != "idle":
        raise RuntimeError("the engine did not run its warm-up request to idle")
    return eng, flat


def draw_requests(cell: Cell) -> List[traffic_mod.Request]:
    """The mix's requests for the file's draw, their token ids under the
    seed's relabelling of the vocabulary (``weights_nemotron.relabel_ids``)."""
    m = cell.config
    requests = resolve(cell.traffic["generator"])(
        cell.traffic, m["draw"]["seed"], cell.seconds, m["vocab_size"])
    lengths = np.cumsum([len(r.prompt) for r in requests])[:-1]
    ids = weights_nemotron.relabel_ids(m, cell.seed, np.concatenate([r.prompt for r in requests]))
    return [dataclasses.replace(r, prompt=p)
            for r, p in zip(requests, np.split(ids, lengths))]


def record(eng: Any) -> Dict[str, Any]:
    """Wrap the engine so that the window leaves, from the program's own
    counts: the slots each request was admitted to (in order), the attention
    rows the decode steps read (the ``rows_read`` field of their action span,
    block-rounded where the decode kernel runs) and the slots in use at each
    step that ran (in ``drive``'s order of steps)."""
    out: Dict[str, Any] = {"slots": [], "decode_rows_read": 0, "decode_spans": 0,
                           "active": []}
    admit, step = eng._on_admit, eng.step
    timeline = default_timeline()

    def on_admit(req: Any) -> None:
        out["slots"].append((req.rid, req.slot))
        admit(req)

    def wrapped() -> bool:
        ran = step()
        if ran:
            out["active"].append(len(eng.scheduler.active))
            for e in reversed(timeline.events):
                if e.name in ACTIONS:
                    if e.name == "engine.decode":
                        out["decode_rows_read"] += (e.fields or {}).get("rows_read", 0)
                        out["decode_spans"] += 1
                    break
        return ran

    eng._on_admit, eng.step = on_admit, wrapped
    return out


def recycled_rids(slots: List[Tuple[str, int]]) -> Set[str]:
    """Requests admitted to a slot that an earlier request (the warm-up's
    among them) held."""
    seen: Set[int] = {0}
    out = set()
    for rid, slot in slots:
        if slot in seen:
            out.add(rid)
        seen.add(slot)
    return out


def state_facts(cell: Cell, rec: Dict[str, Any], seen: Dict[str, Any],
                before: Dict[str, Any], after: Dict[str, Any]) -> Dict[str, Any]:
    """The pool's live and reserved bytes by kind, and the bytes a decode step
    must move, averaged over the window's decode steps."""
    m, sv = cell.config, cell.config["serve"]
    steps = np.asarray(rec["steps"], np.float64).reshape(-1, 4)
    reserved = pk.pool_bytes(m, sv)
    out: Dict[str, Any] = {"kv_pool_bytes_by_kind": reserved,
                           "kv_pool_bytes": sum(reserved.values()),
                           "kv_live_bytes": None, "decode_step_bytes": None}
    if len(steps) and len(seen["active"]) == len(steps):
        w = steps[:, 1]
        live = {"full": float(np.average(steps[:, 3], weights=w)) * pk.cache_row_bytes(m)
                * pk.letters(m)["*"],
                "state": float(np.average(seen["active"], weights=w)) * pk.slot_state_bytes(m)}
        out.update(kv_live_bytes_by_kind=live, kv_live_bytes=sum(live.values()))
    decode = after["moe_decode_steps"] - before["moe_decode_steps"]
    if decode and seen["decode_spans"]:
        touched = (after["moe_decode_experts_touched"] - before["moe_decode_experts_touched"]) / decode
        state = (after["state_bytes_decode"] - before["state_bytes_decode"]) / decode
        rows = seen["decode_rows_read"] / seen["decode_spans"]
        out.update(decode_experts_touched=touched, decode_state_bytes=state,
                   decode_rows_read=rows,
                   decode_step_bytes=pk.decode_step_bytes(m, touched, state, rows))
    return out


def counters(eng: Any) -> Dict[str, float]:
    """The expert counters (``engine_latent_moe.expert_counters``), the held
    experts the decode steps gave a token and the state bytes by kind of step,
    as running sums."""
    out = expert_counters(eng)
    load = eng.metrics.moe_expert_tokens("decode")
    out["moe_decode_experts_touched"] = load["touched"] * load["steps"]
    for kind, value in eng.metrics.snapshot()["state_bytes"].items():
        out[f"state_bytes_{kind}"] = float(value)
    out["state_zeroed_slots"] = float(eng.metrics.state_zeroed_slots)
    return out


def sample_finished(cell: Cell, finished: List[traffic_mod.Request],
                    recycled: Set[str]) -> List[traffic_mod.Request]:
    """The longest finished request, the longest finished ones served from a
    recycled slot (``RECYCLED`` of them), and others drawn from the seed,
    ``checked_requests`` in all."""
    sv = cell.config["serve"]
    if not finished:
        return []
    by_length = sorted(finished, key=lambda r: len(r.prompt) + r.new_tokens, reverse=True)
    picked = by_length[:1] + [r for r in by_length[1:] if r.rid in recycled][:RECYCLED]
    rest = [r for r in finished if all(r is not p for p in picked)]
    order = np.random.default_rng(cell.seed).permutation(len(rest))
    return picked + [rest[i] for i in order[:max(sv["checked_requests"] - len(picked), 0)]]


def served_logit_gaps(cell: Cell, flat: Any, sample: List[traffic_mod.Request],
                      served: Dict[str, List[int]], low: bool = False,
                      leave_out: Tuple[str, ...] = ()) -> np.ndarray:
    """For every served token of the sample, by how much its logit in
    ``reference_nemotron`` lies below the reference's best at that position
    (0 where the served token IS the reference's best).  With ``low`` the
    tokens judged are the fp8 control's own first choices on the same prompts
    and tokens; ``leave_out`` plants a fault in the reference."""
    sv = cell.config["serve"]
    rows = max([r.new_tokens for r in sample], default=1)
    ref = ServeReference(cell.config, flat, sv["max_len"], rows, leave_out=leave_out)
    control = ServeReference(cell.config, flat, sv["max_len"], rows, low=True) if low else None
    gaps = []
    for r in sample:
        tokens = np.asarray(served[r.rid], np.int32)
        logits = ref.chosen_logits(r.prompt, tokens)
        if control is not None:
            tokens = control.chosen_logits(r.prompt, tokens).argmax(-1)
        gaps.append(logits.max(-1) - logits[np.arange(len(tokens)), tokens])
    return np.concatenate(gaps) if gaps else np.full((1,), np.inf)


def window(cell: Cell) -> Dict[str, Any]:
    """Build, drive and reduce one window: everything but the reference."""
    eng, flat = build(cell)
    requests = draw_requests(cell)
    seen = record(eng)
    before = counters(eng)
    rec = drive(cell, eng, requests)
    after = counters(eng)
    peak = peak_memory_bytes([jax.devices()[0]])
    del eng
    gc.collect()
    got = measure(cell, requests, rec)
    moe = {k: after[k] - before[k] for k in after}
    for what in ("max", "mean"):
        moe[f"moe_expert_tokens_{what}"] = (
            moe[f"moe_prefill_expert_tokens_{what}"] + moe[f"moe_decode_expert_tokens_{what}"])
    facts = got["facts"]
    facts.update(state_facts(cell, rec, seen, before, after), **moe)
    facts["admitted"] = len(rec["queue_wait_s"])
    return {"flat": flat, "requests": requests, "rec": rec, "peak": peak, "got": got,
            "recycled": recycled_rids(seen["slots"])}


NOTES = (
    "submitted", "admitted", "finished", "output_tokens", "processed_tokens", "prefill_steps",
    "decode_steps", "step_wall_ms", "step_wall_max_ms", "kv_live_bytes", "kv_live_bytes_by_kind",
    "kv_pool_bytes", "kv_pool_bytes_by_kind", "decode_experts_touched", "decode_state_bytes",
    "decode_rows_read", "decode_step_bytes", "state_bytes_prefill", "state_bytes_decode",
    "state_zeroed_slots", "checked_requests", "checked_tokens", "checked_contexts",
    "checked_recycled", "served_logit_gap_max", "moe_routed_assignments",
    "moe_held_assignments", "moe_expert_tokens_max", "moe_expert_tokens_mean",
    "moe_prefill_steps", "moe_decode_steps",
    "moe_prefill_expert_tokens_max", "moe_prefill_expert_tokens_mean",
    "moe_decode_expert_tokens_max", "moe_decode_expert_tokens_mean")


def run(cell: Cell) -> Outcome:
    sv = cell.config["serve"]
    w = window(cell)
    got, rec = w["got"], w["rec"]
    sample = sample_finished(cell, got["finished"], w["recycled"])
    gaps = served_logit_gaps(cell, w["flat"], sample, rec["served"])
    recycled = sum(r.rid in w["recycled"] for r in sample)
    facts = dict(got["facts"], checked_requests=len(sample),
                 checked_tokens=sum(r.new_tokens for r in sample),
                 checked_contexts=[len(r.prompt) + r.new_tokens for r in sample],
                 checked_recycled=recycled)
    facts["served_logit_gap_max"] = float(gaps.max())
    checks = [
        Check("served_logit_gap_p99", float(np.quantile(gaps, 0.99)),
              sv["limits"]["served_logit_gap_p99"]),
        Check("served_logit_gap_mean", float(gaps.mean()), sv["limits"]["served_logit_gap_mean"]),
        Check("recycled_missing", float(max(RECYCLED - recycled, 0)), 0.0),
        Check("compiled_in_window", float(rec["compiled_in_window"]), 0.0),
    ]
    facts["notes"] = {k: facts[k] for k in NOTES if k in facts}
    return Outcome(attempted=got["judged"], failed=got["failed"],
                   end_to_end=got["end_to_end"], checks=checks, facts=facts,
                   memory_peak_bytes=w["peak"])
