"""Builder ``engine-solar``: the program's ``serving.Engine`` serving a
Solar-Open2 (``model_type: solar_open2``) configuration, whose delta-rule
layers keep a float32 state a slot beside one gated full layer's rows, driven
as ``builders/engine.py`` drives a dense one.

What it adds to that builder: the two program configs from the published keys
(``hf_interop.config_from_hf_solar_open2``; the engine is given ``moe=`` and no
other argument a dense engine lacks), this chip's share of the experts
(``held_first`` and the cut ``n_routed_experts`` of the configuration file),
weights from ``weights_solar`` (ONE draw, the configuration file's, which a
run's seed relabels, and the token ids with it: every seed does the same
work), the comparison against ``reference_solar``, and as facts for the layer
readers: the engine's expert counters of the window
(``engine_latent_moe.expert_counters``), the bytes a decode step must move
(``peaks_solar.decode_step_bytes``, from the held experts the decode steps
gave a token, the recurrent-state bytes they read and wrote and the attention
rows they read, block-rounded, each as the engine counted it), and the pool's
live and reserved bytes by kind (``full``: the attention layer's rows;
``state``: the delta-rule layers' tails and states of the slots in use).  The
measured window (``drive``) and its reduction (``measure``) are
``builders/engine.py``'s; the window's records, counters and checked sample
are ``engine_nemotron.py``'s.

``correct`` is decided as ``engine_nemotron.py`` decides it: of the served
tokens' logit gaps below the plain reference's best, the 99th percentile and
the mean against limits, ``compiled_in_window`` 0, and two checked requests
served from a RECYCLED slot (``recycled_missing``).
"""

from __future__ import annotations

import dataclasses
import gc
import types
from typing import Any, Dict, List, Tuple

import jax
import numpy as np

from chipbench import peaks_solar as pk
from chipbench import traffic as traffic_mod
from chipbench import weights_solar
from chipbench.builders.engine import drive, measure
from chipbench.builders.engine_nemotron import (NOTES, RECYCLED, counters, draw_requests,
                                                record, recycled_rids, sample_finished)
from chipbench.common import Cell, Check, Outcome, peak_memory_bytes
from chipbench.reference_solar import ServeReference
from chipbench.weights import DTYPES
from chipbench.weights_axk1 import published

# The program under test.  What this PR adds to it is imported here, at the
# top, so that a tree without it fails at once and does not reach the chip.
from torchgpipe_tpu.models import kda  # noqa: F401
from torchgpipe_tpu.models.hf_interop import config_from_hf_solar_open2
from torchgpipe_tpu.serving import Engine


def program_config(cell: Cell) -> Tuple[Any, Any]:
    """(TransformerConfig, MoEConfig): the published keys with the router at
    its published width, and the experts this chip holds."""
    m = cell.config
    hf = dict(m, n_routed_experts=published(m, "n_routed_experts"))
    cfg, moe = config_from_hf_solar_open2(
        types.SimpleNamespace(**hf), held=(m["held_first"], m["n_routed_experts"]))
    cfg = dataclasses.replace(cfg, dtype=DTYPES[m["torch_dtype"]])
    return cell.tap("program_config", (cfg, moe))


def build(cell: Cell) -> Any:
    """(engine, weights): the engine as the configuration sizes it, both of
    its programs warmed on a request of their own."""
    m, sv = cell.config, cell.config["serve"]
    flat = weights_solar.make_flat(m, cell.seed)
    cfg, moe = program_config(cell)
    eng = Engine(cfg, cell.tap("weights", flat), moe=moe, num_slots=sv["num_slots"],
                 max_len=sv["max_len"], prefill_chunk=sv["prefill_chunk"], donate=sv["donate"])
    eng.submit(np.arange(sv["prefill_chunk"] + 3, dtype=np.int32) % m["vocab_size"], 3, rid="warm")
    if eng.run() != "idle":
        raise RuntimeError("the engine did not run its warm-up request to idle")
    return eng, flat


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


def served_logit_gaps(cell: Cell, flat: Any, sample: List[traffic_mod.Request],
                      served: Dict[str, List[int]], low: bool = False,
                      leave_out: Tuple[str, ...] = ()) -> np.ndarray:
    """For every served token of the sample, by how much its logit in
    ``reference_solar`` lies below the reference's best at that position (0
    where the served token IS the reference's best).  With ``low`` the tokens
    judged are the fp8 control's own first choices on the same prompts and
    tokens; ``leave_out`` plants a fault in the reference."""
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
