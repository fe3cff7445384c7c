"""Builder ``engine-latent-moe``: the program's ``serving.Engine`` serving a
latent-attention, routed-expert configuration (the ``axk1`` key set), driven
as ``builders/engine.py`` drives a dense one.

What it adds to that builder: the two program configs from the published keys
(``hf_interop.config_from_hf_latent_moe``; the engine is given ``moe=`` and no
other argument a dense engine lacks), this chip's share of the experts
(``held_first`` and the cut ``n_routed_experts`` of the configuration file),
weights from ``weights_axk1``, the comparison against ``reference_axk1``, the
cache's bytes restated for a latent row (``kv_live_bytes`` / ``kv_pool_bytes``
at ``(kv_lora_rank + qk_rope_head_dim) x 2`` bytes a token a layer) and the
engine's expert counters of the window as facts for the layer readers.  The
measured window (``drive``), its reduction (``measure``) and the sample of
finished requests (``sample_finished``) are ``builders/engine.py``'s.

``correct`` compares ``compiled_in_window`` as ``builders/engine.py`` does, and
of the served tokens' logit gaps against the plain reference not the widest but
the 99th percentile and the mean.  A top-k near-tie that flips between bfloat16
and float32 hidden states swaps one held expert's share of a token's output, so
the WIDEST of some 400 gaps is an extreme of rare legitimate events: it read up
to 1.32 where the fp8 control's smallest is 1.61, and no limit between them
leaves room.  It stays in ``notes`` (``served_logit_gap_max``), uncompared.
"""

from __future__ import annotations

import dataclasses
import gc
import types
from typing import Any, Dict, List, Tuple

import jax
import numpy as np

from chipbench import peaks_latent_moe as pk
from chipbench import traffic as traffic_mod
from chipbench import weights_axk1
from chipbench.builders.engine import drive, measure, sample_finished
from chipbench.common import Cell, Check, Outcome, peak_memory_bytes, resolve
from chipbench.reference_axk1 import ServeReference
from chipbench.weights import DTYPES

# The program under test.  The MLA module is imported here, at the top, so
# that a tree without it fails at once and does not reach the chip.
from torchgpipe_tpu.models import mla  # noqa: F401
from torchgpipe_tpu.models.hf_interop import config_from_hf_latent_moe
from torchgpipe_tpu.serving import Engine

MOE_COUNTERS = ("moe_routed_assignments", "moe_held_assignments")


def program_config(cell: Cell) -> Tuple[Any, Any]:
    """(TransformerConfig, MoEConfig): the published keys with the router at
    its published width, and the experts this chip holds."""
    m = cell.config
    hf = dict(m, n_routed_experts=weights_axk1.published(m, "n_routed_experts"))
    cfg, moe = config_from_hf_latent_moe(
        types.SimpleNamespace(**hf), held=(m["held_first"], m["n_routed_experts"]))
    cfg = dataclasses.replace(cfg, dtype=DTYPES[m["torch_dtype"]])
    return cfg, cell.tap("moe_config", moe)


def build(cell: Cell) -> Any:
    """(engine, weights): the engine as the configuration sizes it, both of
    its programs warmed on a request of their own."""
    m, sv = cell.config, cell.config["serve"]
    flat = weights_axk1.make_flat(m, cell.seed)
    cfg, moe = program_config(cell)
    eng = Engine(cfg, flat, moe=moe, num_slots=sv["num_slots"], max_len=sv["max_len"],
                 prefill_chunk=sv["prefill_chunk"], donate=sv["donate"])
    eng.submit(np.arange(sv["prefill_chunk"] + 3, dtype=np.int32) % m["vocab_size"], 3, rid="warm")
    if eng.run() != "idle":
        raise RuntimeError("the engine did not run its warm-up request to idle")
    return eng, flat


def expert_counters(eng: Any) -> Dict[str, float]:
    """The engine's expert counters as running sums (over steps, for the
    per-step fullest and mean held expert), every step so far counted."""
    eng.read_expert_counts()
    out = {k: float(getattr(eng.metrics, k)) for k in MOE_COUNTERS}
    for kind in ("prefill", "decode"):
        load = eng.metrics.moe_expert_tokens(kind)
        out[f"moe_{kind}_steps"] = load["steps"]
        out[f"moe_{kind}_expert_tokens_max"] = load["max"] * load["steps"]
        out[f"moe_{kind}_expert_tokens_mean"] = load["mean"] * load["steps"]
    return out


def cache_facts(cell: Cell, rec: Dict[str, Any]) -> Dict[str, Any]:
    """The cache's live rows (time-weighted over the window's steps) and
    bytes, at the latent row's size."""
    m, sv = cell.config, cell.config["serve"]
    steps = np.asarray(rec["steps"], np.float64).reshape(-1, 4)
    rows = float(np.average(steps[:, 3], weights=steps[:, 1])) if len(steps) else None
    row_bytes = pk.cache_row_bytes(m)
    return {"kv_live_rows": rows,
            "kv_live_bytes": rows * row_bytes if rows is not None else None,
            "kv_pool_bytes": sv["num_slots"] * sv["max_len"] * row_bytes}


def served_logit_gaps(cell: Cell, flat: Any, requests: List[traffic_mod.Request],
                      sample: List[traffic_mod.Request], served: Dict[str, List[int]],
                      low: bool = False) -> np.ndarray:
    """For every served token of the sample, by how much its logit in
    ``reference_axk1`` lies below the reference's best at that position (0
    where the served token IS the reference's best).  With ``low`` the tokens
    judged are the fp8 control's own first choices on the same prompts and
    tokens."""
    length = max(len(r.prompt) + r.new_tokens for r in requests)
    rows = max(r.new_tokens for r in requests)
    ref = ServeReference(cell.config, flat, length, rows)
    control = ServeReference(cell.config, flat, length, rows, low=True) if low else None
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
    requests = resolve(cell.traffic["generator"])(
        cell.traffic, cell.seed, cell.seconds, cell.config["vocab_size"])
    before = expert_counters(eng)
    rec = drive(cell, eng, requests)
    after = expert_counters(eng)
    peak = peak_memory_bytes([jax.devices()[0]])
    del eng
    gc.collect()
    got = measure(cell, requests, rec)
    moe = {k: after[k] - before[k] for k in after}
    for what in ("max", "mean"):
        moe[f"moe_expert_tokens_{what}"] = (
            moe[f"moe_prefill_expert_tokens_{what}"] + moe[f"moe_decode_expert_tokens_{what}"])
    got["facts"].update(cache_facts(cell, rec), **moe)
    return {"flat": flat, "requests": requests, "rec": rec, "peak": peak, "got": got}


def run(cell: Cell) -> Outcome:
    sv = cell.config["serve"]
    w = window(cell)
    got, rec = w["got"], w["rec"]
    sample = sample_finished(got["finished"], cell.seed, sv["checked_requests"])
    gaps = served_logit_gaps(cell, w["flat"], w["requests"], sample, rec["served"])
    facts = dict(got["facts"], checked_requests=len(sample),
                 checked_tokens=sum(r.new_tokens for r in sample))
    facts["served_logit_gap_max"] = float(gaps.max())
    checks = [
        Check("served_logit_gap_p99", float(np.quantile(gaps, 0.99)),
              sv["limits"]["served_logit_gap_p99"]),
        Check("served_logit_gap_mean", float(gaps.mean()), sv["limits"]["served_logit_gap_mean"]),
        Check("compiled_in_window", float(rec["compiled_in_window"]), 0.0),
    ]
    facts["notes"] = {k: facts[k] for k in (
        "submitted", "finished", "output_tokens", "prefill_steps", "decode_steps", "step_wall_ms",
        "step_wall_max_ms", "kv_live_bytes", "kv_pool_bytes", "checked_requests", "checked_tokens",
        "served_logit_gap_max",
        "moe_routed_assignments", "moe_held_assignments", "moe_prefill_steps", "moe_decode_steps",
        "moe_prefill_expert_tokens_max", "moe_prefill_expert_tokens_mean",
        "moe_decode_expert_tokens_max", "moe_decode_expert_tokens_mean")}
    return Outcome(attempted=got["judged"], failed=got["failed"],
                   end_to_end=got["end_to_end"], checks=checks, facts=facts,
                   memory_peak_bytes=w["peak"])
