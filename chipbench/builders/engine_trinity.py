"""Builder ``engine-trinity``: the program's ``serving.Engine`` serving a
Trinity (``model_type: afmoe``) configuration, whose layers mix window and
full attention, driven as ``builders/engine.py`` drives a dense one.

What it adds to that builder: the two program configs from the published keys
(``hf_interop.config_from_hf_afmoe``; the engine is given ``moe=`` and no
other argument a dense engine lacks), this chip's share of the experts
(``held_first`` and the cut ``num_experts`` of the configuration file),
weights from ``weights_trinity`` (ONE draw, the configuration file's, which a
run's seed relabels, and the token ids with it: every seed does the same
work), the comparison against ``reference_trinity``,
and as facts for the layer readers: the engine's expert counters of the
window (``engine_latent_moe.expert_counters``), its attention-row counters by
kind of layer (the window's, and the traced steps' from the spans' fields),
the (query, key) pairs of the processed positions by kind, and the cache's
live and reserved bytes by kind (``kv_live_bytes`` / ``kv_pool_bytes`` are
their sums; a slot's window layers hold a ring, its full layers ``max_len``
rows).  The measured window (``drive``) and its reduction (``measure``) are
``builders/engine.py``'s.

``correct`` is decided as ``engine_latent_moe.py`` decides it: of the served
tokens' logit gaps below the plain reference's best, the 99th percentile and
the mean against limits, and ``compiled_in_window`` 0.  The sample is the
longest finished request, then the longest finished ones whose context passed
``sliding_window + prefill_chunk`` (two of them at least, so that the ring
wrapped under what is compared: ``ring_wrapped_missing`` counts those short of
two and fails the run), then requests drawn from the seed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import types
from typing import Any, Dict, Iterator, List, Tuple

import jax
import numpy as np

from chipbench import peaks_trinity as pk
from chipbench import traffic as traffic_mod
from chipbench import weights_trinity
from chipbench.builders.engine import drive, measure
from chipbench.builders.engine_latent_moe import expert_counters
from chipbench.common import Cell, Check, Outcome, peak_memory_bytes, resolve
from chipbench.reference_trinity import ServeReference
from chipbench.weights import DTYPES
from chipbench.weights_axk1 import published

# The program under test.  What this PR adds to it is imported here, at the
# top, so that a tree without it fails at once and does not reach the chip.
from torchgpipe_tpu.models import generation
from torchgpipe_tpu.models.hf_interop import config_from_hf_afmoe
from torchgpipe_tpu.serving import Engine

KINDS = ("window", "full")
WRAPPED = 2             # checked requests whose ring has to have wrapped


def program_config(cell: Cell) -> Tuple[Any, Any]:
    """(TransformerConfig, MoEConfig): the published keys with the router at
    its published width, and the experts this chip holds."""
    m = cell.config
    hf = dict(m, num_experts=published(m, "num_experts"))
    cfg, moe = config_from_hf_afmoe(
        types.SimpleNamespace(**hf), held=(m["held_first"], m["num_experts"]))
    cfg = dataclasses.replace(cfg, dtype=DTYPES[m["torch_dtype"]])
    return cell.tap("program_config", (cfg, moe))


@contextlib.contextmanager
def planted_attention(cell: Cell) -> Iterator[None]:
    """A fault planted under the program's cache attention for the length of
    a run (``limits_trinity.ring_unmasked``); nothing where none is planted."""
    real = generation._attend_chunk
    generation._attend_chunk = cell.tap("attend_chunk", real)
    try:
        yield
    finally:
        generation._attend_chunk = real


def build(cell: Cell) -> Any:
    """(engine, weights): the engine as the configuration sizes it, both of
    its programs warmed on a request of their own."""
    m, sv = cell.config, cell.config["serve"]
    flat = weights_trinity.make_flat(m, cell.seed)
    cfg, moe = program_config(cell)
    eng = Engine(cfg, flat, moe=moe, num_slots=sv["num_slots"], max_len=sv["max_len"],
                 prefill_chunk=sv["prefill_chunk"], donate=sv["donate"])
    eng.submit(np.arange(sv["prefill_chunk"] + 3, dtype=np.int32) % m["vocab_size"], 3, rid="warm")
    if eng.run() != "idle":
        raise RuntimeError("the engine did not run its warm-up request to idle")
    return eng, flat


def draw_requests(cell: Cell) -> List[traffic_mod.Request]:
    """The mix's requests for the file's draw, their token ids under the
    seed's relabelling of the vocabulary (``weights_trinity.relabel_ids``)."""
    m = cell.config
    requests = resolve(cell.traffic["generator"])(
        cell.traffic, m["draw"]["seed"], cell.seconds, m["vocab_size"])
    lengths = np.cumsum([len(r.prompt) for r in requests])[:-1]
    ids = weights_trinity.relabel_ids(m, cell.seed, np.concatenate([r.prompt for r in requests]))
    return [dataclasses.replace(r, prompt=p)
            for r, p in zip(requests, np.split(ids, lengths))]


def attend_counters(eng: Any) -> Dict[str, Dict[str, int]]:
    """The engine's attention-row counters by kind of layer (``None`` from a
    program that does not count by kind)."""
    by_kind = eng.metrics.snapshot().get("attend_rows_by_kind")
    return by_kind and {k: dict(by_kind[k]) for k in KINDS}


def record_live_rows(eng: Any, ring: int) -> List[Tuple[int, int]]:
    """Wrap ``eng.step`` so that every step that ran leaves ``(window, full)``:
    the rows ONE layer of each kind holds a token in, summed over the slots,
    from the pool's own frontiers (a window layer holds ``min(context, ring)``
    of a slot's rows).  ``drive`` records the step's wall time in the same
    order."""
    rows: List[Tuple[int, int]] = []
    inner = eng.step

    def step() -> bool:
        ran = inner()
        if ran:
            lengths = eng.pool.lengths
            rows.append((int(np.minimum(lengths, ring).sum()), int(lengths.sum())))
        return ran

    eng.step = step
    return rows


def traced_rows_read(rec: Dict[str, Any], n_steps: int) -> Any:
    """Rows the traced steps' attention read, by kind, from the ``rows_read_*``
    fields of the window's action spans (one span a step, in the order
    ``drive`` recorded the steps)."""
    from torchgpipe_tpu.utils.tracing import default_timeline

    actions = [e for e in default_timeline().events
               if e.name in ("engine.prefill", "engine.decode")][-n_steps:]
    if len(actions) < n_steps or n_steps != len(rec["steps"]):
        return None
    out = {k: 0 for k in KINDS}
    for span, step in zip(actions, rec["steps"]):
        if step[2]:
            for k in KINDS:
                out[k] += (span.fields or {}).get(f"rows_read_{k}", 0)
    return out if any(out.values()) else None


def cache_facts(cell: Cell, rec: Dict[str, Any], live: List[Tuple[int, int]]) -> Dict[str, Any]:
    """The cache's live rows (time-weighted over the window's steps) and its
    live and reserved bytes, by kind of layer and in all."""
    m, sv = cell.config, cell.config["serve"]
    steps = np.asarray(rec["steps"], np.float64).reshape(-1, 4)
    reserved = pk.pool_bytes(m, sv)
    out: Dict[str, Any] = {"kv_pool_bytes_by_kind": reserved,
                           "kv_pool_bytes": sum(reserved.values()),
                           "kv_live_rows": None, "kv_live_bytes": None}
    if len(steps) and len(live) == len(steps):
        rows = np.average(np.asarray(live, np.float64), axis=0, weights=steps[:, 1])
        by_kind = pk.live_bytes(m, dict(zip(KINDS, rows)))
        out.update(kv_live_rows=dict(zip(KINDS, map(float, rows))),
                   kv_live_bytes_by_kind=by_kind, kv_live_bytes=sum(by_kind.values()))
    return out


def pairs_by_kind(cell: Cell, requests: List[traffic_mod.Request],
                  rec: Dict[str, Any]) -> Dict[str, float]:
    """(query, key) pairs of the positions the window processed, in ONE layer
    of each kind (``measure`` counts a request's processed positions so)."""
    window, out = cell.config["sliding_window"], {k: 0.0 for k in KINDS}
    for r in requests:
        times = rec["token_times"][r.rid]
        n = len(r.prompt) if times else rec["prefilled"].get(r.rid, 0)
        n += max(len(times) - 1, 0)
        out["window"] += pk.pairs(n, window)
        out["full"] += pk.pairs(n)
    return out


def sample_finished(cell: Cell, finished: List[traffic_mod.Request]) -> List[traffic_mod.Request]:
    """The longest finished request, the longest others whose context passed
    the window and a prefill chunk (``WRAPPED`` of them), and others drawn
    from the seed, ``checked_requests`` in all."""
    sv = cell.config["serve"]
    if not finished:
        return []

    def context(r):
        return len(r.prompt) + r.new_tokens

    by_length = sorted(finished, key=context, reverse=True)
    past = cell.config["sliding_window"] + sv["prefill_chunk"]
    picked = by_length[:1] + [r for r in by_length[1:] if context(r) > past][:WRAPPED]
    rest = [r for r in finished if all(r is not p for p in picked)]
    order = np.random.default_rng(cell.seed).permutation(len(rest))
    return picked + [rest[i] for i in order[:max(sv["checked_requests"] - len(picked), 0)]]


def wrapped(cell: Cell, sample: List[traffic_mod.Request]) -> int:
    past = cell.config["sliding_window"] + cell.config["serve"]["prefill_chunk"]
    return sum(len(r.prompt) + r.new_tokens > past for r in sample)


def served_logit_gaps(cell: Cell, flat: Any, sample: List[traffic_mod.Request],
                      served: Dict[str, List[int]], low: bool = False,
                      leave_out: Tuple[str, ...] = ()) -> np.ndarray:
    """For every served token of the sample, by how much its logit in
    ``reference_trinity`` lies below the reference's best at that position (0
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
    with planted_attention(cell):
        eng, flat = build(cell)
        requests = draw_requests(cell)
        live = record_live_rows(eng, pk.slot_rows(cell.config, cell.config["serve"])["window"])
        before, rows_before = expert_counters(eng), attend_counters(eng)
        rec = drive(cell, eng, requests)
        after, rows_after = expert_counters(eng), attend_counters(eng)
    peak = peak_memory_bytes([jax.devices()[0]])
    del eng
    gc.collect()
    got = measure(cell, requests, rec)
    moe = {k: after[k] - before[k] for k in after}
    for what in ("max", "mean"):
        moe[f"moe_expert_tokens_{what}"] = (
            moe[f"moe_prefill_expert_tokens_{what}"] + moe[f"moe_decode_expert_tokens_{what}"])
    facts = got["facts"]
    facts.update(cache_facts(cell, rec, live), **moe)
    facts["admitted"] = len(rec["queue_wait_s"])
    facts["pairs_by_kind"] = pairs_by_kind(cell, requests, rec)
    if rows_after is not None:
        for what in ("read", "capacity"):
            facts[f"attend_rows_{what}"] = {
                k: rows_after[k][what] - rows_before[k][what] for k in KINDS}
        facts["traced_rows_read"] = traced_rows_read(
            rec, rec["prefill_steps"] + rec["decode_steps"])
    return {"flat": flat, "requests": requests, "rec": rec, "peak": peak, "got": got}


NOTES = (
    "submitted", "admitted", "finished", "output_tokens", "processed_tokens", "prefill_steps",
    "decode_steps", "step_wall_ms", "step_wall_max_ms", "kv_live_rows", "kv_live_bytes",
    "kv_live_bytes_by_kind", "kv_pool_bytes", "kv_pool_bytes_by_kind", "attend_rows_read",
    "attend_rows_capacity", "checked_requests", "checked_tokens", "checked_contexts",
    "served_logit_gap_max", "moe_routed_assignments", "moe_held_assignments",
    "moe_prefill_steps", "moe_decode_steps", "moe_prefill_expert_tokens_max",
    "moe_prefill_expert_tokens_mean", "moe_decode_expert_tokens_max",
    "moe_decode_expert_tokens_mean")


def run(cell: Cell) -> Outcome:
    sv = cell.config["serve"]
    w = window(cell)
    got, rec = w["got"], w["rec"]
    sample = sample_finished(cell, got["finished"])
    gaps = served_logit_gaps(cell, w["flat"], sample, rec["served"])
    facts = dict(got["facts"], checked_requests=len(sample),
                 checked_tokens=sum(r.new_tokens for r in sample),
                 checked_contexts=[len(r.prompt) + r.new_tokens for r in sample])
    facts["served_logit_gap_max"] = float(gaps.max())
    checks = [
        Check("served_logit_gap_p99", float(np.quantile(gaps, 0.99)),
              sv["limits"]["served_logit_gap_p99"]),
        Check("served_logit_gap_mean", float(gaps.mean()), sv["limits"]["served_logit_gap_mean"]),
        Check("ring_wrapped_missing", float(max(WRAPPED - wrapped(cell, sample), 0)), 0.0),
        Check("compiled_in_window", float(rec["compiled_in_window"]), 0.0),
    ]
    facts["notes"] = {k: facts[k] for k in NOTES if k in facts}
    return Outcome(attempted=got["judged"], failed=got["failed"],
                   end_to_end=got["end_to_end"], checks=checks, facts=facts,
                   memory_peak_bytes=w["peak"])
