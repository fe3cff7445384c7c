"""Builder ``engine``: the program's ``serving.Engine`` driven by a list of
requests with due times, made by the generator the traffic file names.

One thread: the generator shares the engine's loop, submitting what is due
between engine steps, as a server's own accept loop would.  Every output
token is stamped where the engine hands it over (``on_token``).  TTFT counts
from the time a request was DUE, not from its submit.  Greedy decoding, no
EOS: every request emits exactly its ``new_tokens``.

After the window closes and the peak is read, the engine is freed and a
sample of the finished requests, drawn from the seed with the longest in it,
is run once each through the plain reference: the widest gap by which a
served token's logit lies below the reference's best decides ``correct``.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import jax
import numpy as np

from chipbench import traffic as traffic_mod
from chipbench import weights
from chipbench.builders.spmd_train import program_config
from chipbench.common import (Cell, Check, Outcome, peak_memory_bytes,
                              percentile, process_age_s, resolve)
from chipbench.reference import ServeReference, widest_gap

# The program under test.
from torchgpipe_tpu.serving import Engine


def sample_finished(finished: List[traffic_mod.Request], seed: int, count: int) -> List[traffic_mod.Request]:
    """The longest finished request and ``count - 1`` others drawn from the seed."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: len(r.prompt) + r.new_tokens)
    rest = [r for r in finished if r is not longest]
    picks = np.random.default_rng(seed).permutation(len(rest))[:count - 1]
    return [longest] + [rest[i] for i in picks]


def build(cell: Cell) -> Any:
    """(engine, weights): the engine as the configuration sizes it, both of
    its programs warmed on a request of their own."""
    m, sv = cell.config, cell.config["serve"]
    flat = weights.make_flat(m, cell.seed)
    eng = Engine(program_config(m), flat, num_slots=sv["num_slots"],
                 max_len=sv["max_len"], prefill_chunk=sv["prefill_chunk"],
                 donate=sv["donate"])
    eng.submit(np.arange(sv["prefill_chunk"] + 3, dtype=np.int32) % m["vocab_size"], 3, rid="warm")
    if eng.run() != "idle":
        raise RuntimeError("the engine did not run its warm-up request to idle")
    return eng, flat


def drive(cell: Cell, eng: Any, requests: List[traffic_mod.Request]) -> Dict[str, Any]:
    """The measured window: submit what is due, step the engine, until
    ``cell.seconds`` are up.  Returns the raw record."""
    traffic = cell.traffic
    token_times: Dict[str, List[float]] = {r.rid: [] for r in requests}
    served: Dict[str, List[int]] = {r.rid: [] for r in requests}
    submit_s: Dict[str, float] = {}
    steps: List[Any] = []       # (start_s, wall_s, traced, live KV rows) of every step that ran
    clock = time.perf_counter

    def on_token(rid: str, token: int) -> None:
        token_times[rid].append(clock() - t0)
        served[rid].append(cell.tap("token", token))

    trace_from = None
    if cell.trace:
        end = requests[-1].due_s if requests[-1].due_s > 0 else cell.seconds
        trace_from = max(0.0, end - traffic["trace_seconds"])
    tracing = False
    before = (eng.metrics.prefill_steps, eng.metrics.decode_steps)
    programs = cell.meter.programs
    setup_s = process_age_s()
    t0 = clock()
    nxt = 0
    while True:
        now = clock() - t0
        if now >= cell.seconds:
            break
        if trace_from is not None and not tracing and now >= trace_from:
            jax.profiler.start_trace(str(cell.trace_dir))
            tracing = True
        if tracing and now >= trace_from + traffic["trace_seconds"]:
            jax.profiler.stop_trace()
            tracing, trace_from = False, None
        while nxt < len(requests) and requests[nxt].due_s <= now:
            r = requests[nxt]
            with jax.profiler.TraceAnnotation("cb.submit"):
                eng.submit(r.prompt, r.new_tokens, rid=r.rid, on_token=on_token)
            submit_s[r.rid] = clock() - t0
            nxt += 1
        began = clock()
        with jax.profiler.TraceAnnotation("cb.engine.step"):
            ran = eng.step()
        if ran:
            # Rows of the KV pool that hold a token: what each slot in use
            # has absorbed of its prompt and emitted since.
            live = sum(r.prefilled + len(token_times[rid])
                       for rid, r in eng.scheduler.active.items() if rid in token_times)
            steps.append((began - t0, clock() - began, tracing, live))
        else:
            with jax.profiler.TraceAnnotation("cb.wait"):
                wait = requests[nxt].due_s - (clock() - t0) if nxt < len(requests) else 0.001
                time.sleep(min(max(wait, 0.0), 0.001))
    elapsed = clock() - t0
    if tracing:
        jax.profiler.stop_trace()
    return {
        "token_times": token_times, "served": served, "submit_s": submit_s,
        "steps": steps, "elapsed_s": elapsed, "setup_s": setup_s,
        "submitted": nxt,
        "compiled_in_window": cell.meter.programs - programs,
        "prefill_steps": eng.metrics.prefill_steps - before[0],
        "decode_steps": eng.metrics.decode_steps - before[1],
        "queue_wait_s": [t.queue_wait for rid, t in eng.metrics.requests.items()
                         if rid in token_times and t.queue_wait is not None],
        # Tokens through the blocks of requests still prefilling at the close.
        "prefilled": {rid: r.prefilled for rid, r in eng.scheduler.active.items()},
    }


def measure(cell: Cell, requests: List[traffic_mod.Request], rec: Dict[str, Any]) -> Dict[str, Any]:
    """The window's record reduced: who finished, the end-to-end numbers over
    ALL requests and ALL gaps, and the counts the layer readers use."""
    token_times, served, elapsed = rec["token_times"], rec["served"], rec["elapsed_s"]
    finished = [r for r in requests if len(served[r.rid]) == r.new_tokens]
    done = {r.rid for r in finished}
    # The mix says who is judged: ``all`` it offered (an open loop below the
    # knee), or those it ``finished`` (a backlog, by design more than fit).
    judged = {"all": requests, "finished": finished}[cell.traffic["judge"]]
    ttft = [(token_times[r.rid][0] if token_times[r.rid] else elapsed) - r.due_s
            for r in judged]
    gaps = [b - a for r in requests for a, b in zip(token_times[r.rid], token_times[r.rid][1:])]
    output_tokens = sum(len(v) for v in token_times.values())
    processed, key_sum = 0, 0.0
    for r in requests:
        n = len(r.prompt) if token_times[r.rid] else rec["prefilled"].get(r.rid, 0)
        n += max(len(token_times[r.rid]) - 1, 0)     # the last token is never fed back
        processed += n
        key_sum += n * (n + 1) / 2.0
    end_to_end = {"setup_s": rec["setup_s"], "serve_tokens_per_s": output_tokens / elapsed}
    if ttft:
        end_to_end["ttft_p95_ms"] = 1e3 * percentile(ttft, 95)
    if gaps:
        end_to_end["itl_p95_ms"] = 1e3 * percentile(gaps, 95)
    steps = np.asarray(rec["steps"], np.float64).reshape(-1, 4)
    walls = {"all": steps[:, 1], "traced": steps[steps[:, 2] > 0, 1],
             "untraced": steps[steps[:, 2] == 0, 1]}
    m = cell.config
    row_bytes = (2 * m["num_hidden_layers"] * m["num_key_value_heads"]
                 * (m["hidden_size"] // m["num_attention_heads"]) * 2)      # K and V, bf16
    return {
        "finished": finished, "judged": len(judged),
        "failed": sum(1 for r in judged if r.rid not in done),
        "end_to_end": end_to_end,
        "facts": {
            "elapsed_s": elapsed, "depth": cell.config["num_hidden_layers"],
            "offered": len(requests), "submitted": rec["submitted"],
            "finished": len(finished), "output_tokens": output_tokens,
            "processed_tokens": processed, "key_sum": key_sum,
            "prefill_steps": rec["prefill_steps"], "decode_steps": rec["decode_steps"],
            "generator_lag_s": [rec["submit_s"][r.rid] - r.due_s for r in requests
                                if r.rid in rec["submit_s"]],
            "queue_wait_s": rec["queue_wait_s"],
            # Host-clock time of an engine step, over every step of the
            # window and over those inside and outside the traced part, so
            # that a traced run says whether its trace stands for the rest.
            "step_wall_ms": {k: 1e3 * float(v.mean()) for k, v in walls.items() if len(v)},
            "step_wall_max_ms": 1e3 * float(steps[:, 1].max()) if len(steps) else None,
            "kv_live_bytes": (float(np.average(steps[:, 3], weights=steps[:, 1])) * row_bytes
                              if len(steps) else None),
            "kv_pool_bytes": m["serve"]["num_slots"] * m["serve"]["max_len"] * row_bytes,
        },
    }


def served_logit_gap(cell: Cell, flat: Any, requests: List[traffic_mod.Request],
                     sample: List[traffic_mod.Request], served: Dict[str, List[int]],
                     low: bool = False) -> float:
    """The widest gap, over the sample's served tokens, by which the served
    token's reference logit lies below the reference's best.  With ``low``
    (the control) the tokens judged are those the low-precision reference
    itself puts first at each position of the same prompts and tokens."""
    length = max(len(r.prompt) + r.new_tokens for r in requests)
    rows = max(r.new_tokens for r in requests)
    ref = ServeReference(cell.config, flat, length, rows)
    control = ServeReference(cell.config, flat, length, rows, low=True) if low else None
    widest = 0.0
    for r in sample:
        tokens = np.asarray(served[r.rid], np.int32)
        logits = ref.chosen_logits(r.prompt, tokens)
        if control is not None:
            tokens = control.chosen_logits(r.prompt, tokens).argmax(-1)
        widest = max(widest, widest_gap(logits, tokens))
    return widest if sample else float("inf")


def run(cell: Cell) -> Outcome:
    sv = cell.config["serve"]
    eng, flat = build(cell)
    requests = resolve(cell.traffic["generator"])(
        cell.traffic, cell.seed, cell.seconds, cell.config["vocab_size"])
    rec = drive(cell, eng, requests)
    peak = peak_memory_bytes([jax.devices()[0]])
    del eng
    gc.collect()
    got = measure(cell, requests, rec)
    sample = sample_finished(got["finished"], cell.seed, sv["checked_requests"])
    widest = served_logit_gap(cell, flat, requests, sample, rec["served"])
    checks = [
        Check("served_logit_gap", widest, sv["limits"]["served_logit_gap"]),
        Check("compiled_in_window", float(rec["compiled_in_window"]), 0.0),
    ]
    facts = dict(got["facts"], checked_requests=len(sample),
                 checked_tokens=sum(r.new_tokens for r in sample))
    facts["notes"] = {k: facts[k] for k in (
        "submitted", "finished", "output_tokens", "prefill_steps", "decode_steps", "step_wall_ms",
        "step_wall_max_ms", "kv_live_bytes", "kv_pool_bytes", "checked_requests", "checked_tokens")}
    return Outcome(attempted=got["judged"], failed=got["failed"],
                   end_to_end=got["end_to_end"], checks=checks, facts=facts,
                   memory_peak_bytes=peak)
