"""Toy widths for the tests under chipbench/tests: every code path of a run
at a size the CPU holds.  Limits here were set from toy readings on the CPU
(six seeds of the program, three of the control; PERF.md section 2)."""

TOY_CONFIG = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 256, "num_hidden_layers": 2,
    "sliding_window": 64,
    "train": {"batch": 4, "seq": 128, "chunks": 2, "trace_steps": 2,
              # program <= 2.0e-4 / 3.7e-3 / 6.4e-3; control >= 1.4e-3 / 1.5e-2 / 1.3e-2
              "limits": {"loss_rel_gap": 6e-4, "grad_norm_gap": 8e-3, "change_norm_gap": 1.1e-2}},
    "serve": {"num_slots": 4, "max_len": 256, "prefill_chunk": 8, "donate": True,
              # program <= 0.023; control >= 0.30
              "limits": {"served_logit_gap": 0.1}},
}
TOY_SIZES = {
    "requests": 20, "trace_seconds": 1.0, "max_total": 116,
    "prompt_len": {"median": 24, "sigma": 0.9, "min": 4, "max": 100},
    "new_tokens": {"median": 8, "sigma": 0.7, "min": 2, "max": 16},
}
# No cell of BENCHMARK.json is an open loop yet; the generator and the
# builder's tails are kept for the cells PERF.md lists, and are driven here
# through the backlog cell's entry with the mix replaced.
OPEN_LOOP = dict(TOY_SIZES, generator="traffic:open_loop_fixed_set", judge="all",
                 arrival_span_s=2.0, drain_s=2.0, arrival_seed=3)
TRAIN, BACKLOG = "mistral-7b.train-4x4096", "mistral-7b.serve-backlog"
PP4 = "mistral-7b-pp4.train-8x4096"
STEADY = "open-loop"        # not a cell: see OPEN_LOOP
PP4_CONFIG = dict(TOY_CONFIG, num_hidden_layers=4,
                  train=dict(TOY_CONFIG["train"], batch=8, chunks=8))


def cell_of(workload):
    return BACKLOG if workload == STEADY else workload


def traffic_patch(workload):
    return {BACKLOG: TOY_SIZES, STEADY: OPEN_LOOP}.get(workload)
