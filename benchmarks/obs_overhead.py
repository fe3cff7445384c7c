"""Telemetry overhead rung: tracer + registry + reporter on vs off.

The obs layer's promise is observability that is ALWAYS ON — which only
holds if recording costs nothing measurable.  This rung times the CPU
tiny-llama training step twice: bare, and fully instrumented (a
``sync=False`` :class:`~torchgpipe_tpu.utils.tracing.Timeline` on the
engine — one ``perf_counter`` pair + list append per cell — plus a
:class:`~torchgpipe_tpu.obs.StepReporter` on a shared
:class:`~torchgpipe_tpu.obs.MetricsRegistry` called once per step).
``sync=False`` deliberately: ``sync=True`` is the *measurement* mode
(it serializes on purpose — that cost is the ablation's point, not
overhead); the always-on production configuration is dispatch
recording.

The two arms run INTERLEAVED (A/B per round) so host frequency drift
hits both equally, and each arm's per-step times are medianed.  Gate:
instrumented / bare − 1 must be **< 2%** (``BENCH_NOTES.md`` records
the measured figure).  Emits one JSON line (the bench contract)::

    env JAX_PLATFORMS=cpu python bench.py --obs-overhead
"""

from __future__ import annotations

import json
import time
from typing import Any, Callable, Dict, List, Tuple

OVERHEAD_GATE = 0.02  # <2% instrumented-over-bare, the documented bound
CHUNKS = 4
ROUNDS = 12  # per-arm measured steps (interleaved A/B)


def _build(tracer: Any) -> Tuple[Any, Any]:
    import jax
    import jax.numpy as jnp

    from benchmarks.llama_speed import PRESETS
    from torchgpipe_tpu.gpipe import GPipe
    from torchgpipe_tpu.models.transformer import TransformerConfig, llama

    dim, n_layers, n_heads, n_kv, vocab, mlp_ratio = PRESETS["tiny"]
    cfg = TransformerConfig(
        vocab=vocab, dim=dim, n_layers=n_layers, n_heads=n_heads,
        n_kv_heads=n_kv, mlp_ratio=mlp_ratio,
    )
    layers = llama(cfg)
    n_stages = 2
    base, rem = len(layers) // n_stages, len(layers) % n_stages
    balance = [
        base + (1 if j >= n_stages - rem else 0) for j in range(n_stages)
    ]
    model = GPipe(layers, balance=balance, chunks=CHUNKS,
                  checkpoint="except_last", tracer=tracer)
    x = jnp.zeros((8, 128), jnp.int32)
    return model, x


def _stepper(model: Any, x: Any, reporter: Any) -> Callable[[int], float]:
    """Returns ``run(i) -> seconds`` for one blocked training step,
    including the reporter tick when one is attached (that IS the
    instrumented arm's per-step cost)."""
    import jax

    from torchgpipe_tpu.models.transformer import cross_entropy

    def loss_fn(out: Any, tok: Any) -> Any:
        return cross_entropy(out[:, :-1, :], tok[:, 1:])

    in_spec = jax.ShapeDtypeStruct(x.shape, x.dtype)
    params, state = model.init(jax.random.PRNGKey(0), in_spec)
    rng = jax.random.PRNGKey(1)

    def run(i: int) -> float:
        t0 = time.perf_counter()
        loss, grads, _, _ = model.value_and_grad(
            params, state, x, x, loss_fn, rng=jax.random.fold_in(rng, i)
        )
        jax.block_until_ready((loss, grads))
        if reporter is not None:
            reporter.step()
        return time.perf_counter() - t0

    run(0)  # compile warmup, outside the timed rounds
    return run


def run() -> Dict[str, Any]:
    from torchgpipe_tpu.obs import MetricsRegistry, StepReporter
    from torchgpipe_tpu.utils.tracing import Timeline

    bare_model, x = _build(tracer=None)
    tracer = Timeline(sync=False)
    reg = MetricsRegistry()
    reporter = StepReporter(registry=reg, items_per_step=x.shape[0],
                            label="obs-overhead", log_every=0)
    obs_model, _ = _build(tracer=tracer)

    bare = _stepper(bare_model, x, reporter=None)
    inst = _stepper(obs_model, x, reporter=reporter)
    bare_times: List[float] = []
    inst_times: List[float] = []
    for i in range(1, ROUNDS + 1):
        bare_times.append(bare(i))
        inst_times.append(inst(i))
    bare_times.sort()
    inst_times.sort()
    b = bare_times[len(bare_times) // 2]
    o = inst_times[len(inst_times) // 2]
    overhead = o / b - 1.0
    assert tracer.events, "instrumented arm recorded no spans"
    assert reporter.steps == ROUNDS + 1
    return {
        "metric": "obs overhead [tiny llama, cpu, tracer+registry+reporter]",
        "value": round(overhead * 100, 3),
        "unit": "percent",
        "platform": "cpu",
        # Per-step blocking in both arms: neither can over-report.
        "validated": True,
        "gate_percent": OVERHEAD_GATE * 100,
        "pass": overhead < OVERHEAD_GATE,
        "bare_step_ms": round(b * 1e3, 3),
        "instrumented_step_ms": round(o * 1e3, 3),
        "spans_per_step": len(tracer.events) // (ROUNDS + 1),
    }


def main() -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    result = run()
    print(json.dumps(result), flush=True)
    return 0 if result["pass"] else 1


if __name__ == "__main__":
    from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
