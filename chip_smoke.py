"""Chip smoke: train with the pipeline, serve with the same weights, on the TPU.

The quickest proof that the system still starts on the chip.  One process
follows the repo's user story (``examples/serve.py``) at the published
widths of Mistral-7B-v0.1, depth cut to what one 16 GB chip holds, random
weights from ``--seed``:

    python chip_smoke.py            # one chip: phases (a)-(d) below
    python chip_smoke.py --chips 4  # four chips: the pp=4 paths, nothing else

(a) ``train_spmd``  SpmdGPipe -> make_train_step(adamw), loss vs the
                    un-pipelined dense-attention forward;
(b) ``train_mpmd``  the same blocks through GPipe, two stages on one chip;
(c) ``serve``       serving.Engine over the trained weights, staggered
                    ragged requests, chosen-token logits vs a dense forward;
(d) ``generate``    generate() at prompt lengths the flash blocks do not
                    divide, and flash vs dense prefill at an aligned length.

Each phase prints one JSON line; ``compile_s`` is XLA's compile (or
cache-load) time and ``steady_s`` the rest of the phase's wall clock
(tracing, transfers, execution) — smoke timings, not metrics.  The last
line is ``{"ok": true, "device": {...}}``.  Any failed check raises, so
the run exits non-zero with no result line.  There is no CPU mode: without
a TPU the script stops at once (tests/test_chip_smoke.py calls the phase
functions at toy width instead).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import gc
import json
import sys
import time
import types
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from torchgpipe_tpu import GPipe
from torchgpipe_tpu.layers import sequential_apply, sequential_init
from torchgpipe_tpu.models.generation import (
    generate,
    prefill,
    spmd_params_for_generation,
)
from torchgpipe_tpu.models.hf_interop import config_from_hf
from torchgpipe_tpu.models.transformer import (
    TransformerConfig,
    cross_entropy,
    llama,
    llama_spmd,
)
from torchgpipe_tpu.parallel.ring_attention import dense_attention_only
from torchgpipe_tpu.serving import Engine
from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh
from torchgpipe_tpu.utils.compile_cache import enable_compile_cache

# mistralai/Mistral-7B-v0.1 config.json, every width as published.
MISTRAL_7B_V01 = dict(
    hidden_size=4096,
    intermediate_size=14336,
    num_attention_heads=32,
    num_key_value_heads=8,
    vocab_size=32000,
    sliding_window=4096,
    rope_theta=10000.0,
    rms_norm_eps=1e-5,
    tie_word_embeddings=False,
)
PUBLISHED_DEPTH = 32
# Depth is the one cut.  A layer is 218 M parameters and embedding + head
# 262 M.  With weights, gradients and both adamw moments resident in bf16,
# the whole train step compiled for a described v5e counts 13.3 GiB at
# depth 5, 15.0 GiB at 6 and, by the same 1.7 GiB a layer, 16.7 GiB at 7,
# against the 15.75 GiB the chip offers: 6 is the most one chip holds.
DEPTH_ONE_CHIP = 6
# The MPMD engine holds less.  Its loss runs on the gathered mini-batch's
# f32 logits (3.9 GiB of scratch at 4 x 4096 x 32000), and except_last
# keeps one micro-batch's whole vjp residuals, a copy of each stage's
# weights among them.  On the chip depth 2 peaked at 11.8 GiB and depth 3
# missed the loss program's scratch by 0.2 GiB, so phase (b) runs the
# first two blocks of the same weights.
DEPTH_MPMD_ONE_CHIP = 2
# Four chips: a multiple of pp=4 (7.8 GiB a chip in the described-chip
# compile) whose un-pipelined comparison forward still fits ONE chip.
DEPTH_FOUR_CHIPS = 8

# Loss tolerance, pipelined vs un-pipelined, |a - b| <= LOSS_TOL * |b|.
# Both sides run the same bf16 weights; they differ in attention path
# (flash kernel vs dense einsum), micro-batching and fusion order, each
# of which moves a bf16 activation by an ulp (2**-8 relative).  The loss
# is an f32 mean over 16k tokens of f32 log-softmax rows, so those
# roundings average out instead of adding up: the chip showed 4e-6 to
# 9e-6 across both engines, one chip and four, at a loss of 10.88.  The
# bound leaves ten times that, far below what a wrong mask or a dropped
# micro-batch would move.
LOSS_TOL = 1e-4
# Logit tolerance for "the token the system chose is the dense forward's
# arg-max up to rounding".  Logits leave the head as bf16 at magnitude
# 4-8 under these weights, where one ulp is 2**-5 = 0.031; engine,
# generate and reference each round independently through the depth, so
# a near-tie may resolve differently.  The chip showed gaps of one ulp
# (serve, generate) and under two ulps (flash vs dense prefill); four
# ulps is the bound, and a wrong token sits whole units below.
LOGIT_TOL = 0.125


class SmokeFailure(RuntimeError):
    """A smoke check did not hold."""


def check(cond: Any, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The run's data sizes (the defaults are the chip's; tests shrink
    them, the command line cannot)."""

    batch: int = 4
    seq: int = 4096
    chunks: int = 4
    train_steps: int = 3          # after the warm-up step
    mpmd_steps: int = 2
    slots: int = 8
    max_len: int = 4096
    # 8 slots x 128 tokens a prefill step: a 1500-token prompt takes 12
    # steps instead of the 188 the Engine's default chunk of 8 would.
    prefill_chunk: int = 128
    requests: int = 12
    prompt_range: Tuple[int, int] = (30, 1500)
    new_range: Tuple[int, int] = (32, 64)
    # Prompt lengths that must be among the requests, and that phase (d)
    # runs through generate(): 100 is the length Mosaic refused, 200 the
    # one whose tail rows the short grid never wrote.
    fixed_prompts: Tuple[int, ...] = (100, 200)
    generate_prompts: int = 4
    generate_max_len: int = 2048  # a decode-kernel block multiple
    flash_block: int = 128        # no prompt is a multiple of this
    flash_len: int = 256          # the aligned flash-vs-dense length


def mistral_config(depth: int) -> TransformerConfig:
    hf = types.SimpleNamespace(**MISTRAL_7B_V01, num_hidden_layers=depth)
    return dataclasses.replace(config_from_hf(hf), dtype=jnp.bfloat16)


class CompileMeter:
    """Programs compiled, and the seconds XLA spent compiling them (or
    loading them from the persistent cache), from jax's own monitoring
    event.  Tracing and lowering are not in it: their events nest, so
    they cannot be summed."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_: Any) -> None:
        if event == self._EVENT:
            self.seconds += secs
            self.programs += 1


def device_line() -> Dict[str, Any]:
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def peak_bytes(device: Any) -> Optional[int]:
    # The process's high-water mark so far, not this phase's alone; the
    # CPU backend (tests) reports no statistics.
    stats = device.memory_stats()
    return None if stats is None else stats["peak_bytes_in_use"]


@contextlib.contextmanager
def phase(name: str, meter: CompileMeter) -> Iterator[Dict[str, Any]]:
    """Time one phase and print its line on success; a failure propagates
    and prints nothing."""
    line: Dict[str, Any] = {"phase": name}
    t0, c0 = time.perf_counter(), meter.seconds
    yield line
    wall, compile_s = time.perf_counter() - t0, meter.seconds - c0
    line.update(
        ok=True,
        compile_s=round(compile_s, 2),
        steady_s=round(wall - compile_s, 2),
        device=device_line(),
        peak_bytes_in_use=peak_bytes(jax.devices()[0]),
    )
    print(json.dumps(line), flush=True)


def release() -> None:
    """Drop compiled programs and dead buffers before the next phase."""
    gc.collect()
    jax.clear_caches()
    gc.collect()


def kernel_calls(lowered: Any) -> int:
    return lowered.as_text(dialect="hlo").count("tpu_custom_call")


class DenseReference:
    """The model's plain full forward on the dense attention path:
    ``sequential_apply`` over ``llama(cfg)``, all positions' logits, one
    row at a time at one fixed length (attention is causal, so right
    padding changes no earlier position — one program serves every
    request).  Its programs are lowered under ``dense_attention_only``,
    which pins the dispatcher to ``full_attention``, the dense einsum,
    and refused if a kernel was lowered all the same."""

    def __init__(self, cfg: TransformerConfig, params: Sequence[Any],
                 length: int, rows: int) -> None:
        self.params, self.length, self.rows = params, length, rows
        self._layers = llama(cfg)

    def _logits(self, params: Any, tokens: Any) -> jnp.ndarray:
        out, _ = sequential_apply(
            self._layers, params, [()] * len(self._layers), tokens,
            train=False,
        )
        return out.astype(jnp.float32)

    def _compile(self, fn: Any, *extra: Any) -> Any:
        tokens = jax.ShapeDtypeStruct((1, self.length), jnp.int32)
        with dense_attention_only():
            lowered = jax.jit(fn).lower(self.params, tokens, *extra)
        check(kernel_calls(lowered) == 0,
              "the dense reference lowered a Pallas kernel")
        return lowered.compile()

    @functools.cached_property
    def _loss(self) -> Any:
        return self._compile(
            lambda params, x, y: cross_entropy(self._logits(params, x), y),
            jax.ShapeDtypeStruct((1, self.length), jnp.int32),
        )

    @functools.cached_property
    def _rows(self) -> Any:
        return self._compile(
            lambda params, tokens, start: jax.lax.dynamic_slice_in_dim(
                self._logits(params, tokens)[0], start, self.rows
            ),
            jax.ShapeDtypeStruct((), jnp.int32),
        )

    def row_losses(self, x: jnp.ndarray, y: jnp.ndarray) -> np.ndarray:
        """Mean token loss of each row (rows are equally long, so the
        mean over any rows is those rows' batch loss)."""
        check(x.shape[1] == self.length, "reference length mismatch")
        rows = [
            self._loss(self.params, x[i:i + 1], y[i:i + 1])
            for i in range(x.shape[0])
        ]
        return np.asarray(jax.device_get(rows), np.float64)

    def worst_gap(self, prompt: np.ndarray, chosen: np.ndarray) -> float:
        """Teacher-force ``prompt + chosen``; over the generated
        positions, the largest (row max - logit of the chosen token)."""
        n, p = len(chosen), len(prompt)
        check(0 < n <= self.rows and p + n <= self.length,
              "request does not fit the reference program")
        tokens = np.zeros((1, self.length), np.int32)
        tokens[0, :p + n] = np.concatenate([prompt, chosen])
        # Position p-1+i predicts chosen[i].
        rows = np.asarray(
            self._rows(self.params, tokens, np.int32(p - 1))
        )[:n]
        check(np.isfinite(rows).all(), "reference logits are not finite")
        return float(np.max(rows.max(-1) - rows[np.arange(n), chosen]))


def make_batch(cfg: TransformerConfig, sizes: Sizes, seed: int,
               batch: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch, sizes.seq + 1), 0, cfg.vocab
    )
    return tokens[:, :-1], tokens[:, 1:]


def make_requests(cfg: TransformerConfig, sizes: Sizes,
                  seed: int) -> List[Tuple[np.ndarray, int]]:
    """(prompt, new tokens) pairs: the fixed lengths first, the rest
    drawn from the seed, none a multiple of the flash block."""
    rng = np.random.RandomState(seed)
    lengths = list(sizes.fixed_prompts)
    while len(lengths) < sizes.requests:
        n = int(rng.randint(sizes.prompt_range[0], sizes.prompt_range[1] + 1))
        if n % sizes.flash_block:
            lengths.append(n)
    check(all(n % sizes.flash_block for n in lengths),
          "a prompt length is a multiple of the flash block")
    return [
        (
            rng.randint(0, cfg.vocab, size=n).astype(np.int32),
            int(rng.randint(sizes.new_range[0], sizes.new_range[1] + 1)),
        )
        for n in lengths
    ]


def reference_length(sizes: Sizes) -> int:
    longest = max(sizes.prompt_range[1], *sizes.fixed_prompts)
    return -(-(longest + sizes.new_range[1]) // 128) * 128


def build_spmd(cfg: TransformerConfig, devices: Sequence[Any], chunks: int,
               seed: int, in_spec: Any) -> Tuple[SpmdGPipe, Any]:
    """One pipeline stage per device."""
    n_stages = len(devices)
    mesh = make_mesh(n_stages, devices=devices)
    block, pre, post = llama_spmd(cfg, n_stages)
    pipe = SpmdGPipe(
        block, n_stages, mesh, chunks=chunks, loss_fn=cross_entropy,
        pre=pre, post=post,
    )
    return pipe, pipe.init(jax.random.PRNGKey(seed), in_spec)


def run_spmd_steps(
    pipe: SpmdGPipe, params: Any, x: jnp.ndarray, y: jnp.ndarray,
    steps_after_warmup: int, meter: CompileMeter,
) -> Tuple[List[float], Any]:
    """Warm-up step plus ``steps_after_warmup`` through the fused
    train step; no program may compile after the warm-up."""
    opt = optax.adamw(1e-4)
    opt_state = pipe.place_tree(opt.init(params))
    step = pipe.make_train_step(opt)
    loss, params, opt_state = step(params, opt_state, x, y)
    losses = [float(loss)]
    compiled = meter.programs
    for _ in range(steps_after_warmup):
        loss, params, opt_state = step(params, opt_state, x, y)
        losses.append(float(loss))
    check(meter.programs == compiled,
          f"{meter.programs - compiled} programs compiled after the "
          "warm-up step (the train step retraced)")
    check(np.isfinite(losses).all(), f"non-finite loss in {losses}")
    return losses, params


def unpipelined_row_losses(cfg: TransformerConfig, flat: Sequence[Any],
                           x: jnp.ndarray, y: jnp.ndarray,
                           device: Any) -> np.ndarray:
    """Each row's loss through the plain dense forward on ``device``."""
    flat = jax.device_put(list(flat), device)
    return DenseReference(cfg, flat, x.shape[1], 1).row_losses(x, y)


def check_loss(got: float, ref: float, what: str) -> None:
    check(abs(got - ref) <= LOSS_TOL * abs(ref),
          f"{what} loss {got} vs un-pipelined {ref}: beyond {LOSS_TOL}")


def build_mpmd(cfg: TransformerConfig, n_stages: int,
               devices: Sequence[Any], chunks: int,
               flat_host: Sequence[Any], in_spec: Any) -> Tuple[GPipe, Any, Any]:
    """GPipe over ``llama(cfg)`` holding the given per-layer weights;
    stages wrap around ``devices``."""
    layers = llama(cfg)
    base, rem = divmod(len(layers), n_stages)
    balance = [base + (j < rem) for j in range(n_stages)]
    model = GPipe(layers, balance, devices=devices, chunks=chunks,
                  checkpoint="except_last")
    state_shapes = jax.eval_shape(
        lambda key: sequential_init(layers, key, in_spec)[1],
        jax.random.PRNGKey(0),
    )
    check(not jax.tree_util.tree_leaves(state_shapes),
          "llama layers carry state")
    params = model.place(model.repartition((list(flat_host),)))
    state = model.repartition((state_shapes,))
    return model, params, state


def run_mpmd_steps(
    model: GPipe, params: Any, state: Any, x: jnp.ndarray, y: jnp.ndarray,
    steps: int, meter: CompileMeter,
) -> List[float]:
    """``value_and_grad`` steps with a plain SGD update between them; the
    per-cell programs compile in the first step only."""
    sgd = jax.jit(
        lambda p, g: jax.tree_util.tree_map(
            lambda a, b: (a - 1e-3 * b).astype(a.dtype), p, g
        )
    )
    losses = []
    for i in range(steps):
        compiled = meter.programs
        loss, grads, state, _ = model.value_and_grad(
            params, state, x, y, cross_entropy
        )
        losses.append(float(loss))
        check(i == 0 or meter.programs == compiled,
              "a per-cell program compiled after the first MPMD step")
        params = tuple(sgd(p, g) for p, g in zip(params, grads))
    check(np.isfinite(losses).all(), f"non-finite loss in {losses}")
    return losses


# ------------------------------------------------------------------ #
# one chip                                                           #
# ------------------------------------------------------------------ #


def train_spmd(cfg: TransformerConfig, sizes: Sizes, seed: int,
               meter: CompileMeter) -> Dict[str, Any]:
    """Phase (a).  Returns what the later phases need: the batch, and
    host copies of the initial and the trained per-layer weights."""
    with phase("train_spmd", meter) as line:
        device = jax.devices()[0]
        x, y = make_batch(cfg, sizes, seed, sizes.batch)
        in_spec = jax.ShapeDtypeStruct(x.shape, x.dtype)
        pipe, params = build_spmd(cfg, [device], sizes.chunks, seed, in_spec)
        flat = spmd_params_for_generation(pipe, params)
        ref_loss = float(
            unpipelined_row_losses(cfg, flat, x, y, device).mean()
        )
        init_host = jax.device_get(flat)
        del flat
        kernels = kernel_calls(jax.jit(pipe.train_step).lower(params, x, y))
        if device.platform == "tpu":
            check(kernels > 0,
                  "the train step lowered no tpu_custom_call: attention "
                  "took interpret mode or the dense branch")
        losses, params = run_spmd_steps(
            pipe, params, x, y, sizes.train_steps, meter
        )
        check_loss(losses[0], ref_loss, "SpmdGPipe step-1")
        trained_host = jax.device_get(
            spmd_params_for_generation(pipe, params)
        )
        line.update(
            depth=cfg.n_layers, published_depth=PUBLISHED_DEPTH,
            batch=sizes.batch, seq=sizes.seq, chunks=sizes.chunks,
            losses=losses, unpipelined_loss=ref_loss,
            tpu_custom_calls=kernels,
        )
    return dict(x=x, y=y, init_host=init_host, trained_host=trained_host)


def train_mpmd(cfg: TransformerConfig, depth: int, sizes: Sizes,
               carry: Dict[str, Any], meter: CompileMeter) -> None:
    """Phase (b): the first ``depth`` blocks of phase (a)'s initial
    weights, and its batch, through the MPMD engine — two stages, both
    on the one chip."""
    with phase("train_mpmd", meter) as line:
        device = jax.devices()[0]
        cfg = dataclasses.replace(cfg, n_layers=depth)
        init = carry["init_host"]
        weights = [init[0], *init[1:1 + depth], init[-1]]
        x, y = carry["x"], carry["y"]
        ref_loss = float(
            unpipelined_row_losses(cfg, weights, x, y, device).mean()
        )
        model, params, state = build_mpmd(
            cfg, 2, [device], sizes.chunks, weights,
            jax.ShapeDtypeStruct(x.shape, x.dtype),
        )
        losses = run_mpmd_steps(
            model, params, state, x, y, sizes.mpmd_steps, meter
        )
        check_loss(losses[0], ref_loss, "GPipe step-1")
        line.update(
            depth=depth, published_depth=PUBLISHED_DEPTH,
            balance=model.balance, losses=losses, unpipelined_loss=ref_loss,
        )


def serve(cfg: TransformerConfig, sizes: Sizes, seed: int,
          flat: Sequence[Any], ref: DenseReference,
          meter: CompileMeter) -> List[Tuple[np.ndarray, int]]:
    """Phase (c): continuous batching over the trained weights."""
    with phase("serve", meter) as line:
        requests = make_requests(cfg, sizes, seed)
        eng = Engine(
            cfg, flat, num_slots=sizes.slots, max_len=sizes.max_len,
            prefill_chunk=sizes.prefill_chunk,
        )
        rids = []
        for prompt, new in requests:
            rids.append(eng.submit(prompt, new))
            eng.step()  # staggered: the engine serves between arrivals
        check(eng.run() == "idle", "the engine did not run to idle")
        check(sum(eng.compile_stats.values()) == eng.program_count,
              f"compiled {eng.compile_stats}, bound {eng.program_count}")
        worst = 0.0
        for rid, (prompt, new) in zip(rids, requests):
            out = eng.result(rid)
            check(eng.status(rid) == "finished" and len(out) == new,
                  f"{rid}: {eng.status(rid)}, {len(out)} of {new} tokens")
            worst = max(worst, ref.worst_gap(prompt, out))
        check(worst <= LOGIT_TOL,
              f"an engine token is {worst} below the dense arg-max logit")
        snap = eng.metrics.snapshot()
        line.update(
            requests=len(requests),
            prompt_lengths=[len(p) for p, _ in requests],
            new_tokens=sum(n for _, n in requests),
            engine_steps=snap["engine_steps"],
            programs=eng.compile_stats, worst_logit_gap=worst,
        )
    return requests


def generate_phase(cfg: TransformerConfig, sizes: Sizes, seed: int,
                   flat: Sequence[Any], ref: DenseReference,
                   requests: Sequence[Tuple[np.ndarray, int]],
                   meter: CompileMeter) -> None:
    """Phase (d): ``generate()`` at undivided prompt lengths, then the
    flash prefill kernel against the dense prefill at an aligned one."""
    with phase("generate", meter) as line:
        worst = 0.0
        lengths = []
        for prompt, new in requests[:sizes.generate_prompts]:
            def run(params, tokens, new=new):
                logits0, _ = prefill(cfg, params, tokens,
                                     sizes.generate_max_len)
                out = generate(cfg, params, tokens, new,
                               max_len=sizes.generate_max_len)
                return logits0, out

            logits0, out = jax.jit(run)(flat, prompt[None])
            check(bool(jnp.isfinite(logits0).all()),
                  f"prefill logits not finite at prompt length {len(prompt)}")
            worst = max(worst, ref.worst_gap(prompt, np.asarray(out[0])))
            lengths.append(len(prompt))
        check(worst <= LOGIT_TOL,
              f"a generate() token is {worst} below the dense arg-max logit")

        tokens = jax.random.randint(
            jax.random.PRNGKey(seed + 2), (1, sizes.flash_len), 0, cfg.vocab
        )
        by_path = {
            use_flash: jax.jit(
                lambda p, t, f=use_flash: prefill(
                    cfg, p, t, sizes.flash_len, use_flash=f
                )[0]
            )(flat, tokens)
            for use_flash in (True, False)
        }
        flash_gap = float(
            jnp.max(jnp.abs(by_path[True] - by_path[False]))
        )
        check(flash_gap <= LOGIT_TOL,
              f"flash vs dense prefill logits differ by {flash_gap}")
        line.update(prompt_lengths=lengths, worst_logit_gap=worst,
                    flash_len=sizes.flash_len,
                    flash_vs_dense_max_abs=flash_gap)


def one_chip(cfg: TransformerConfig, mpmd_depth: int, sizes: Sizes,
             seed: int, meter: CompileMeter) -> None:
    carry = train_spmd(cfg, sizes, seed, meter)
    release()
    train_mpmd(cfg, mpmd_depth, sizes, carry, meter)
    trained_host = carry["trained_host"]
    del carry
    release()
    flat = jax.device_put(trained_host, jax.devices()[0])
    ref = DenseReference(cfg, flat, reference_length(sizes), sizes.new_range[1])
    requests = serve(cfg, sizes, seed, flat, ref, meter)
    generate_phase(cfg, sizes, seed, flat, ref, requests, meter)


# ------------------------------------------------------------------ #
# four chips                                                         #
# ------------------------------------------------------------------ #


def shard_device_ids(tree: Any) -> List[int]:
    return sorted({
        shard.device.id
        for leaf in jax.tree_util.tree_leaves(tree)
        for shard in leaf.addressable_shards
    })


def four_chips(cfg: TransformerConfig, sizes: Sizes, seed: int,
               devices: Sequence[Any], meter: CompileMeter) -> None:
    """pp=4 through both engines against the un-pipelined forward on one
    of the chips; placement is asserted, not assumed."""
    check(len(devices) == 4, f"--chips 4 needs 4 devices, got {len(devices)}")
    want = sorted(d.id for d in devices)
    chunks = 2 * sizes.chunks
    with phase("train_spmd_pp4", meter) as line:
        x, y = make_batch(cfg, sizes, seed, chunks)
        in_spec = jax.ShapeDtypeStruct(x.shape, x.dtype)
        pipe, params = build_spmd(cfg, devices, chunks, seed, in_spec)
        check(shard_device_ids(params["blocks"]) == want,
              "SPMD block parameters are not on four distinct devices")
        flat = spmd_params_for_generation(pipe, params, devices[0])
        ref_rows = unpipelined_row_losses(cfg, flat, x, y, devices[0])
        ref_loss = float(ref_rows.mean())
        init_host = jax.device_get(flat)
        del flat
        hlo = jax.jit(pipe.train_step).lower(params, x, y).as_text(
            dialect="hlo"
        )
        check("collective-permute" in hlo,
              "no collective-permute in the pp=4 step: no stage hand-off")
        losses, params = run_spmd_steps(
            pipe, params, x, y, sizes.train_steps - 1, meter
        )
        check_loss(losses[0], ref_loss, "SpmdGPipe pp=4 step-1")
        in_use = [
            (d.memory_stats() or {}).get("bytes_in_use") for d in devices
        ]
        if devices[0].platform == "tpu":
            check(all(in_use), f"a chip holds nothing: bytes in use {in_use}")
        line.update(
            depth=cfg.n_layers, published_depth=PUBLISHED_DEPTH,
            batch=chunks, seq=sizes.seq, chunks=chunks, losses=losses,
            unpipelined_loss=ref_loss, block_param_devices=want,
            bytes_in_use=in_use,
            tpu_custom_calls=hlo.count("tpu_custom_call"),
        )
    del pipe, params
    release()
    with phase("train_mpmd_pp4", meter) as line:
        # Half the rows: the MPMD loss runs on the gathered mini-batch's
        # f32 logits, all of it on the last stage's chip.
        rows = sizes.chunks
        x, y, ref_loss = x[:rows], y[:rows], float(ref_rows[:rows].mean())
        model, params, state = build_mpmd(
            cfg, 4, devices, rows, init_host,
            jax.ShapeDtypeStruct(x.shape, x.dtype),
        )
        placed = [shard_device_ids(stage) for stage in params]
        check(placed == [[d.id] for d in model.devices] and
              sorted(i for ids in placed for i in ids) == want,
              f"MPMD stage parameters sit on {placed}")
        losses = run_mpmd_steps(
            model, params, state, x, y, sizes.mpmd_steps, meter
        )
        check_loss(losses[0], ref_loss, "GPipe pp=4 step-1")
        line.update(
            depth=cfg.n_layers, batch=rows, chunks=rows,
            balance=model.balance, losses=losses, unpipelined_loss=ref_loss,
            stage_devices=placed,
        )


def main(argv: Optional[Sequence[str]] = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    info = device_line()
    if info["platform"] != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; jax found {info}")
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    print(json.dumps({"phase": "start", "chips": args.chips,
                      "seed": args.seed, "compile_cache": cache_dir,
                      "model": "Mistral-7B-v0.1", **MISTRAL_7B_V01}),
          flush=True)
    if args.chips == 4:
        four_chips(mistral_config(DEPTH_FOUR_CHIPS), Sizes(), args.seed,
                   jax.devices(), meter)
    else:
        one_chip(mistral_config(DEPTH_ONE_CHIP), DEPTH_MPMD_ONE_CHIP, Sizes(),
                 args.seed, meter)
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
