"""The main path's kernels, compiled for a DESCRIBED TPU v5e.

No chip is attached: the TPU compiler installed beside jax compiles for a
topology description, so what Mosaic or XLA:TPU would refuse on the chip
(untileable slices, too much VMEM, a program that does not fit HBM) is
refused here, on the CPU, at no chip time.  Interpret mode enforces none
of that.  Shapes are chip_smoke.py's: Mistral-7B widths, bf16.  A compile
that passes is a compile, not a chip run — nothing executes.
"""

import dataclasses
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from torchgpipe_tpu.models import generation
from torchgpipe_tpu.ops.flash_attention import (
    flash_attention,
    flash_decode_attention,
    latent_decode_attention,
)
from torchgpipe_tpu.ops.grouped_matmul import grouped_matmul

H, G, D = 32, 8, 128           # Mistral-7B: query heads, KV heads, head dim
SEQ, WINDOW, MAX_LEN = 4096, 4096, 4096
BF16 = jnp.bfloat16


def _qkv(s, d=D):
    return [((1, s, H, d), BF16), ((1, s, G, d), BF16), ((1, s, G, d), BF16)]


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology: {e}")
    return topo.devices[0]


@pytest.fixture(autouse=True)
def _no_compile_cache():
    # A described-chip executable is written to the persistent cache but
    # cannot be read back without a chip: the next run would warn on
    # every case.  Keep these compiles out of it.
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _flash(s, d=D, grad=False, **kw):
    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, **kw)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    return (jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd), _qkv(s, d)


def _decode(quant, window):
    def fn(q, ck, cv, pos0, *scales):
        ks, vs = scales if quant else (None, None)
        return flash_decode_attention(
            q, ck, cv, pos0, window=window, k_scale=ks, v_scale=vs
        )

    cache = ((1, MAX_LEN, G, D), jnp.int8 if quant else BF16)
    shapes = [((1, 1, H, D), BF16), cache, cache, ((), jnp.int32)]
    if quant:
        shapes += [((1, G, MAX_LEN), jnp.float32)] * 2
    return fn, shapes


def _calls(text, kernel):
    """The instructions of a compiled program named after ``kernel`` (a
    Pallas kernel's name, or the compiler's ``ragged-dot``).  The
    program's source-frame tables are not searched: a lowering the
    compiler cached for another program of the same process (a cumulative
    sum over as many elements, say) brings that program's function names
    along."""
    return re.findall(rf"%{re.escape(kernel)}[\w.-]* = ", text)


def _row_scatters(text, rows, width):
    """The compiled program's scatters into ``[rows, width]``: the shape of
    the expert layer's token rows.  The KV banks' writes are scatters too,
    so the shape decides, not the word."""
    shape = re.compile(rf"= \w+\[{rows},{width}\]\S* scatter\(")
    return [line for line in text.splitlines() if shape.search(line)]


SLOTS, ROWS, CHUNK = 64, 12, 32    # mistral-7b.serve-backlog's pool
BANK = (SLOTS, MAX_LEN, G, D)
BANK_BYTES = SLOTS * MAX_LEN * G * D * 2      # one layer's K (or V): 512 MiB


def _decode_rows(rows, g, compact):
    """The per-row kernel over the serving pool's bank: ``rows`` query
    rows of ``g`` tokens, each at its own frontier (and slot)."""
    def fn(q, ck, cv, pos0, lengths, slots):
        return flash_decode_attention(
            q, ck, cv, pos0, window=WINDOW, lengths=lengths,
            slots=slots if compact else None,
        )

    ints = ((rows,), jnp.int32)
    return fn, [((rows, g, H, D), BF16), (BANK, BF16), (BANK, BF16),
                ints, ints, ints]


# trinity-large.serve-mixed-backlog's pool: 48 query heads on 8 KV heads,
# a window layer's ring of 4,608 rows (window 4,096 + a chunk, rounded to
# the kernel's block) and a full layer's 16,384.
T_H, T_SLOTS, T_ROWS, T_RING, T_LEN = 48, 48, 9, 4608, 16384


def _decode_ring(rows, g, compact, ring):
    """The per-row kernel over a bank of the mixed pool: a window layer's
    ring (the band's blocks fetched modulo the ring) or a full layer's
    rows, ``rows`` query rows of ``g`` tokens at their own frontiers."""
    def fn(q, ck, cv, pos0, lengths, slots):
        return flash_decode_attention(
            q, ck, cv, pos0, window=4096 if ring else None, lengths=lengths,
            slots=slots if compact else None, ring=ring,
        )

    bank = (T_SLOTS, T_RING if ring else T_LEN, G, D)
    ints = ((rows,), jnp.int32)
    return fn, [((rows, g, T_H, D), BF16), (bank, BF16), (bank, BF16),
                ints, ints, ints]


T_RING_BYTES = T_SLOTS * T_RING * G * D * 2

# axk1.serve-backlog's pool: 64 heads over a latent of 512 and a shared
# rotated key head of 64, 128 slots x 4096 rows; the decode program's
# 128 rows of one token and the prefill program's 25 rows of 32.
L_H, L_C, L_R, L_SLOTS, L_ROWS = 64, 512, 64, 128, 25
L_KPE_BYTES = L_SLOTS * MAX_LEN * L_R * 2     # one layer's key head: 64 MiB
# The same bank rows-major, its 64 padded to a lane tile: what the scatter
# that writes a step's key heads relays it to and back, twice a layer.
L_RELAID_BYTES = L_SLOTS * MAX_LEN * 128 * 2


def _latent_decode(rows, g, compact):
    """The latent kernel over the pool's two banks as they lie."""
    def fn(q_lat, q_pe, ckv, kpe, pos0, lengths, slots):
        return latent_decode_attention(
            q_lat, q_pe, ckv, kpe, pos0, sm_scale=0.13, lengths=lengths,
            slots=slots if compact else None,
        )

    ints = ((rows,), jnp.int32)
    return fn, [((rows, g, L_H, L_C), BF16), ((rows, g, L_H, L_R), BF16),
                ((L_SLOTS, MAX_LEN, L_C), BF16),
                ((L_SLOTS, MAX_LEN, L_R), BF16), ints, ints, ints]


def _prefill_attention(s):
    def fn(q, k, v):
        return generation._attend_full(q, k, v, WINDOW)

    return fn, _qkv(s)


def _grouped(m, k, n, groups):
    def fn(x, w, group_sizes):
        return grouped_matmul(x, w, group_sizes)

    return fn, [((m, k), BF16), ((groups, k, n), BF16), ((groups,), jnp.int32)]


def _bank_bytes(m, k, n, groups):
    return groups * k * n * 2


# name -> (fn, argument (shape, dtype)s, kernel expected in the executable)
CASES = {
    "flash-fwd": (*_flash(SEQ, window=WINDOW), True),
    "flash-bwd-resident": (
        *_flash(SEQ, grad=True, streaming=False, window=WINDOW), True),
    "flash-bwd-streaming": (
        *_flash(SEQ, grad=True, streaming=True, window=WINDOW), True),
    # A window shorter than the sequence: the banded block ranges.
    "flash-bwd-windowed": (*_flash(SEQ, grad=True, window=1024), True),
    "flash-head64-padded-2048": (*_flash(2048, d=64, grad=True), True),
    "decode-bf16": (*_decode(False, None), True),
    "decode-bf16-window": (*_decode(False, WINDOW), True),
    "decode-int8": (*_decode(True, None), True),
    "decode-int8-window": (*_decode(True, WINDOW), True),
    # The serving engine's two programs' attention, at the cell's shapes:
    # the bank is the kernel's operand as it lies, so the executable
    # holds no temporary of a bank's size (a head-folded view of it was
    # a relayout copy of 512 MiB a bank: described-chip compile, PR 29).
    "decode-rows-pool": (*_decode_rows(SLOTS, 1, False), True, BANK_BYTES),
    "decode-rows-compact": (
        *_decode_rows(ROWS, CHUNK, True), True, BANK_BYTES),
    # chip_smoke.py's engine: 8 rows of 128 tokens (a shorter block).
    "decode-rows-chunk128": (*_decode_rows(8, 128, True), True, BANK_BYTES),
    # The mixed pool's two kinds of bank under both programs' shapes
    # (the ring's index map takes the band's blocks modulo the ring).
    "decode-ring-pool": (
        *_decode_ring(T_SLOTS, 1, False, True), True, T_RING_BYTES),
    "decode-ring-compact-32": (
        *_decode_ring(T_ROWS, 32, True, True), True, T_RING_BYTES),
    "decode-ring-compact-128": (
        *_decode_ring(T_ROWS, 128, True, True), True, T_RING_BYTES),
    "decode-full-16k-pool": (
        *_decode_ring(T_SLOTS, 1, False, False), True, T_RING_BYTES),
    "decode-full-16k-compact-128": (
        *_decode_ring(T_ROWS, 128, True, False), True, T_RING_BYTES),
    # The latent pool's kernel under both programs' shapes.  The key
    # head's bank lies positions-minor on the chip (a minor dim of 64
    # would be padded to a lane tile), and the kernel takes it so: a
    # ``[block_k, 64]`` tile of it was a relayout copy of the bank, 128
    # MiB a layer (described-chip compile, PR 35).
    "latent-decode-pool": (
        *_latent_decode(L_SLOTS, 1, False), True, L_KPE_BYTES),
    "latent-decode-compact": (
        *_latent_decode(L_ROWS, CHUNK, True), True, L_KPE_BYTES),
    # prefill()/generate(): a prompt the 128-blocks do not divide must
    # take the dense path (100 was refused by Mosaic, 200 compiled to a
    # short grid that left the tail rows unwritten), an aligned one the
    # kernel.
    "prefill-attention-100": (*_prefill_attention(100), False),
    "prefill-attention-200": (*_prefill_attention(200), False),
    "prefill-attention-256": (*_prefill_attention(256), True),
    # The served expert layers' grouped products (rows, width in, width
    # out, held experts), each under a bank's bytes of temporaries: the
    # bank goes in as it lies.  Nemotron's up bank, 1,856 wide, is stored
    # with its 2,688 minor; the compiler's ragged-dot took a relaid copy
    # of it, 638 MB a call (a described-chip compile of that product).
    **{
        f"grouped-{name}": (*_grouped(*shape), True, _bank_bytes(*shape))
        for name, shape in {
            "nemotron-decode-up": (3072, 2688, 1856, 64),
            "nemotron-prefill-up": (39168, 2688, 1856, 64),
            "nemotron-prefill-down": (39168, 1856, 2688, 64),
            "axk1-decode-gate": (1024, 7168, 2048, 12),
            "axk1-prefill-down": (6400, 2048, 7168, 12),
            # 192 rows: padded to the row tile.
            "trinity-decode-up": (192, 3072, 3072, 32),
        }.items()
    },
}


@pytest.mark.parametrize("name", list(CASES))
def test_compiles_for_v5e(name, chip, monkeypatch):
    fn, shapes, wants_kernel, *temp_limit = CASES[name]
    # generation.py asks jax.devices() for the platform; a described-chip
    # compile still sees the CPU there, so the test answers for it.
    monkeypatch.setattr(jax, "devices", lambda *a: [chip])
    where = SingleDeviceSharding(chip)
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=where)
        for shape, dtype in shapes
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == wants_kernel
    for limit in temp_limit:
        assert compiled.memory_analysis().temp_size_in_bytes < limit


@pytest.mark.parametrize("compact", [False, True], ids=["pool", "compact"])
def test_decode_slots_compiles_for_v5e(compact, chip, monkeypatch):
    """``decode_slots`` at depth 1, Mistral-7B widths, over the cell's
    pool (64 slots x 4096 rows, donated): the decode program (pool-wide,
    one token a row) and the compact prefill program (12 rows of 32).
    On a TPU both take the per-row kernel, and neither holds a
    temporary of a bank's size: the scattered bank goes into the kernel
    as it lies."""
    from torchgpipe_tpu.layers import sequential_init
    from torchgpipe_tpu.models.transformer import TransformerConfig, llama

    cfg = TransformerConfig(
        vocab=32000, dim=4096, n_layers=1, n_heads=H, n_kv_heads=G,
        mlp_ratio=14336 / 4096, rope_theta=10000.0, dtype=BF16,
        attn_window=WINDOW,
    )
    monkeypatch.setattr(jax, "devices", lambda *a: [chip])
    where = SingleDeviceSharding(chip)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=where),
            tree,
        )

    params = jax.eval_shape(
        lambda: sequential_init(
            llama(cfg), jax.random.PRNGKey(0),
            jax.ShapeDtypeStruct((1, 8), jnp.int32),
        )[0]
    )
    cache = jax.eval_shape(
        lambda: generation.init_cache(cfg, SLOTS, MAX_LEN)
    )
    rows, g = (ROWS, CHUNK) if compact else (SLOTS, 1)
    ints = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=where
    )

    def step(params, cache, lengths, tokens, n_valid, slots):
        return generation.decode_slots(
            cfg, params, tokens, cache, lengths, n_valid,
            slots=slots if compact else None,
        )

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache), ints(SLOTS), ints(rows, g),
        ints(rows), ints(rows),
    ).compile()
    assert "flash_decode" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < BANK_BYTES


@pytest.mark.parametrize("compact", [False, True], ids=["pool", "compact"])
def test_latent_decode_slots_compiles_for_v5e(compact, chip, monkeypatch):
    """``decode_slots`` over ``axk1.serve-backlog``'s latent pool (128
    slots x 4096 rows, donated) at published widths, the dense layer and
    one expert layer: the decode program and the compact prefill program
    take the latent kernel once a layer.  The dense path's f32 score
    planes are gone (524 MB of temporaries at these shapes); what stays
    is ONE relaid key head's bank, 128 MiB: the scatter writes it
    rows-major, the program holds it positions-minor, so each layer
    copies it there and back (the dense program does too; 0.62 ms a
    layer in both programs of the cell, chip run, PR 35).  The latent
    bank goes into the kernel as it lies.  The served expert sum moves its
    rows by gathers alone: no scatter-add into the token rows (the
    parent's combine, 1.59 ms a layer at prefill, chip run, PR 35)."""
    import types

    from chipbench import weights_axk1
    from chipbench.common import HERE, load_json
    from torchgpipe_tpu.models.hf_interop import config_from_hf_latent_moe

    m = dict(load_json(HERE / "configs" / "axk1.json"), num_hidden_layers=2)
    hf = dict(m, n_routed_experts=weights_axk1.published(m, "n_routed_experts"))
    cfg, moe = config_from_hf_latent_moe(
        types.SimpleNamespace(**hf),
        held=(m["held_first"], m["n_routed_experts"]),
    )
    cfg = dataclasses.replace(cfg, dtype=BF16)
    monkeypatch.setattr(jax, "devices", lambda *a: [chip])
    where = SingleDeviceSharding(chip)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=where),
            tree,
        )

    params = jax.eval_shape(lambda: weights_axk1.make_flat(m, 0))
    cache = jax.eval_shape(
        lambda: generation.init_cache(cfg, L_SLOTS, MAX_LEN)
    )
    rows, g = (L_ROWS, CHUNK) if compact else (L_SLOTS, 1)
    ints = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=where
    )

    def step(params, cache, lengths, tokens, n_valid, slots):
        return generation.decode_slots(
            cfg, params, tokens, cache, lengths, n_valid, moe=moe,
            slots=slots if compact else None,
        )

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache), ints(L_SLOTS), ints(rows, g),
        ints(rows), ints(rows),
    ).compile()
    text = compiled.as_text()
    assert _calls(text, "latent_decode") and not _calls(text, "flash_decode")
    # The expert sum's grouped products are the Pallas kernel, not the
    # compiler's ragged-dot.
    assert _calls(text, "grouped_matmul") and not _calls(text, "ragged-dot")
    assert not _row_scatters(text, rows * g, cfg.dim)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.25 * L_RELAID_BYTES


def test_mpmd_stored_backward_compiles_for_v5e(chip, monkeypatch):
    """The MPMD engine's backward over STORED residuals (``except_last``
    keeps the last micro-batch's vjp; chip_smoke.py phase b, stage 1:
    one Mistral-7B block and the head at 4096): its dK/dV kernel in
    512-blocks missed the compiler's default 16 MiB of scoped VMEM by
    0.26 MiB in this program alone (chip run, PR 31), which is why the
    training kernels state their own limit."""
    import chip_smoke
    from torchgpipe_tpu import GPipe
    from torchgpipe_tpu.layers import sequential_init
    from torchgpipe_tpu.models.transformer import llama

    monkeypatch.setattr(jax, "devices", lambda *a: [chip])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    where = SingleDeviceSharding(chip)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=where),
            tree,
        )

    layers = llama(chip_smoke.mistral_config(2))
    params, state, _ = jax.eval_shape(
        lambda key: sequential_init(
            layers, key, jax.ShapeDtypeStruct((1, SEQ), jnp.int32)
        ),
        jax.random.PRNGKey(0),
    )
    model = GPipe(layers, [2, 2], devices=[chip], chunks=4,
                  checkpoint="except_last")
    stage = model._pipeline.stages[1]
    x = jax.ShapeDtypeStruct((1, SEQ, H * D), BF16)
    y, ext, _, pull = jax.eval_shape(
        lambda p, s, x: stage.fwd_vjp(p, s, x, {}, None, 1.0),
        list(params[2:]), list(state[2:]), x,
    )
    compiled = stage.bwd.lower(on_chip(pull), (on_chip(y), on_chip(ext)))
    assert "flash_bwd_dkv" in compiled.compile().as_text()


def test_mixed_attention_expert_train_step_compiles_for_v5e(chip, monkeypatch):
    """The ``mellum2.train-4x8192`` cell's whole train step (``llama_moe_spmd``
    through ``SpmdGPipe.make_train_step``, AdamW, 4 micro-batches of one
    8,192-token row, published widths, depth 8, 16 of 64 experts held):
    window and full flash calls side by side, the grouped expert products
    and their transposes, under the chip's 15.75 GiB.  Without the expert
    sum's own recomputation (``moe.py``) this program needs 19.4 GiB."""
    import optax

    from chipbench import weights_mellum2
    from chipbench.builders import spmd_train_moe
    from chipbench.common import HERE, load_json
    from torchgpipe_tpu.models.moe import llama_moe_spmd
    from torchgpipe_tpu.models.transformer import cross_entropy
    from torchgpipe_tpu.spmd import SpmdGPipe, make_mesh

    monkeypatch.setattr(jax, "devices", lambda *a: [chip])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    where = SingleDeviceSharding(chip)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=where),
            tree,
        )

    m = load_json(HERE / "configs" / "mellum2.json")
    tr = m["train"]
    cfg, moe = spmd_train_moe.program_config(m)
    block, pre, post = llama_moe_spmd(cfg, moe, 1)
    pipe = SpmdGPipe(block, 1, make_mesh(1, devices=[chip]), chunks=tr["chunks"],
                     loss_fn=cross_entropy, pre=pre, post=post)
    params = jax.eval_shape(
        lambda: weights_mellum2.stack_for_stages(weights_mellum2.make_flat(m, 0), 1)
    )
    opt = optax.adamw(**tr["optimizer"])
    step = pipe.make_train_step(opt)
    tokens = jax.ShapeDtypeStruct((tr["batch"], tr["seq"]), jnp.int32, sharding=where)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(jax.eval_shape(opt.init, params)), tokens, tokens,
    ).compile()
    text = compiled.as_text()
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "ragged-dot"):
        assert kernel in text
    assert compiled.memory_analysis().peak_memory_in_bytes < 15.75 * 2 ** 30


def _pool_program(workload, builder, weights, compact, chip, monkeypatch):
    """A hybrid serving cell's decode program (one token a slot) or compact
    prefill program (``prefill_rows_for`` rows of ``prefill_chunk``, the
    head at each row's sampled position, as the engine runs it), compiled
    for the described chip over its pool, donated, at published widths."""
    from chipbench.run import make_cell
    from torchgpipe_tpu.serving.engine import prefill_rows_for

    cell = make_cell(workload, 1, 44.0, False)
    m, sv = cell.config, cell.config["serve"]
    cfg, moe = builder.program_config(cell)
    monkeypatch.setattr(jax, "devices", lambda *a: [chip])
    where = SingleDeviceSharding(chip)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=where),
            tree,
        )

    slots, g = sv["num_slots"], sv["prefill_chunk"]
    params = jax.eval_shape(lambda: weights.make_flat(m, 0))
    cache = jax.eval_shape(
        lambda: generation.init_cache(cfg, slots, sv["max_len"]))
    rows, g = (prefill_rows_for(slots), g) if compact else (slots, 1)
    ints = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=where
    )

    def step(params, cache, lengths, tokens, n_valid, slots):
        last = jnp.clip(n_valid - 1, 0, g - 1)
        return generation.decode_slots(
            cfg, params, tokens, cache, lengths, n_valid, moe=moe,
            slots=slots if compact else None, expert_counts=True,
            logits_at=last if compact else None,
        )

    return jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache), ints(slots), ints(rows, g),
        ints(rows), ints(rows),
    ).compile()


@pytest.mark.parametrize("compact", [False, True], ids=["decode", "prefill"])
def test_hybrid_state_pool_programs_fit_the_v5e(compact, chip, monkeypatch):
    """``nemotron3-nano.serve-deep-backlog``'s two programs at published
    widths (EMEMEMEM*, 64 of 128 experts held, 65,536 rows of vocabulary)
    over its pool of 512 slots x 4096, donated: four mixer layers' float32
    states (4 GiB) beside the attention layer's rows (2 GiB).  The decode
    program (one token a slot) and the compact prefill program (102 rows
    of 64, the head at each row's sampled position, as the engine runs
    it) take the decode kernel, and each peaks under the chip's 15.75 GiB:
    12.3 and 13.1 GiB of 5.90 GiB of weights, 6.07 of pool and their
    temporaries (printed; the decode program's read 12.8 while the up
    bank was relaid)."""
    from chipbench import weights_nemotron
    from chipbench.builders import engine_nemotron

    compiled = _pool_program("nemotron3-nano.serve-deep-backlog",
                             engine_nemotron, weights_nemotron, compact, chip,
                             monkeypatch)
    text = compiled.as_text()
    assert "flash_decode" in text
    # The grouped products are the Pallas kernel, and the up bank, which
    # the chip stores with its 2,688 minor, goes in as it lies: no relaid
    # copy of it (the compiler's grouped product took one a layer and
    # program, 2 ms each on a v5e).
    assert _calls(text, "grouped_matmul") and not _calls(text, "ragged-dot")
    assert not re.search(r"= bf16\[64,2688,1856\]\S* copy\(", text)
    peak = compiled.memory_analysis().peak_memory_in_bytes
    print(f"peak {peak / 2 ** 30:.3f} GiB")
    assert peak < 15.75 * 2 ** 30


@pytest.mark.parametrize("compact", [False, True], ids=["decode", "prefill"])
def test_delta_rule_pool_programs_fit_the_v5e(compact, chip, monkeypatch):
    """``solar-open2.serve-deep-backlog``'s two programs at published widths
    (*E KE KE KE, 40 of 320 experts held, 24,576 rows of vocabulary) over
    its pool of 256 slots x 4096, donated: three delta-rule layers' float32
    states (3 GiB) and tails beside the full layer's rows (4 GiB).  The
    decode program and the compact prefill program (51 rows of 64) take
    the decode kernel and the grouped-product kernel, and each peaks under
    15 GiB, the most the configuration lets its programs take (13.48 and
    14.80 GiB of 6.16 GiB of weights, 7.11 of pool and their temporaries;
    at chunks of 128 the prefill program needs 16.12 GiB and is refused)."""
    from chipbench import weights_solar
    from chipbench.builders import engine_solar

    compiled = _pool_program("solar-open2.serve-deep-backlog", engine_solar,
                             weights_solar, compact, chip, monkeypatch)
    text = compiled.as_text()
    assert _calls(text, "flash_decode") and _calls(text, "grouped_matmul")
    assert not _calls(text, "ragged-dot")
    peak = compiled.memory_analysis().peak_memory_in_bytes
    print(f"peak {peak / 2 ** 30:.3f} GiB")
    assert peak < 15 * 2 ** 30
