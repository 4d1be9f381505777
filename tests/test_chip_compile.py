"""Compile-time guards that cost no chip time (ISSUE 26).

1. The flash-attention forward kernel at the shapes the benchmark's cells
   run it at compiles for a DESCRIBED v5e (the TPU's compiler is installed
   here; nothing is attached, nothing runs), and the compiled text holds the
   Mosaic call and the kernel's own name: a device trace names the event
   after that instruction, and the benchmark's readers key on it.
2. On the CPU backend the lowered text of a tiny train step, decode tick and
   admission program holds every ``jax.named_scope`` the device-time-by-scope
   readers will key on.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU's library, every xdist worker imports
every test file, and a fixture keeps the load to the worker that runs this
file. All such tests live in THIS file for the same reason.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

TRAIN_SCOPES = ("embed", "block.attn", "block.mlp", "head_loss",
                "grad_accum", "adam")
TICK_SCOPES = ("tick.scatter", "tick.gather_kv", "tick.attend",
               "tick.sample")
ADMIT_SCOPES = ("admit.prefill", "admit.scatter", "block.attn",
                "block.mlp")


def _has_scope(text: str, name: str) -> bool:
    """`name` as one element of an operation's name stack: plain
    (`jit(step)/adam/mul`) or under a transform (`jvp(embed)/gather`,
    `transpose(jvp(head_loss))/dot_general`)."""
    return re.search(r'[/"(]' + re.escape(name) + r"[)/]", text) is not None


# ---------------------------------------------------------------------------
# 1. the kernel, for a described chip
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here, no test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# batch/accum x heads of the cells: 590m 1 x 12, 1.3b-stage8 1 x 16
@pytest.mark.parametrize("rows", [12, 16])
def test_flash_forward_compiles_for_v5e_under_its_name(one_chip, rows,
                                                       no_compile_cache):
    from deeplearning4j_tpu.ops import pallas_attention as pa

    assert pa.flash_fits(2048, 128)
    x = jax.ShapeDtypeStruct((rows, 2048, 128), jnp.bfloat16,
                             sharding=one_chip)
    # the test session runs with 64-bit types on (the gradient checks);
    # the chip does not, and Mosaic lowers no 64-bit index arithmetic
    with jax.enable_x64(False):
        text = jax.jit(
            lambda q, k, v: pa._flash_raw(q, k, v, causal=True,
                                          interpret=False)
        ).lower(x, x, x).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    # the instruction is named after the kernel, and so is the device event
    assert re.match(r"\s*(ROOT )?%flash_fwd(\.\d+)? = ", calls[0]), calls[0]
    assert f"bf16[{rows},2048,128]" in calls[0]
    assert "flash_fwd/pallas_call" in calls[0]


def _compile_serve_program(one_chip, heads, n_layers, program="tick"):
    """The serve cell's tick, or its admission at width 512, compiled for
    the described chip at the cell's shape but for the depth: 40 lanes,
    1,280 blocks of 16, bf16 arena, heads of 128 (590m 12 of them, 1.3b
    16). Returns (compiled, the arena's shapes, lanes, block_tokens)."""
    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )
    from deeplearning4j_tpu.serving import paged

    cfg = TransformerConfig(vocab_size=1024, d_model=128 * heads,
                            n_layers=n_layers, n_heads=heads,
                            d_ff=512 * heads, max_len=2048,
                            dtype_policy="performance")
    lanes, n_blocks, bt, width = 40, 1280, 16, 512
    on = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(on, jax.eval_shape(lambda: init_params(cfg)))
    arena = jax.tree.map(on, _arena(cfg, n_blocks, bt))
    arg = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    m = cfg.max_len // bt
    with jax.enable_x64(False):
        if program == "tick":
            compiled = paged._paged_tick_for(cfg, bt).lower(
                params, arena, arg((lanes,), jnp.int32),
                arg((lanes,), jnp.int32), arg((lanes, m), jnp.int32),
                arg((lanes, 2), jnp.uint32), arg((lanes,), jnp.float32)
            ).compile()
            paged._PAGED_TICK_CACHE.pop((cfg, bt, "gather", 1), None)
        else:
            compiled = paged._paged_admit_for(cfg, width, bt).lower(
                params, arena, arg((1, width), jnp.int32),
                arg((m,), jnp.int32)).compile()
            paged._PAGED_ADMIT_CACHE.pop((cfg, width, bt), None)
    return compiled, arena, lanes, bt


def _outside_fusions(text):
    """The instructions a compiled program runs one by one: every line of
    every computation but the fused ones, whose instructions are one
    operation's insides and yield no buffer of their own."""
    keep, out = True, []
    for ln in text.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", ln)
        if m:
            keep = not m.group(1).startswith("fused_computation")
        elif keep:
            out.append(ln)
    return out


@pytest.mark.parametrize("heads", [12, 16])
def test_tick_attention_reads_the_arena_as_stored_on_v5e(one_chip, heads,
                                                         no_compile_cache):
    """The serve cell's tick, two layers of it: the chip's compiler gives
    the attention products to the matrix unit, makes no float32 copy of a
    gathered chunk and no copy of it in another order. A one-row product
    it rewrites as multiply-and-reduce over a float32 copy, which was 38%
    of the tick (PERF.md section 6, PR 27): paged._exact_rows is what
    keeps it from that. A product batched over the head it feeds from a
    transposed copy of the chunk, ``bf16[40,128,12,128]``, which was 35%
    of the device's time (PERF.md section 6, PR 35): a product a head over
    its own columns of the chunk as gathered is what keeps it from that.
    This is what watches both."""
    from deeplearning4j_tpu.serving import paged

    compiled, _, lanes, bt = _compile_serve_program(one_chip, heads, 2)
    text = compiled.as_text()
    cols = paged.ATTN_CHUNK_COLS
    chunk = lanes * cols * bt * heads * 128
    gathered = f"bf16[{lanes * cols},{bt},{heads * 128}]"
    assert gathered in text                                   # the gathers
    sizes = {int(np.prod([int(d) for d in dims.split(",")]))
             for dims in re.findall(r"f32\[([\d,]+)\]", text)}
    assert chunk not in sizes and chunk // 2 not in sizes
    products = [ln for ln in text.splitlines()
                if " convolution(" in ln and "tick.attend" in ln]
    assert len(products) == 2 * heads, products
    # whatever yields a buffer of a chunk's size, or of one head's columns
    # of it, outside a fusion: the two gathers in the shape they gather to,
    # and views of them (a bitcast moves nothing). No copy, transpose or
    # re-laying reshape, of the whole chunk or head by head
    made = []
    for ln in _outside_fusions(text):
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (bf16\[([\d,]+)\])\S* "
                     r"([a-z][\w\-]*)\(", ln)
        if m and (int(np.prod([int(d) for d in m.group(2).split(",")]))
                  == chunk or m.group(2) in (f"{lanes},{cols * bt},128",
                                             f"{lanes * cols},{bt},128")):
            made.append((m.group(3), m.group(1)))
    assert {op for op, _ in made} <= {"fusion", "bitcast",
                                      "get-tuple-element"}, made
    assert {shape for op, shape in made if op == "fusion"} == {gathered}, made
    assert f"bf16[{lanes},{cols * bt},{heads},128]" not in text


def _makes_arena_sized(text, arena):
    """Instructions of a compiled program, in any computation (ENTRY, a
    loop's body, a fusion's), that YIELD a buffer as large as the whole
    arena leaf ``arena`` [L, rows, bt, width] or as one layer of it by
    copying, re-laying or slicing: (opcode, shape) pairs. A fusion is
    named after what it holds (``copy_bitcast_fusion``,
    ``copy_dynamic-update-slice_fusion``, ``constant_dynamic-slice_fusion``
    are what the parent's tick spent half its time in) and the in-place
    scatter of a tick's 40 rows is a fusion too, so a fusion counts by
    what is inside it: its own computation is read like any other."""
    dtype = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(arena.dtype).name]
    whole = int(np.prod(arena.shape))
    sizes = {whole, whole // arena.shape[0]}
    found = []
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\w+)\[([\d,]+)\]\S* "
                     r"([a-z][\w\-]*)\(", ln)
        if not m or m.group(1) != dtype or m.group(3) not in (
                "copy", "transpose", "dynamic-slice", "slice",
                "dynamic-update-slice"):
            continue
        if int(np.prod([int(d) for d in m.group(2).split(",")])) in sizes:
            found.append((m.group(3), m.group(2)))
    return found


@pytest.mark.parametrize("program", ["tick", "admit"])
@pytest.mark.parametrize("heads", [12, 16])
def test_serve_programs_write_into_the_arena_where_it_lies_on_v5e(
        one_chip, heads, program, no_compile_cache):
    """The serve cell's tick and one admission width, three scanned layers:
    the arena comes in donated and leaves aliased, and NO instruction of
    the compiled program, in the loop's body as little as in ENTRY, copies,
    transposes or slices a buffer of the arena's size or of one layer's.
    As ``[L, blocks, 16, heads, 128]`` scanned over as xs/ys the tick
    sliced each layer's K and V out of the stack, re-laid them, wrote them
    back and re-laid the whole stack once a tick: seven of its ten longest
    device operations, over half of the device's time (ROADMAP S15,
    PERF.md section 6, PR 29)."""
    compiled, arena, _, _ = _compile_serve_program(one_chip, heads, 3,
                                                   program)
    assert not _makes_arena_sized(compiled.as_text(), arena["k"])
    arena_bytes = sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
                      for a in jax.tree.leaves(arena))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= arena_bytes
    assert mem.temp_size_in_bytes < arena_bytes


def test_hybrid_tick_rewrites_its_state_in_place_on_v5e(one_chip,
                                                        no_compile_cache):
    """The hybrid serve cell's tick at the published widths (d 2048, 64
    Mamba heads of 64 over a state of 128, 32 query heads over 8 KV heads),
    three layers of its pattern, 64 lanes: every layer's state buffer is an
    argument that the program aliases to its output (donated, rewritten in
    place), the update is ONE fusion that reads S once and gives S and y,
    and nothing at the top level copies a buffer of the state's shape or
    of the KV arena's (the arena's fault, ROADMAP S15, not repeated for
    4.8 GB of state nor for these K and V). The
    scopes the trace readers look for are in the program."""
    from deeplearning4j_tpu.models import hybrid
    from deeplearning4j_tpu.ops import memory as opsmem
    from deeplearning4j_tpu.serving import paged

    cfg = hybrid.HybridConfig(
        vocab_size=4096, d_model=2048, n_heads=32, n_kv_heads=8, d_ff=8192,
        layer_types=("mamba", "attention", "mamba"), max_len=2048,
        ssm_heads=64, ssm_head_dim=64, ssm_state=128)
    lanes, n_blocks, bt = 64, 512, 16
    arg = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    params = jax.tree.map(lambda s: arg(s, jnp.bfloat16),
                          hybrid.param_shapes(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    needs = opsmem.cache_needs(cfg)
    kv = (n_blocks + 1, bt, needs.kv_heads * needs.head_dim)
    arena = {"k": (arg(kv, jnp.bfloat16),), "v": (arg(kv, jnp.bfloat16),)}
    for leaf in needs.state:
        arena[leaf.name] = tuple(arg((lanes,) + leaf.shape, leaf.dtype)
                                 for _ in range(leaf.layers))
    with jax.enable_x64(False):
        compiled = paged._paged_tick_for(cfg, bt).lower(
            params, arena, arg((lanes,), jnp.int32), arg((lanes,), jnp.int32),
            arg((lanes, cfg.max_len // bt), jnp.int32),
            arg((lanes, 2), jnp.uint32), arg((lanes,), jnp.float32)
        ).compile()
    paged._PAGED_TICK_CACHE.pop((cfg, bt, "gather", 1), None)
    text = compiled.as_text()
    state = f"f32[{lanes},64,64,128]"
    entry = text[text.index("\nENTRY "):]
    made = [m for m in (re.match(
        r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(", ln)
        for ln in entry.splitlines()) if m and state in m.group(1)]
    # the two state buffers come in as parameters, each leaves through one
    # fusion that also gives y [lanes, 64, 64], and nothing else of that
    # shape is made at the top level: no copy, no transpose
    kinds = sorted(m.group(2) for m in made)
    assert kinds.count("parameter") == 2, kinds
    assert kinds.count("fusion") == 2, kinds
    assert not [k for k in kinds if k not in
                ("parameter", "fusion", "get-tuple-element", "bitcast",
                 "tuple")], kinds
    fused = [m.group(1) for m in made if m.group(2) == "fusion"]
    assert all(f"f32[{lanes},64,64]" in t for t in fused), fused
    # donated and aliased: the whole arena pytree, byte for byte
    donated = sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
                  for a in jax.tree.leaves(arena))
    # (at least: the chip pads a tiled buffer, here the conv tails)
    assert compiled.memory_analysis().alias_size_in_bytes >= donated
    # nor a buffer of the KV arena's: a token's heads lie side by side in
    # one row of 512, which the chip stores as the scatter and the gather
    # want it (as [.., 8, 64] it re-laid each buffer twice a tick)
    assert not re.findall(rf" = bf16\[{n_blocks + 1},{bt},[\d,]+\]\S* "
                          r"(?:copy|transpose)\(", entry)
    for scope in ("tick.ssm_step", "tick.ssm_conv", "tick.scatter",
                  "tick.gather_kv", "tick.attend", "tick.sample"):
        assert scope in text, scope


def _smallthinker_period(one_chip):
    """One period (global, window, window, window) of the routed-expert
    serve cell at its published widths (d 2560, 28 heads of 128 over 4 KV
    heads, 64 experts of 768, window 4,096), 48 lanes at a context of
    8,192: (cfg, params, arena, arg) as shapes on the described chip."""
    from deeplearning4j_tpu.models import hybrid
    from deeplearning4j_tpu.ops import memory as opsmem

    cfg = hybrid.HybridConfig(
        vocab_size=4096, d_model=2560, n_heads=28, n_kv_heads=4,
        attn_head_dim=128, d_ff=768, layer_types=("attention",) * 4,
        max_len=8192, rope=(False, True, True, True), rope_theta=1.5e6,
        window=(0, 4096, 4096, 4096), attn_exact=False, ffn="experts",
        ffn_act="relu", moe_experts=64, moe_top_k=6, tie_head=False,
        embedding_multiplier=1.0, residual_multiplier=1.0,
        attention_multiplier=128 ** -0.5, logits_scaling=1.0, rms_eps=1e-6)
    lanes, n_blocks, bt = 48, 1024, 16
    arg = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    params = jax.tree.map(
        lambda s: arg(s, jnp.bfloat16), hybrid.param_shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], int))
    needs = opsmem.cache_needs(cfg)
    blocks = opsmem.kv_group_blocks(needs, n_blocks, bt, lanes)
    rows = {j: n + 1 for g, n in zip(needs.groups, blocks)
            for j in g.layer_ids}
    arena = {name: tuple(arg((rows[j], bt, 512), jnp.bfloat16)
                         for j in range(4)) for name in ("k", "v")}
    return cfg, params, arena, arg


def _smallthinker_admit(one_chip, width):
    """The period's admission at `width`, compiled for the described chip."""
    from deeplearning4j_tpu.serving import paged

    cfg, params, arena, arg = _smallthinker_period(one_chip)
    bt, m = 16, cfg.max_len // 16
    with jax.enable_x64(False):
        admit = paged._paged_admit_for(cfg, width, bt).lower(
            params, arena, arg((1, width), jnp.int32), arg((2, m), jnp.int32),
            arg((2,), jnp.int32)).compile()
    paged._PAGED_ADMIT_CACHE.pop((cfg, width, bt), None)
    return admit


def _smallthinker_programs(one_chip, width):
    """The period's compiled tick and its compiled admission at `width`."""
    from deeplearning4j_tpu.serving import paged

    cfg, params, arena, arg = _smallthinker_period(one_chip)
    lanes, bt = 48, 16
    with jax.enable_x64(False):
        tick = paged._paged_tick_for(cfg, bt).lower(
            params, arena, arg((lanes,), jnp.int32), arg((lanes,), jnp.int32),
            arg((2, lanes, cfg.max_len // bt), jnp.int32),
            arg((lanes, 2), jnp.uint32), arg((lanes,), jnp.float32)).compile()
    paged._PAGED_TICK_CACHE.pop((cfg, bt, "gather", 1), None)
    return tick, _smallthinker_admit(one_chip, width), arena


def test_smallthinker_programs_read_the_experts_in_place_on_v5e(
        one_chip, no_compile_cache):
    """The tick and an admission of 2,048 positions compile for a v5e; no
    program copies, slices or transposes a buffer of an expert matrix's
    shape (a layer's 64 experts are one buffer of their own: sliced out of
    a stack they were copied for the grouped kernel, 0.75 GB a layer), nor
    one of the arena's; the arena is donated and aliased whole; the tick
    takes the batched product (no grouped kernel), the admission the
    grouped one; the admission's attention goes by query blocks (nothing of
    [28, 2048, 2048] scores) and a window layer's keys by the band."""
    tick, admit, arena = _smallthinker_programs(one_chip, 2048)
    donated = sum(int(np.prod(a.shape)) * 2 for a in jax.tree.leaves(arena))
    for name, compiled in (("tick", tick), ("admit", admit)):
        text = compiled.as_text()
        entry = text[text.index("\nENTRY "):]
        assert not re.findall(r" = bf16\[64,(?:2560,1536|768,2560)\]\S* "
                              r"(?:copy|transpose|slice|dynamic-slice)\(",
                              entry), name
        assert not re.findall(r" = bf16\[1025,16,512\]\S* "
                              r"(?:copy|transpose)\(", entry), name
        assert compiled.memory_analysis().alias_size_in_bytes >= donated
    grouped = 'custom_call_target="tpu_custom_call"'
    assert grouped not in tick.as_text()
    assert grouped in admit.as_text()
    # 2,048 rows x 6 pairs through the grouped kernel, the window's whole
    # band inside the sequence: one block of query rows, keys 0 .. 2,047
    assert "12288,2560]" in admit.as_text()
    assert admit.memory_analysis().temp_size_in_bytes < 1.2e9
    assert tick.memory_analysis().temp_size_in_bytes < 0.3e9


def test_smallthinker_admission_attends_in_the_kernel_on_v5e(
        one_chip, no_compile_cache, monkeypatch):
    """The period admitted at 8,192 with Pallas on (the chip's default):
    its global layer and the window layers each call the attention kernel
    under its own name, as a Mosaic call (the last layer's attention feeds
    nothing the admission keeps, so the compiler drops it with Pallas on or
    off: 1 + 2 calls); no float32 buffer of the plain path's pass of scores
    ([4 KV heads, 7 x 2,048 rows, 2,048 keys], hybrid._attend_tiles)
    remains; the temporaries fall below the plain path's, 1,318,196,224
    against 1,334,432,768 bytes (the grouped expert product's 49,152 rows
    set the peak either way)."""
    monkeypatch.setenv("DL4J_TPU_PALLAS", "force")
    kernel = _smallthinker_admit(one_chip, 8192)
    monkeypatch.setenv("DL4J_TPU_PALLAS", "0")
    plain = _smallthinker_admit(one_chip, 8192)
    text, plain_text = kernel.as_text(), plain.as_text()
    called = sorted(m.group(1) for m in (
        re.match(r"\s*(?:ROOT )?%(prefill_attn(?:_w\d+)?)(?:\.\d+)? = ", ln)
        for ln in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in ln) if m)
    assert called == ["prefill_attn"] + ["prefill_attn_w4096"] * 2, called
    assert "prefill_attn" not in plain_text
    scores = "f32[4,14336,2048]"
    assert scores in plain_text and scores not in text
    temp = kernel.memory_analysis().temp_size_in_bytes
    plain_temp = plain.memory_analysis().temp_size_in_bytes
    assert temp < plain_temp < 1.4e9, (temp, plain_temp)


# granite's admission at its widest bucket (512: prompts to 448), lowered
# with Pallas on: its products are float32 (``attn_exact``), so it keeps the
# plain path, and the text is the parent's of PR 38 to the byte. A later
# change to that program updates the digest, saying why.
GRANITE_ADMIT_512 = \
    "e4ca69d3b6e2e6355639b7cfa8883317fc7d86e3524ca53e71d125fa7b76e251"


def test_granite_admission_lowers_as_before(monkeypatch):
    import hashlib

    from deeplearning4j_tpu.models import hybrid
    from deeplearning4j_tpu.ops import memory as opsmem
    from deeplearning4j_tpu.serving import paged
    from perfbench import harness

    monkeypatch.setenv("DL4J_TPU_PALLAS", "force")
    conf = harness.load_json(os.path.join(
        harness.HERE, "configs", "granite-4.0-h-micro.json"))
    cfg = hybrid.HybridConfig.from_published(conf, max_len=2048)
    assert cfg.attn_exact and cfg.admit_attend(512) == "xla"
    lanes, blocks, bt, width = 64, 4096, 16, 512
    sd = jax.ShapeDtypeStruct
    params = jax.tree.map(
        lambda s: sd(s, jnp.bfloat16), hybrid.param_shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], int))
    needs = opsmem.cache_needs(cfg)
    kv = sd((blocks + 1, bt, needs.kv_heads * needs.head_dim), jnp.bfloat16)
    arena = {"k": (kv,) * cfg.n_attention, "v": (kv,) * cfg.n_attention}
    for leaf in needs.state:
        arena[leaf.name] = tuple(sd((lanes,) + leaf.shape, leaf.dtype)
                                 for _ in range(leaf.layers))
    i32 = lambda *shape: sd(shape, jnp.int32)
    with jax.enable_x64(False):
        text = paged._paged_admit_for(cfg, width, bt).lower(
            params, arena, i32(1, width), i32(cfg.max_len // bt),
            i32(2)).as_text()
    paged._PAGED_ADMIT_CACHE.pop((cfg, width, bt), None)
    assert "tpu_custom_call" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == GRANITE_ADMIT_512


# ---------------------------------------------------------------------------
# 2. the scopes, in the lowered programs
# ---------------------------------------------------------------------------


def _tiny_cfg(**over):
    from deeplearning4j_tpu.models.transformer import TransformerConfig

    kw = dict(vocab_size=29, d_model=16, n_layers=2, n_heads=2, d_ff=32,
              max_len=32, use_flash=False)
    kw.update(over)
    return TransformerConfig(**kw)


def _shapes(cfg):
    from deeplearning4j_tpu.models.transformer import (
        init_opt_state,
        init_params,
    )

    params = jax.eval_shape(lambda: init_params(cfg))
    return params, jax.eval_shape(init_opt_state, params)


def test_train_step_holds_the_scope_names():
    from deeplearning4j_tpu.models.transformer import make_train_step

    cfg = _tiny_cfg(accum_steps=2, remat="dots")
    params, opt = _shapes(cfg)
    tok = jax.ShapeDtypeStruct((4, 16), jnp.int32)
    text = make_train_step(cfg).lower(params, opt, tok, tok).as_text(
        debug_info=True)
    missing = [s for s in TRAIN_SCOPES if not _has_scope(text, s)]
    assert not missing, missing


def _arena(cfg, n_blocks, bt):
    leaf = jax.ShapeDtypeStruct(
        (cfg.n_layers, n_blocks + 1, bt, cfg.d_model), cfg.compute_dtype)
    return {"k": leaf, "v": leaf}


def test_tick_and_admit_programs_hold_the_scope_names():
    from deeplearning4j_tpu.serving import paged

    cfg, bt, lanes = _tiny_cfg(), 8, 3
    params, _ = _shapes(cfg)
    arena = _arena(cfg, 6, bt)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    m = cfg.max_len // bt
    tick = paged._paged_tick_for(cfg, bt).lower(
        params, arena, i32(lanes), i32(lanes), i32(lanes, m),
        jax.ShapeDtypeStruct((lanes, 2), jnp.uint32),
        jax.ShapeDtypeStruct((lanes,), jnp.float32)).as_text(debug_info=True)
    missing = [s for s in TICK_SCOPES if not _has_scope(tick, s)]
    assert not missing, missing
    admit = paged._paged_admit_for(cfg, 16, bt).lower(
        params, arena, i32(1, 16), i32(m)).as_text(debug_info=True)
    missing = [s for s in ADMIT_SCOPES if not _has_scope(admit, s)]
    assert not missing, missing


def test_smallthinker_tick_and_admit_hold_the_five_new_scopes():
    """A tiny model of routed experts and window layers: the tick's and the
    admission's lowered text holds the scopes of the expert layer and of
    the admission's attention, beside the ones every tick has."""
    from deeplearning4j_tpu.models import hybrid
    from deeplearning4j_tpu.serving import paged

    cfg = hybrid.HybridConfig(
        vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, attn_head_dim=16,
        d_ff=16, layer_types=("attention",) * 2, max_len=32,
        rope=(False, True), window=(0, 8), ffn="experts", ffn_act="relu",
        moe_experts=4, moe_top_k=2, tie_head=False, dtype_policy="strict")
    bt, lanes, m = 4, 3, 8
    params = jax.eval_shape(
        lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0)))
    layer = lambda n: jax.ShapeDtypeStruct((n + 1, bt, 32), jnp.float32)
    arena = {"k": (layer(16), layer(9)), "v": (layer(16), layer(9))}
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    tick = paged._paged_tick_for(cfg, bt).lower(
        params, arena, i32(lanes), i32(lanes), i32(2, lanes, m),
        jax.ShapeDtypeStruct((lanes, 2), jnp.uint32),
        jax.ShapeDtypeStruct((lanes,), jnp.float32)).as_text(debug_info=True)
    for scope in TICK_SCOPES + ("tick.moe_route", "tick.moe_experts"):
        assert _has_scope(tick, scope), scope
    admit = paged._paged_admit_for(cfg, 16, bt).lower(
        params, arena, i32(1, 16), i32(2, m), i32(2)).as_text(
        debug_info=True)
    for scope in ("admit.prefill", "admit.scatter", "admit.attend",
                  "admit.moe_route", "admit.moe_experts"):
        assert _has_scope(admit, scope), scope
    paged._PAGED_TICK_CACHE.pop((cfg, bt, "gather", 1), None)
    paged._PAGED_ADMIT_CACHE.pop((cfg, 16, bt), None)


@pytest.mark.parametrize("which", ["flash_bwd", "flash_ext_bwd"])
def test_flash_backward_rules_are_scoped(which):
    """The backward is plain XLA: the scope is all that tells its fusions
    from the rest of the step. Lowered through the interpreter, as the CPU
    has no Mosaic."""
    from deeplearning4j_tpu.ops import pallas_attention as pa

    q = jax.ShapeDtypeStruct((2, 128, 64), jnp.float32)
    if which == "flash_bwd":
        loss = lambda q, k, v: pa._flash(q, k, v, True, True).sum()
        fwd = "flash_fwd"
    else:
        loss = lambda q, k, v: pa.flash_attention_block(
            q, k, v, offset=jnp.int32(0), interpret=True)[0].sum()
        fwd = "flash_ext_fwd"
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).as_text(
        debug_info=True)
    assert _has_scope(text, which)
    assert _has_scope(text, fwd)
