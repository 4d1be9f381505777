"""Paged-KV continuous decode: a block-pool /generate plane.

The fixed slot pool (serving/decode.ContinuousDecoder) allocates each
request a CONTIGUOUS ``cfg.max_len`` KV stripe, so concurrency quantizes
to ``DL4J_TPU_SERVE_SLOTS`` no matter how short the requests actually
are — the serving-side twin of the dense-batch over-allocation SURVEY
§3.1 charges the reference's one-record route with. PagedAttention
(Kwon et al., vLLM) fixes it with virtual memory's oldest trick: one
device-resident BLOCK ARENA of fixed-size KV blocks, per-request block
TABLES mapping logical token positions to physical blocks, admission
gated by the free-block count, and eviction returning blocks to the
free list. Iteration-level scheduling (Yu et al., Orca) stays exactly
as the fixed pool had it: the device program is a fixed-shape
single-token tick (zero retrace after the first tick), and ALL paging —
allocation, preemption, prefix sharing — is host-side bookkeeping
between ticks.

Layout and invariants:

  * arena k/v: ``[L, n_blocks+1, block_tokens, H*hd]``, a token's heads
    side by side in one row (the order the chip stores is then the one
    the scatter and the gathers want: no program copies or re-lays the
    arena, tests/test_chip_compile.py). Physical block 0 OF EVERY LAYER
    is a TRASH block that is never allocated — inactive lanes and the
    unallocated tail of every table point at it, so the tick's scatter
    always has somewhere harmless to write and the gather somewhere
    harmless to read (the ``arange <= pos`` mask zeroes its softmax
    weight exactly, the same argument decode.py makes for garbage pad
    K/V). The tick carries the arena through its layer scan as
    ``[L * (n_blocks+1), block_tokens, H*hd]`` and layer ``l`` reaches
    its blocks at rows ``l * (n_blocks+1) + block``.
  * the tick reads each lane's blocks through its table a chunk of
    whole blocks at a time (``arena[tables[:, chunk]]``, in the arena's
    dtype) and folds each chunk into an online softmax, looping only as
    far as the longest LIVE lane reaches (chunked_attention; the trip
    count is data, so there is one program). No ``[S, max_len, H, hd]``
    view exists. The heads' two products read the gathered chunk as it
    lies, ``[S, chunk, H*hd]``: a head is a column range of the row, and
    its product takes that range where it is. A product batched over
    the head would make the chip's compiler transpose every gathered
    chunk first (it wants a batch axis outermost), as the arena itself
    was once re-laid for the scatter. Per-lane outputs are functions of
    the gathered VALUES, not the physical block ids, and a chunk wholly
    past a lane's position is an exact no-op on its running softmax,
    which is why a request's tokens are byte-invariant to allocation
    history and pool co-residents (tests/test_serving_paged,
    tests/test_paged_chunked).
    The masked-attention math is decode_step_slots' up to the order of
    the float32 softmax sums.
  * prefix cache: full prompt blocks strictly BELOW a request's first
    write position are content-addressed (chained sha256 over the
    re-based token window) and refcounted; a hit points the new
    request's read table at the shared physical blocks. The divergence
    block — the one containing the re-consumed last prompt token, which
    the first tick overwrites — is always PRIVATE: admission prefill
    recomputes it into a fresh block (copy-on-write by recompute, one
    code path, byte-identical to the cold path by construction), and
    shared blocks are never written after their creating prefill.
  * admission prefill reuses the cold path's full-window program
    (models/transformer.prefill_cache at the bucket-ladder width) and
    scatters ONLY private blocks (shared + beyond-prompt table entries
    are redirected to trash in the write table), so a cache hit saves
    HBM, not byte-determinism.
  * on block exhaustion the YOUNGEST active request is preempted: its
    blocks return to the free list and it is re-queued at the front of
    its SLO class with prompt := window + generated-so-far and its live
    PRNG key saved, so the resumed sample stream continues exactly
    where it stopped.
  * a tick's tokens are booked as soon as they are read back, and
    handed to their clients (streaming callbacks, then the futures of
    finished lanes: _deliver) once the NEXT program is on the device,
    a prefill or the tick, or at once where none follows: the
    streaming threads a delivery wakes then run under the device's
    time and not between two ticks. A lane's sampling key is made on
    the host (seed_key), so an admission asks the device nothing.

SLO classes (serving/slo.py) generalize the FIFO queue: admission is
highest-class-first, per-class default deadlines feed the existing 504
path, and queue overflow sheds the youngest request of the lowest class
(counted per class in ``serving_stats.shed_by_class``).

Dense single-device models only, same gate as ContinuousDecoder. The
fixed-slot pool remains the ``DL4J_TPU_SERVE_KV_BLOCK=0`` fallback.

What is cached is the model's to say (ops/memory.cache_needs): how many
layers hold keys and values, with how many KV heads of what size (the
arena pages exactly those: ``[kv_layers, n_blocks+1, bt, kv_heads * hd]``
where the layers are alike and scanned, or one
``[n_blocks+1, bt, kv_heads * hd]`` a layer where they differ and are
unrolled),
and which per-lane recurrent state it keeps besides. The GPT-2-shaped
TransformerLM is the instance with K and V in every layer for every head
and no state; its tick and admit bodies are the ones in this file. A
model with recurrent layers (models/hybrid.py) brings its own two bodies
(decode_body, _paged_admit_for) and a state pool indexed by LANE: one
``[lanes, *shape]`` buffer a layer and leaf, held in the arena pytree
beside ``k`` and ``v``, so it is donated through every tick and
admission and rewritten in place, zeroed with the arena, and overwritten
whole when a lane is admitted (a released lane's state is dropped by
never being read again). Such a model takes no prefix hit (blocks can
be shared, state cannot be rebuilt from them), is preempted and
requeued by recomputing from the window like any other, and is refused
by the paths that cannot carry its state: scanned ticks, speculation,
the serving mesh, the prefill/decode handoff, the fixed-slot pool.

A paged cache a layer kind (``CacheNeeds.groups``): layers that page alike
are a KV group with a block pool (BlockArena) and a per-lane table of its
own, every group's buffers in the one arena pytree. A model of one kind of
layer has one group and is served as it was. A group with a ``window`` (the
window layers of models/hybrid.py beside its global ones) holds for a lane
only the blocks its window reaches: admission gives it blocks from the one
position ``keep - window`` lies in, the tick's booking returns the blocks
that lie wholly behind ``pos - window`` to the group's pool and points their
table entries at trash (``_trim``), so a lane never holds more than
``window / block_tokens + 2`` of them; its pool is derived from the stated
``n_blocks`` (ops/memory.kv_group_blocks), and its layers attend through
``chunked_attention(..., lo=...)``. Such a model takes no prefix hit and
counts no lookup (a block shared with another lane cannot be let go as one
lane's window passes it), is preempted and prefilled again as any other,
and is refused where state is refused. A model with routed experts is
served if its expert layer is dropless (a row's output its own, whoever
shares the tick: parallel/expert_parallel.dropless_experts); the
capacity-routed layer is refused.
"""

from __future__ import annotations

import hashlib
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    _ln,
    prefill_cache,
)
from deeplearning4j_tpu.obs import trace as obs_trace
from deeplearning4j_tpu.obs.registry import register_net
from deeplearning4j_tpu.ops import dispatch
from deeplearning4j_tpu.ops import env as envknob
from deeplearning4j_tpu.ops import lowprec
from deeplearning4j_tpu.ops import memory as opsmem
from deeplearning4j_tpu.ops import pallas_paged
from deeplearning4j_tpu.serving.batcher import (
    QueueFullError,
    RequestTimeoutError,
)
from deeplearning4j_tpu.serving.decode import _sample_step
from deeplearning4j_tpu.serving.resilience import (
    ClientRequestError,
    WorkerDeadError,
)
from deeplearning4j_tpu.serving.slo import SLOClass, default_classes
from deeplearning4j_tpu.serving.telemetry import ServingStats


def attention_path(cfg: TransformerConfig, block_tokens: int) -> str:
    """Which attention path the paged tick traces for this config:
    ``kernel`` = the pallas paged-decode kernel (ops/pallas_paged.py,
    behind DL4J_TPU_PALLAS_PAGED + the measured-win gate), ``gather`` =
    the chunked ``ck[tables[:, chunk]]`` loop of chunked_attention.
    Resolved at trace time; the tick cache keys on it, and the
    serving_decode bench stamps it."""
    if hasattr(cfg, "paged_decode_step"):
        # a model that brings its own tick body (models/hybrid.py) reads
        # the arena through chunked_attention; the kernel has no grouped
        # heads
        return "gather"
    hd = cfg.d_model // cfg.n_heads
    if jnp.dtype(lowprec.kv_dtype(cfg)) != jnp.dtype(cfg.compute_dtype):
        # a down-cast KV arena (DL4J_TPU_SERVE_KV_DTYPE=bf16 on an f32
        # model) takes the gather path, which reads the blocks as
        # stored against the query's exact rows; the pallas kernel's
        # bench verdicts were measured at the compute dtype
        return "gather"
    if pallas_paged.paged_kernel_enabled(cfg.n_heads, hd, block_tokens):
        return "kernel"
    return "gather"


# table columns one pass of the tick's attention gathers: whole blocks,
# ATTN_CHUNK_COLS * block_tokens tokens a pass. One constant, chosen on
# the chip (PERF.md section 6, PR 27); chunk edges sit at fixed global
# positions, which is what keeps a lane's bits free of its co-residents.
ATTN_CHUNK_COLS = 8


def _chunk_tokens(block_tokens: int, table_width: int) -> int:
    """Tokens one pass covers (a table narrower than the chunk is one
    pass): the program's and the host's count of it."""
    return min(ATTN_CHUNK_COLS, table_width) * block_tokens


def _exact_rows(x):
    """``x`` [S, H, n] as three bfloat16 rows [S, H, 3, n] whose sum is
    float32(x) exactly: the leading 8 bits of the mantissa, the next 8,
    the last 8. A dot of these rows with a bfloat16 operand, accumulated
    in float32, is the dot of the float32 ``x`` with it: every product
    is exact and nothing of ``x`` is rounded away. It is also a matrix
    product of three rows, which the TPU's compiler gives to the matrix
    unit reading the other operand as stored; a one-row product it
    rewrites as multiply-and-reduce over a float32 COPY of that operand
    (the converts that were 38% of the tick, PERF.md section 6)."""
    x = x.astype(jnp.float32)
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.stack([hi, mid, lo], axis=2)


def chunked_attention(q, ck, cv, tables, pos, scale=None, lo=None):
    """Masked single-query attention over the block arena, chunk by
    chunk up to the longest lane: q [S, H, hd], ck/cv [B, bt, Hkv * hd],
    a token's heads side by side as the arena stores them (or
    [B, bt, Hkv, hd], viewed so; block 0 = trash), tables [S, m] int32,
    pos [S] int32 (every entry >= 0) -> att [S, H, hd] float32. ``Hkv``
    divides ``H``: KV head j serves query heads g*j .. g*j+g-1
    (g = H // Hkv; 1 is full multi-head attention), their rows side by
    side in one product. ``scale`` multiplies the scores (None:
    1/sqrt(hd)).

    Each pass gathers ``c`` table columns of K and V in the arena's
    dtype, ``[S, chunk, Hkv * hd]``, takes the scores against the query's
    exact rows with float32 accumulation, masks ``t <= pos`` and folds
    the chunk into a running (max, denominator, accumulator) in float32:
    the online softmax of ops/pallas_paged.py. The probabilities stay
    float32 (_exact_rows again). K and V are read as stored (a float32
    arena at full precision); nothing of ``max_len`` width exists in any
    dtype. The trip count is data (``max(pos) // chunk + 1``, a
    ``while``), so one program serves every live length.

    Both products read the chunk in the layout the gather leaves it in.
    A head is a column range of the row: KV head j's two products take
    columns ``j*hd .. j*hd+hd-1`` of every gathered row, a static slice
    that the chip's compiler reads in place, against that head's rows
    ``[S, R, hd]`` (scores) and ``[S, R, chunk]`` (values), ``R`` the 3
    exact rows of each of its g query heads padded with zero rows to
    whole sublane tiles of 8 (8 for full multi-head attention, 16 for
    g = 4). One form for both sides and for every ``(H, Hkv, hd)``. The
    heads are NOT a batch axis of one product
    (``einsum("nhrd,nthd->nhrt")`` on the chunk viewed ``[S, chunk, Hkv,
    hd]``): the TPU's compiler wants a batch axis outermost and re-laid
    every gathered chunk to ``[S, Hkv, chunk, hd]`` before each product,
    a third of the serve cell's device time (PERF.md section 6, PR 35;
    one wide product a side with the heads' rows laid block-diagonally
    reads the chunk as stored too and measured slower on the chip, at
    both head shapes). A head's products never see another head's
    columns, so a K or V that is not a number in one head of a visible
    token stays in that head, as it did.

    A lane's output does not depend on the trip count: a chunk wholly
    past ``pos`` leaves the running max where it was, so ``corr`` is
    exactly 1 and ``p`` exactly 0, and the triple keeps its bits.
    ``-inf`` never meets ``-inf`` in an ``exp``: chunk 0 holds position
    0, which every lane sees, so the running max is finite from the
    first pass on.

    ``lo`` [S] int32 (a window layer: ``0 <= lo <= pos``) bounds a lane
    from below: it sees ``lo <= t <= pos``. Lane s then starts at ITS OWN
    first chunk, ``lo[s] // chunk`` (a per-lane gather of table columns;
    chunk edges stay at the same fixed global positions), and the loop
    runs as far as the lane whose span ``pos // chunk - lo // chunk`` is
    the longest: a window of w positions is read in at most ``w / chunk +
    2`` passes, however long the lanes are and whoever shares the tick.
    The first pass of a lane holds its position ``lo``, which it sees, so
    the running max is finite from the first pass on, as above; a pass
    past a lane's ``pos`` is the exact no-op it was. Without ``lo`` the
    function traces what it traced before it took one."""
    s, n_heads, hd = q.shape
    bt = ck.shape[1]
    # a token's heads side by side, however the caller names them
    ck = ck.reshape(ck.shape[0], bt, -1)
    cv = cv.reshape(cv.shape[0], bt, -1)
    width = ck.shape[2]
    kv_heads = width // hd
    group = n_heads // kv_heads
    # a KV head's rows in its products: 3 exact rows for each of its
    # query heads, padded with zero rows to whole sublane tiles of 8
    n_rows = -(-3 * group // 8) * 8
    chunk = _chunk_tokens(bt, tables.shape[1])
    c = chunk // bt
    # a table whose width c does not divide reads trash past its end,
    # at positions no lane can see
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % c)))
    if scale is None:
        scale = 1.0 / float(np.sqrt(hd))
    t_in = jnp.arange(chunk)[None, :]                 # [1, chunk]
    first = None if lo is None else lo // chunk       # [S] chunks

    def head_rows(x, dtype):
        # x [S, H, n] -> [S, Hkv, n_rows, n]: a KV head's query heads
        # stand as further rows of its one product
        rows = _exact_rows(x).astype(dtype).reshape(
            s, kv_heads, 3 * group, -1)
        return jnp.pad(rows, ((0, 0), (0, 0), (0, n_rows - 3 * group),
                              (0, 0)))

    def heads_dot(spec, rows, gathered):
        # rows [S, Hkv, n_rows, x] against the chunk as gathered,
        # [S, chunk, Hkv * hd]: KV head j's product reads its columns
        # j*hd .. j*hd+hd-1 of the rows where they lie. HIGHEST touches
        # float32 operands only (a float32 arena)
        out = jnp.stack([
            jnp.einsum(spec, rows[:, j], gathered[:, :, j * hd:(j + 1) * hd],
                       precision=lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
            for j in range(kv_heads)], axis=1)
        return out[:, :, :3 * group].reshape(s, n_heads, 3, -1).sum(axis=2)

    q_rows = head_rows(q, ck.dtype)

    def fold(j, carry):
        m, l, acc = carry
        if lo is None:
            cols = lax.dynamic_slice_in_dim(tables, j * c, c, axis=1)
        else:
            # the lane's own chunk first + j; columns past the table's end
            # read its last one, at positions past every lane's pos
            at = (first[:, None] + j) * c + jnp.arange(c)[None, :]
            cols = jnp.take_along_axis(
                tables, jnp.minimum(at, tables.shape[1] - 1), axis=1)
        with jax.named_scope("tick.gather_kv"):
            kg = ck[cols].reshape(s, chunk, width)
            vg = cv[cols].reshape(s, chunk, width)
        with jax.named_scope("tick.attend"):
            sc = heads_dot("nrd,ntd->nrt", q_rows, kg) * scale
            if lo is None:
                visible = j * chunk + t_in <= pos[:, None]    # [S, chunk]
            else:
                t_at = (first[:, None] + j) * chunk + t_in
                visible = (t_at >= lo[:, None]) & (t_at <= pos[:, None])
            sc = jnp.where(visible[:, None, :], sc, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))  # [S, H], finite
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + heads_dot(
                "nrt,ntd->nrd", head_rows(p, cv.dtype), vg)
        return m_new, l, acc

    init = (jnp.full((s, n_heads), -jnp.inf, jnp.float32),
            jnp.zeros((s, n_heads), jnp.float32),
            jnp.zeros((s, n_heads, hd), jnp.float32))
    trips = jnp.max(pos) // chunk + 1 if lo is None \
        else jnp.max(pos // chunk - first) + 1
    _, l, acc = lax.fori_loop(0, trips, fold, init)
    return acc / l[..., None]


def kv_read_tokens(max_pos: int, block_tokens: int, table_width: int) -> int:
    """Tokens a lane's attention loops over when the longest lane of its
    tick stands at ``max_pos``: chunked_attention's trip count times its
    chunk, as the host counts it for the ``serve.batch`` span."""
    chunk = _chunk_tokens(block_tokens, table_width)
    return (max_pos // chunk + 1) * chunk


def kv_read_tokens_window(pos: np.ndarray, window: int, block_tokens: int,
                          table_width: int) -> int:
    """The same for a window layer (chunked_attention with ``lo``): every
    lane at ``pos`` loops from its own first chunk as far as the lane with
    the longest span of chunks."""
    chunk = _chunk_tokens(block_tokens, table_width)
    lo = np.maximum(pos - (window - 1), 0)
    return int((pos // chunk - lo // chunk).max() + 1) * chunk


def paged_decode_step(params, arena, tok, pos, tables,
                      cfg: TransformerConfig, attention: Optional[str] = None):
    """One decode tick over the block arena: tok [S] int32, pos [S]
    int32, tables [S, max_len//bt] int32 -> (updated arena, logits
    [S, V]).

    The paged variant of serving/decode.decode_step_slots: the per-slot
    cache stripe becomes the lane's blocks, read through its table a
    chunk at a time and only as far as the longest lane reaches
    (chunked_attention), and the one-hot cache write becomes a scatter
    into (block, offset) = (tables[s, pos//bt], pos % bt). Active lanes
    write distinct blocks by allocation invariant; inactive lanes all
    scatter into trash block 0, whose content is never visible under
    the causal mask.

    ``arena`` k/v are ``[L, n_blocks+1, bt, H*hd]``. The layer scan does
    not scan over them: it CARRIES them beside ``h``, viewed as
    ``[L * (n_blocks+1), bt, H*hd]`` (a bitcast), and layer ``l`` writes
    and reads at rows ``l * (n_blocks+1) + block``, so the program
    updates the donated buffers where they lie and nothing of the
    arena's or of a layer's size is sliced, copied or re-laid.

    ``attention`` picks the per-layer attention body ('kernel' streams
    blocks through the pallas online-softmax kernel; 'gather' is the
    chunked XLA loop; None resolves via attention_path at trace time).
    Neither materializes the gathered window, and both honor the same
    ``t <= pos`` visibility mask, so outputs agree to f32 rounding
    (tests/test_pallas_paged.py pins 1e-6)."""
    cdt = cfg.compute_dtype
    s = tok.shape[0]
    hd = cfg.d_model // cfg.n_heads
    n_layers, rows, bt, width = arena["k"].shape
    if attention is None:
        attention = attention_path(cfg, bt)
    h = (params["embed"][tok] + params["pos"][pos])[:, None, :].astype(cdt)
    wb = jnp.take_along_axis(tables, (pos // bt)[:, None], axis=1)[:, 0]
    off = pos % bt

    def block(carry, xs):
        # ck/cv: the WHOLE arena [L * rows, bt, H * hd], carried: a layer
        # scanned over as xs/ys is sliced out of the stack and written
        # back, 63 MB each way a layer at the serve cell's size
        h, ck, cv = carry
        bp, base = xs  # base: the layer's first row, l * rows
        c = lambda a: a.astype(cdt)
        x = _ln(h, c(bp["ln1_g"]), c(bp["ln1_b"]))
        q = (x @ c(bp["Wq"])).reshape(s, cfg.n_heads, hd)
        k1 = (x @ c(bp["Wk"])).reshape(s, width)
        v1 = (x @ c(bp["Wv"])).reshape(s, width)
        with jax.named_scope("tick.scatter"):
            ck = ck.at[base + wb, off].set(k1.astype(ck.dtype))
            cv = cv.at[base + wb, off].set(v1.astype(cv.dtype))
        if attention == "kernel":
            with jax.named_scope("tick.attend"):
                heads = lambda a: a.reshape(a.shape[:2] + (cfg.n_heads, hd))
                att = pallas_paged.paged_attention(
                    q, heads(ck), heads(cv), tables + base, pos)
        else:
            att = chunked_attention(q, ck, cv, tables + base, pos)
        att = att.reshape(s, 1, cfg.d_model)
        h = h + att.astype(cdt) @ c(bp["Wo"])
        x = _ln(h, c(bp["ln2_g"]), c(bp["ln2_b"]))
        h = h + jax.nn.gelu(x @ c(bp["W1"]) + c(bp["b1"])) @ c(bp["W2"]) \
            + c(bp["b2"])
        return (h, ck, cv), None

    flat = lambda a: a.reshape(n_layers * rows, bt, width)
    bases = jnp.arange(n_layers, dtype=tables.dtype) * rows
    (h, ck, cv), _ = lax.scan(
        block, (h, flat(arena["k"]), flat(arena["v"])),
        (params["blocks"], bases))
    h = _ln(h[:, 0].astype(jnp.float32), params["lnf_g"], params["lnf_b"])
    return {"k": ck.reshape(arena["k"].shape),
            "v": cv.reshape(arena["v"].shape)}, h @ params["embed"].T


def decode_body(cfg, attention: Optional[str] = None):
    """The tick's body for a model: ``step(params, arena, tok, pos,
    tables) -> (arena, logits)``. A config that brings its own
    (``cfg.paged_decode_step``, models/hybrid.py: recurrent layers beside
    attention, their per-lane state riding in the arena) is asked for
    it; the GPT-2-shaped TransformerConfig is the instance above."""
    own = getattr(cfg, "paged_decode_step", None)
    if own is not None:
        return own
    return lambda params, arena, tok, pos, tables: paged_decode_step(
        params, arena, tok, pos, tables, cfg, attention=attention)


def serving_view(params, cfg):
    """The model's parameters as the serving programs read them: the same
    tree, in which every leaf of ``params["blocks"]`` (what the tick, the
    prefill and the verify cast to ``cfg.compute_dtype`` where they use
    it, ``a.astype(cdt)``) is held already cast, by ONE jitted call. A
    cast of a leaf that has the dtype is nothing, so the programs are the
    ones they were and the values that reach their products are the same
    bits: the float32 masters are rounded once here and not by every tick
    and every admission. Everything else (``embed``, ``pos``, ``lnf_g``,
    ``lnf_b``: read in float32) is the SAME buffer as in ``params``.

    It adapts on the leaves' dtypes alone: where none differs from the
    compute dtype (a model that holds its weights in it, models/hybrid.py;
    a float32 policy) or the tree has no ``blocks``, the view IS
    ``params``, the same object, and no program runs. A snapshot, as the
    alias it replaces was: a decoder is built over the weights it will
    serve, and a model swapped through the registry gets a new decoder."""
    cdt = jnp.dtype(cfg.compute_dtype)
    blocks = params.get("blocks")
    if all(a.dtype == cdt for a in jax.tree_util.tree_leaves(blocks)):
        return params
    return {**params,
            "blocks": jax.jit(lambda b: lowprec.cast_tree(b, cdt))(blocks)}


# jitted paged programs shared across decoder instances (the _TICK_CACHE
# discipline from serving/decode.py): cfg is a frozen dataclass, and the
# arena/lane shapes are jit trace dimensions, so one compiled program
# serves every decoder with the same (cfg, block_tokens, lanes, blocks)
_PAGED_TICK_CACHE: Dict[tuple, object] = {}
_PAGED_ADMIT_CACHE: Dict[tuple, object] = {}


def _paged_tick_for(cfg: TransformerConfig, block_tokens: int, k: int = 1):
    # the attention path is resolved HERE, not inside the trace: a knob
    # flip after the first tick must rebuild the jitted program, so the
    # resolved path rides the cache key. k (tokens
    # per tick, ISSUE 16) rides it the same way: the adaptive worker only
    # ever asks for k=1 and k=tick_k, so at most two programs per path.
    path = attention_path(cfg, block_tokens)
    key = (cfg, block_tokens, path, int(k))
    fn = _PAGED_TICK_CACHE.get(key)
    if fn is not None:
        return fn

    step_body = decode_body(cfg, path)

    if k == 1:
        def tick(params, arena, tok, pos, tables, keys, temps):
            # a body with routed experts returns, beside the logits, how
            # many experts a live lane's row reached (one int32)
            arena, logits, *more = step_body(params, arena, tok, pos, tables)
            with jax.named_scope("tick.sample"):
                nxt, nkeys = _sample_step(logits, keys, temps)
            return (arena, nxt[:, None], nkeys, *more)
    else:
        # k scanned steps in ONE dispatch: the per-step body (scatter at
        # pos, gather/attend, sample) is IDENTICAL to the k=1 tick, so
        # transcripts are byte-equal to k single ticks; the block tables
        # are loop constants — the worker pre-grew every lane's table k
        # positions ahead (_grow lookahead)
        def tick(params, arena, tok, pos, tables, keys, temps):
            def step(carry, _):
                arena, tok, pos, keys = carry
                arena, logits, *_ = step_body(params, arena, tok, pos,
                                              tables)
                with jax.named_scope("tick.sample"):
                    nxt, keys = _sample_step(logits, keys, temps)
                return (arena, nxt, pos + 1, keys), nxt

            (arena, _, _, keys), toks = lax.scan(
                step, (arena, tok, pos, keys), None, length=k)
            return arena, jnp.swapaxes(toks, 0, 1), keys

    # the arena is single-owner (the worker rebinds every tick), so it
    # donates even on CPU — an un-donated tick would memcpy the whole
    # arena per generated token (dispatch.arena_jit)
    tick = dispatch.arena_jit(tick, donate=(1,))
    _PAGED_TICK_CACHE[key] = tick
    return tick


def _paged_admit_for(cfg: TransformerConfig, width: int, block_tokens: int):
    key = (cfg, width, block_tokens)
    fn = _PAGED_ADMIT_CACHE.get(key)
    if fn is not None:
        return fn
    own = getattr(cfg, "paged_admit", None)
    if own is not None:
        # the model's own admission body (models/hybrid.py): one more
        # argument, ``lane`` int32 [2] = (lane index, positions that feed
        # the lane's recurrent state), which it writes beside the blocks
        # (a model with a window group reads the prompt's length off it)
        def admit(params, arena, window, write_table, lane):
            return own(params, arena, window, write_table, lane)

        admit = dispatch.arena_jit(admit, donate=(1,))
        _PAGED_ADMIT_CACHE[key] = admit
        return admit
    m = cfg.max_len // block_tokens

    def admit(params, arena, window, write_table):
        # window: [1, width]; prefill pads its K/V out to max_len, so
        # the reshape covers every table entry. write_table redirects
        # shared-prefix and beyond-prompt entries to trash block 0:
        # shared blocks are NEVER written after their creating prefill
        # (the prefix-cache byte-stability invariant).
        with jax.named_scope("admit.prefill"):
            c1, _ = prefill_cache(params, window, cfg)
        with jax.named_scope("admit.scatter"):
            kb = c1["k"][:, 0].reshape(cfg.n_layers, m, block_tokens,
                                       cfg.d_model)
            vb = c1["v"][:, 0].reshape(cfg.n_layers, m, block_tokens,
                                       cfg.d_model)
            ak = arena["k"].at[:, write_table].set(
                kb.astype(arena["k"].dtype))
            av = arena["v"].at[:, write_table].set(
                vb.astype(arena["v"].dtype))
        return {"k": ak, "v": av}

    admit = dispatch.arena_jit(admit, donate=(1,))
    _PAGED_ADMIT_CACHE[key] = admit
    return admit


# prefill/decode disaggregation programs (ISSUE 18): the export runs the
# SAME bucketed prefill an admission would, returning the block-shaped
# KV instead of scattering it; the import is the scatter half alone,
# applied to blocks computed elsewhere. Content addressing rides the
# PrefixCache digest chain, so imported blocks are indistinguishable
# from locally-prefilled cache entries.
_PREFIX_EXPORT_CACHE: Dict[tuple, object] = {}
_PREFIX_IMPORT_CACHE: Dict[tuple, object] = {}


def _prefix_export_for(cfg: TransformerConfig, width: int,
                       block_tokens: int, dtype):
    key = (cfg, width, block_tokens, jnp.dtype(dtype).name)
    fn = _PREFIX_EXPORT_CACHE.get(key)
    if fn is not None:
        return fn
    m = cfg.max_len // block_tokens

    def export(params, window):
        # the cast to the arena dtype happens IN-program, the same
        # convert the admit scatter applies — exported bytes must equal
        # what the importer's own prefill would have written. Blocks
        # leave as the arena holds them, [L, m, bt, H*hd]
        c1, _ = prefill_cache(params, window, cfg)
        kb = c1["k"][:, 0].reshape(cfg.n_layers, m, block_tokens,
                                   cfg.d_model)
        vb = c1["v"][:, 0].reshape(cfg.n_layers, m, block_tokens,
                                   cfg.d_model)
        return kb.astype(dtype), vb.astype(dtype)

    fn = jax.jit(export)
    _PREFIX_EXPORT_CACHE[key] = fn
    return fn


def _prefix_import_for(cfg: TransformerConfig, block_tokens: int,
                       table_width: int):
    key = (cfg, block_tokens, int(table_width))
    fn = _PREFIX_IMPORT_CACHE.get(key)
    if fn is not None:
        return fn

    def imp(arena, kb, vb, table):
        # kb/vb [L, table_width, bt, H*hd], as the arena holds blocks;
        # unadopted table entries point at trash block 0 and scatter
        # zeros there — invisible under the causal mask, the same
        # argument the admit path's write_table makes
        ak = arena["k"].at[:, table].set(kb.astype(arena["k"].dtype))
        av = arena["v"].at[:, table].set(vb.astype(arena["v"].dtype))
        return {"k": ak, "v": av}

    fn = dispatch.arena_jit(imp, donate=(0,))
    _PREFIX_IMPORT_CACHE[key] = fn
    return fn


# admission gathers a burst that meets an idle pool: while no lane has given
# a token yet, no tick is dispatched as long as requests are still arriving
# (one was submitted within GATHER_S), for at most GATHER_CAP_S a pass. A
# tick costs every waiting request its whole length, and how many of a
# burst's requests have reached the queue when the worker first runs out of
# them is up to the interpreter's scheduling: without the wait the same 32
# requests are admitted in one pass or in four, a tick between each. The
# wait runs under the first prefills. A pool in mid-generation never waits:
# there it would add to the gap between two tokens of every live lane
GATHER_S = 0.005
GATHER_CAP_S = 0.025


def seed_key(seed: int) -> np.ndarray:
    """``jax.random.PRNGKey(seed)`` as the raw uint32 [2] it is, made on
    the host: asked of the device, the key of every admission waits out
    whatever the device has queued (the prefill before it, in a burst of
    admissions), and the worker with it. The default implementation's
    seeding is (seed >> 32, seed & 0xffffffff), the high word 0 without
    x64; any other implementation is asked as before."""
    if jax.config.jax_default_prng_impl != "threefry2x32":
        return np.asarray(jax.random.PRNGKey(seed))
    seed = int(seed)
    high = (seed >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([high, seed & 0xFFFFFFFF], np.uint32)


def refuse_window(what: str) -> str:
    return (f"{what} cannot carry a KV group with a window (its lanes hold "
            "only the blocks the window reaches, in a pool and a table of "
            "the group's own): not implemented for models with window "
            "layers")


class BlockArena:
    """Host-side allocator for the device block arena: a free list plus
    per-block refcounts (prefix-shared blocks are held by every reader
    AND the cache itself). Physical ids run 1..usable; 0 is trash.
    Single-owner discipline: only the decoder worker thread touches it,
    so it needs no lock of its own."""

    def __init__(self, usable: int) -> None:
        self.usable = int(usable)
        self._free: List[int] = list(range(self.usable, 0, -1))
        self.refs = np.zeros((self.usable + 1,), np.int64)

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.usable - len(self._free)

    def alloc(self) -> Optional[int]:
        if not self._free:
            return None
        b = self._free.pop()
        self.refs[b] = 1
        return b

    def incref(self, block: int) -> None:
        self.refs[block] += 1

    def decref(self, block: int) -> None:
        self.refs[block] -= 1
        if self.refs[block] <= 0:
            self.refs[block] = 0
            self._free.append(block)


class PrefixCache:
    """Content-addressed block index: chained sha256 of the re-based
    prompt window -> physical block id, LRU-ordered. The cache holds one
    reference per entry, so a block survives its creating request; when
    the free list runs dry, :meth:`reclaim` evicts least-recently-used
    entries nobody else references."""

    def __init__(self, arena: BlockArena) -> None:
        self._arena = arena
        self._map: "OrderedDict[bytes, int]" = OrderedDict()

    def __len__(self) -> int:
        return len(self._map)

    @staticmethod
    def chain_hashes(window: np.ndarray, block_tokens: int,
                     limit: int) -> List[bytes]:
        """Digests for full blocks [0, limit) of the window; each digest
        covers ALL tokens up to its block's end (positions are re-based
        to the window, so equal-content prefixes share regardless of the
        original prompt's truncated head)."""
        out: List[bytes] = []
        h = b"paged-kv-v1"
        w = np.ascontiguousarray(window.astype(np.int32, copy=False))
        for i in range(limit):
            h = hashlib.sha256(
                h + w[i * block_tokens:(i + 1) * block_tokens].tobytes()
            ).digest()
            out.append(h)
        return out

    def lookup(self, hashes: List[bytes]) -> List[int]:
        """Longest-prefix hit: block ids for the leading run of known
        digests (LRU-refreshed). Caller increfs what it keeps."""
        hits: List[int] = []
        for h in hashes:
            b = self._map.get(h)
            if b is None:
                break
            self._map.move_to_end(h)
            hits.append(b)
        return hits

    def insert(self, digest: bytes, block: int) -> bool:
        if digest in self._map:
            return False  # equal content already cached; keep ours private
        self._map[digest] = block
        self._arena.incref(block)
        return True

    def reclaim(self, n: int) -> int:
        """Evict up to n LRU entries whose only reference is the cache's
        own — returns how many blocks went back to the free list."""
        freed = 0
        for digest, block in list(self._map.items()):
            if freed >= n:
                break
            if self._arena.refs[block] == 1:
                del self._map[digest]
                self._arena.decref(block)
                freed += 1
        return freed


class _ReqTrace:
    """What the spans of one request share, made at submit when tracing
    is on: the span that caused the request (the engine's
    ``serve.request``) and its ``rid``, when the request last joined the
    queue, and how often a preemption has put it back there."""

    __slots__ = ("parent", "rid", "since", "requeued")

    def __init__(self, parent, rid, since, requeued=0) -> None:
        self.parent = parent
        self.rid = rid
        self.since = since
        self.requeued = requeued


class _PendingReq:
    __slots__ = ("prompt", "n_new", "temperature", "seed", "future",
                 "deadline", "enqueued", "slo", "on_token", "tokens",
                 "key_override", "seq", "trace")

    def __init__(self, prompt, n_new, temperature, seed, deadline, slo,
                 on_token, seq, future=None, tokens=None,
                 key_override=None, enqueued=None, trace=None) -> None:
        self.prompt = prompt
        self.n_new = n_new
        self.temperature = temperature
        self.seed = seed
        self.future = future if future is not None else Future()
        self.deadline = deadline
        self.enqueued = enqueued if enqueued is not None \
            else time.monotonic()
        self.slo = slo
        self.on_token = on_token
        self.tokens = tokens if tokens is not None else []
        self.key_override = key_override  # preemption-saved PRNG key
        self.seq = seq
        self.trace = trace  # _ReqTrace, or None with tracing off


class _Held:
    """A lane's blocks in one KV group: its table entries ``first ..
    nxt - 1``, in order. ``first`` moves only in a window group, whose
    blocks go back to their pool as the window passes them."""

    __slots__ = ("first", "nxt", "blocks")

    def __init__(self, first: int, blocks: List[int]) -> None:
        self.first = first
        self.nxt = first + len(blocks)
        self.blocks = blocks


class _Lane:
    __slots__ = ("future", "tokens", "remaining", "deadline", "enqueued",
                 "temperature", "seed", "slo", "on_token", "held",
                 "window", "admit_seq", "trace")

    def __init__(self, req: _PendingReq, blocks: List[int], n_table: int,
                 window: np.ndarray, admit_seq: int,
                 more: Tuple[_Held, ...] = ()) -> None:
        self.future = req.future
        self.tokens = req.tokens
        self.remaining = req.n_new
        self.deadline = req.deadline
        self.enqueued = req.enqueued
        self.temperature = req.temperature
        self.seed = req.seed
        self.slo = req.slo
        self.on_token = req.on_token
        # a KV group each: the blocks this lane holds a ref on and its
        # allocated read-table entries; the first group's are ``blocks``
        # and ``n_table``, further groups' come as ``more``
        self.held = (_Held(n_table - len(blocks), blocks),) + tuple(more)
        self.window = window      # re-based prompt (for preempt requeue)
        self.admit_seq = admit_seq
        self.trace = req.trace

    @property
    def blocks(self) -> List[int]:
        return self.held[0].blocks

    @property
    def n_table(self) -> int:
        return self.held[0].nxt


class PagedDecoder:
    """Block-pool continuous decode over a TransformerLM, or over a model
    that brings its own tick and admission (models/hybrid.py), with a pool
    a KV group (the vLLM/Orca scheduling pair applied to this repo's
    decode_step — models/transformer.py:710). API-compatible with ContinuousDecoder
    (submit/generate/drain/stop + chaos admission faults + crash
    isolation + dead-worker fast-fail), plus ``slo=`` scheduling classes
    and per-token ``on_token`` streaming callbacks."""

    def __init__(self, lm, *, block_tokens: int = 16,
                 n_blocks: Optional[int] = None,
                 lanes: Optional[int] = None, min_lanes: int = 4,
                 stats: Optional[ServingStats] = None,
                 default_timeout_s: float = 300.0,
                 chaos=None,
                 slo_classes: Optional[List[SLOClass]] = None,
                 queue_cap: Optional[int] = None,
                 tick_k: Optional[int] = None) -> None:
        cfg = lm._run_cfg
        if lm.mesh is not None:
            raise ValueError("paged decode needs a single-device LM "
                             "(mesh-sharded models generate via ring/GSPMD)")
        if cfg.moe_experts and not getattr(cfg, "moe_dropless", False):
            raise ValueError(
                "paged decode does not serve capacity-routed experts (a "
                "token dropped past an expert's capacity makes a lane's "
                "output depend on its batch); a dropless expert layer "
                "(parallel/expert_parallel.dropless_experts) is served")
        self.lm = lm
        self.cfg = cfg
        # what the model holds per request (ops/memory.cache_needs): the
        # layers and heads of K and V the arena pages, and the per-lane
        # recurrent state that rides beside them in the arena pytree
        self.needs = opsmem.cache_needs(cfg)
        # multi-token ticks (ISSUE 16): steady-state decode scans tick_k
        # steps per dispatch, adaptively dropping to 1 whenever
        # admissions are pending or any lane is within k tokens of its
        # budget — scheduling semantics stay per-token
        self.tick_k = max(1, int(
            tick_k if tick_k is not None
            else envknob.get_int("DL4J_TPU_SERVE_TICK_K", 1)))
        self._refuse_state("scanned ticks (DL4J_TPU_SERVE_TICK_K > 1)",
                           self.tick_k > 1)
        # every device program reads params through this view (block
        # leaves held once in the compute dtype; lm.params itself where
        # they already are), so the mesh subclass (serving/mesh.py) can
        # swap in a replicated placement without re-plumbing the call sites
        self._infer_params = serving_view(lm.params, cfg)
        bt = max(1, min(int(block_tokens), cfg.max_len))
        while cfg.max_len % bt:
            bt //= 2
        self.block_tokens = bt
        self.table_width = cfg.max_len // bt
        # arena dtype (DL4J_TPU_SERVE_KV_DTYPE): bf16 halves block bytes,
        # so the auto-sized arena admits ~2x tokens on the same budget
        self.kv_dtype = jnp.dtype(lowprec.kv_dtype(cfg))
        if n_blocks is None:
            # per-device accounting: the mesh subclass head-shards the
            # arena, so each device prices only H/d heads per block
            n_blocks = opsmem.kv_arena_blocks(cfg, bt, params=lm.params,
                                              dtype=self.kv_dtype,
                                              devices=self.mesh_devices,
                                              lanes=lanes or 64)
        self.n_blocks = int(n_blocks)
        if self.n_blocks < self.table_width + 1:
            raise ValueError(
                f"n_blocks {self.n_blocks} cannot hold one max_len "
                f"sequence ({self.table_width + 1} blocks)")
        if lanes is None:
            # sized so sequences averaging a quarter of max_len fill the
            # arena; min_lanes keeps the fixed pool's floor, 64 caps the
            # tick's gather width
            est_seq = max(bt, cfg.max_len // 4)
            lanes = max(int(min_lanes),
                        min(64, max(1, self.n_blocks * bt // est_seq)))
        self.lanes = int(lanes)
        # a pool of blocks a KV group: the stated ``n_blocks`` for a group
        # that sees every position, a derived size for a window group
        self.group_blocks = opsmem.kv_group_blocks(
            self.needs, self.n_blocks, bt, self.lanes)
        # expert rows a token makes (top_k x expert layers; 0 without)
        self._moe_rows = int(getattr(cfg, "moe_rows_per_token", 0))
        self.stats = stats if stats is not None else ServingStats()
        self.default_timeout_s = float(default_timeout_s)
        self.queue_cap = int(queue_cap) if queue_cap else None
        classes = list(slo_classes) if slo_classes else \
            default_classes(self.default_timeout_s)
        self._classes = classes
        self._class_map = {c.name: c for c in classes}
        self._default_class = classes[0].name
        self._pending: Dict[str, deque] = {c.name: deque() for c in classes}
        # a read table a KV group; ``_tables`` is the first group's
        self._group_tables = [
            np.zeros((self.lanes, self.table_width), np.int32)
            for _ in self.needs.groups]
        self._tables = self._group_tables[0]
        self._reset_arena()
        self._tok = np.zeros((self.lanes,), np.int32)
        self._pos = np.zeros((self.lanes,), np.int32)
        self._temps = np.ones((self.lanes,), np.float32)
        # np.array (not asarray): jax array views are read-only and the
        # admit path writes per-lane key rows in place
        self._keys = np.array(
            jax.vmap(jax.random.PRNGKey)(jnp.zeros((self.lanes,),
                                                   jnp.uint32)))
        self._slots: List[Optional[_Lane]] = [None] * self.lanes
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._running = True
        self._chaos = chaos
        self._dead: Optional[str] = None
        self._last_submit = 0.0   # monotonic time of the newest submit
        self._seq = 0        # submit/requeue order (shed picks youngest)
        self._admit_seq = 0  # admission order (preemption picks youngest)
        # prefills dispatched since the last tick, and their summed
        # widths: attributes of the tick's span, which waits them out
        self._admits = 0
        self._admit_width_sum = 0
        self.peak_active = 0
        # decoder-owned dispatch ledger (TransformerLM carries only
        # memory_stats): decode_ticks / decode_tokens surface the
        # amortization win at /metrics
        self.dispatch_stats = dispatch.DispatchStats()
        register_net(self)
        # handed-off prefix blocks waiting for the worker to adopt them
        # (prefill/decode disaggregation — the worker owns the donated
        # arena, so imports must run on its thread)
        self._imports: deque = deque()
        # the last tick's callbacks and finished lanes, kept back until
        # the next program is on the device (_deliver); the worker's alone
        self._undelivered: Optional[tuple] = None
        # per-k tick memo: the attention path is resolved ONCE per k at
        # first use (construction-time for k=1, matching the old
        # self._tick behavior) — not per iteration, where the kernel
        # gate's measured-win lookup would run per generated token
        self._ticks: Dict[int, object] = {1: self._build_tick(1)}
        self._start_worker()

    def _refuse_state(self, what: str, asked: bool = True) -> None:
        """A path that cannot carry per-lane recurrent state, or a KV
        group whose lanes let blocks go as a window passes, refuses a
        model that has one, loudly, where it is asked for."""
        if asked and self.needs.state:
            raise ValueError(
                f"{what} cannot carry the recurrent state this model keeps "
                f"per lane ({', '.join(x.name for x in self.needs.state)}): "
                "not implemented for models with recurrent layers")
        if asked and self.needs.windowed:
            raise ValueError(refuse_window(what))

    def _tick_fn(self, k: int):
        fn = self._ticks.get(k)
        if fn is None:
            fn = self._build_tick(k)
            self._ticks[k] = fn
        return fn

    # -- program builders (overridden by serving/mesh.py) ----------------
    def _build_tick(self, k: int):
        return _paged_tick_for(self.cfg, self.block_tokens, k)

    def _build_admit(self, width: int):
        return _paged_admit_for(self.cfg, width, self.block_tokens)

    def _build_import(self):
        return _prefix_import_for(self.cfg, self.block_tokens,
                                  self.table_width)

    def _start_worker(self) -> None:
        """Factored out so subclasses (serving/speculate.py) can finish
        their own state setup before the decode thread goes live."""
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="paged-decoder")
        self._worker.start()

    supports_streaming = True  # engine.generate_stream dispatches on this
    # a tick's tokens reach their clients once the NEXT program is on the
    # device (_deliver): serving/speculate.py, whose rounds stream on
    # their own, hands them over at once
    defer_delivery = True
    mesh_devices = 1  # serving-mesh width; MeshPagedDecoder overrides
    _arena_sharding = None  # default placement; MeshPagedDecoder overrides

    def _reset_arena(self) -> None:
        """Fresh zeroed arena + allocator + prefix cache. Construction
        and the pool-wide failure path share it: a failed DONATED tick
        may have invalidated the old buffers, and with every lane failed
        no block content is worth keeping — cached prefixes included
        (they would read garbage from a reset arena)."""
        self._arena = self._zero_arena()
        self._pools = [BlockArena(n) for n in self.group_blocks]
        self._blocks = self._pools[0]
        self._prefix = PrefixCache(self._blocks)
        self.stats.set_kv_blocks(0, self.n_blocks)

    def _zero_arena(self):
        """Fresh zeroed k/v buffers, allocated directly under
        ``_arena_sharding`` (the mesh subclass head-shards them: each
        device only ever holds its own slice, which is what the
        auto-sizer priced). Two distinct buffers: k and v donate
        separately and must not alias each other; the scatter in
        paged_decode_step casts k/v onto ck.dtype, so a bf16 arena under
        an f32 model just works. A model with recurrent layers gets its
        state pool here too: for each leaf it names, one ``[lanes,
        *shape]`` buffer a layer, donated with k and v through every
        tick and admission and so rewritten in place.

        A row of a block is one token's heads side by side,
        ``kv_heads * head_dim`` wide. K and V are each ONE buffer
        ``[kv_layers, n_blocks+1, bt, kv_heads * head_dim]`` where the
        tick scans over layers that are alike (it carries the buffer
        and addresses layer ``l`` by row), block 0 of every layer that
        layer's trash; a model that asks for it (``kv_per_layer``: its
        layers differ and are unrolled) gets one ``[n_blocks+1, bt,
        kv_heads * head_dim]`` a layer, with the blocks of the layer's
        KV group (``group_blocks``: a window group's pool is smaller)."""
        needs = opsmem.cache_needs(self.cfg)
        layer = (self.n_blocks + 1, self.block_tokens,
                 needs.kv_heads * needs.head_dim)
        zeros = lambda shape: jnp.zeros(shape, self.kv_dtype,
                                        device=self._arena_sharding)
        if needs.kv_per_layer:
            rows = {j: n + 1 for g, n in zip(needs.groups, self.group_blocks)
                    for j in g.layer_ids}
            arena = {name: tuple(zeros((rows[j],) + layer[1:])
                                 for j in range(needs.kv_layers))
                     for name in ("k", "v")}
        else:
            stacked = (needs.kv_layers,) + layer
            arena = {"k": zeros(stacked), "v": zeros(stacked)}
        for leaf in needs.state:
            arena[leaf.name] = tuple(
                jnp.zeros((self.lanes,) + leaf.shape, leaf.dtype)
                for _ in range(leaf.layers))
        return arena

    def _arena_deleted(self) -> bool:
        """Did a donated program that failed take the arena with it?"""
        try:
            return jax.tree_util.tree_leaves(self._arena)[0].is_deleted()
        except Exception:  # noqa: BLE001 — probe only
            return False

    def state_pool(self) -> Dict[str, tuple]:
        """The per-lane recurrent state as the last tick or admission
        left it: for each leaf the model names, its ``[lanes, *shape]``
        buffers, one a layer (nothing for a model of KV layers alone).
        The buffers themselves, not copies: the next tick donates them,
        so read them while the pool is idle and let them go."""
        with self._cond:
            return {leaf.name: tuple(self._arena[leaf.name])
                    for leaf in self.needs.state}

    # -- capacity ---------------------------------------------------------
    def kv_capacity(self) -> Dict[str, object]:
        """/models KV report: what the arena can hold, in tokens."""
        with self._cond:
            in_use = self._blocks.in_use
            tokens_in_use = sum(
                int(self._pos[i]) + 1
                for i, st in enumerate(self._slots) if st is not None)
        return {
            "scheme": "paged",
            "kv_dtype": str(self.kv_dtype),
            "block_tokens": self.block_tokens,
            "blocks_total": self.n_blocks,
            "blocks_in_use": in_use,
            "capacity_tokens": self.n_blocks * self.block_tokens,
            "tokens_in_use": tokens_in_use,
            "lanes": self.lanes,
            "prefix_blocks_cached": len(self._prefix),
            "mesh_devices": int(self.mesh_devices),
            # what the model said it holds: the arena's layers and heads,
            # and the state pool's lanes and bytes (0 without recurrent
            # layers)
            "kv_layers": self.needs.kv_layers,
            "kv_heads": self.needs.kv_heads,
            "block_bytes": opsmem.kv_block_bytes(
                self.cfg, self.block_tokens, self.kv_dtype,
                devices=int(self.mesh_devices)),
            # a KV group each: its layers, its window (0: none), its pool
            "groups": [
                {"layers": g.layers, "window": g.window,
                 "blocks": pool.usable, "blocks_in_use": pool.in_use,
                 "block_bytes": opsmem.kv_block_bytes(
                     self.cfg, self.block_tokens, self.kv_dtype,
                     devices=int(self.mesh_devices), group=gi)}
                for gi, (g, pool) in enumerate(zip(self.needs.groups,
                                                   self._pools))],
            "state_lanes": self.lanes if self.needs.state else 0,
            "state_bytes": self.lanes * self.needs.state_lane_bytes,
            **self._weights_report(),
        }

    def _weights_report(self) -> Dict[str, object]:
        """How the serving programs hold the weights (serving_view): the
        dtype of the block leaves, and the bytes of the leaves held
        BESIDE the model's own (0 where the view is ``lm.params``)."""
        view, own = self._infer_params, self.lm.params
        beside = [a for a, b in zip(jax.tree_util.tree_leaves(view),
                                    jax.tree_util.tree_leaves(own))
                  if a is not b]
        held = jax.tree_util.tree_leaves(view.get("blocks", view))
        return {
            "weights_dtype": "+".join(sorted({a.dtype.name for a in held})),
            "weights_view_bytes": sum(int(a.nbytes) for a in beside),
        }

    # -- client side ------------------------------------------------------
    def submit(self, prompt, n_new: int, temperature: float = 1.0,
               seed: int = 0, timeout_s: Optional[float] = None,
               slo: Optional[str] = None, on_token=None,
               parent=None) -> Future:
        """Queue one prompt ([T] int ids) for n_new sampled tokens;
        returns a Future of the [n_new] int32 continuation. ``slo``
        names a scheduling class (default: the highest-priority one);
        ``on_token`` is called with each token as it is sampled (the
        streaming hook — keep it fast, it runs on the decode thread).
        ``parent`` is the caller's request span where it is not the one
        open on this thread (a streamed request's outlives this call):
        with tracing on, the request's queue, admission and tick spans
        hang under it and carry its ``rid``."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if n_new < 1 or n_new >= self.cfg.max_len:
            raise ValueError(f"n_new {n_new} must be in [1, max_len)")
        cls = self._class_map.get(slo if slo is not None
                                  else self._default_class)
        if cls is None:
            raise ClientRequestError(
                f"unknown SLO class {slo!r} (have: "
                f"{sorted(self._class_map)})")
        keep = min(prompt.size, self.cfg.max_len - int(n_new))
        total_blocks = (keep + int(n_new) - 2) // self.block_tokens + 1
        if total_blocks > self.n_blocks:
            raise ValueError(
                f"request needs {total_blocks} blocks > arena "
                f"{self.n_blocks}; it could never be scheduled")
        deadline = time.monotonic() + (timeout_s if timeout_s is not None
                                       else cls.deadline_s)
        self.stats.record_request()
        with self._cond:
            if not self._running:
                raise RuntimeError("decoder is stopped")
            if self._dead is not None:
                raise WorkerDeadError(
                    f"decoder worker died ({self._dead}); prompts would "
                    "queue forever")
            self._seq += 1
            req = _PendingReq(prompt, int(n_new), float(temperature),
                              int(seed), deadline, cls.name, on_token,
                              self._seq)
            if obs_trace.obs_enabled():
                if parent is None:
                    parent = obs_trace.tracer().current_span()
                req.trace = _ReqTrace(
                    getattr(parent, "span_id", None),
                    getattr(parent, "attrs", {}).get("rid"), req.enqueued)
            if self.queue_cap is not None and \
                    self._total_pending() >= self.queue_cap:
                victim = self._shed_for(cls)
                if victim is None:
                    self.stats.record_shed(cls.name)
                    self.stats.record_rejected()
                    raise QueueFullError(
                        f"decode queue full ({self.queue_cap}) and no "
                        f"lower-priority work to shed below {cls.name!r}")
                self.stats.record_shed(victim.slo)
                self.stats.record_rejected()
                victim.future.set_exception(QueueFullError(
                    f"shed by higher-priority class {cls.name!r}"))
            self._pending[cls.name].append(req)
            self._last_submit = time.monotonic()
            self.stats.set_queue_depth(self._total_pending(), "decode")
            self._cond.notify_all()
        return req.future

    def generate(self, prompts, n_new: int, temperature: float = 1.0,
                 seed: int = 0, timeout_s: Optional[float] = None,
                 slo: Optional[str] = None) -> np.ndarray:
        """Batch convenience: [N, T] prompts -> [N, n_new] continuations
        (independent requests; seeds offset per row, matching
        ContinuousDecoder.generate's contract)."""
        prompts = np.asarray(prompts, np.int32)
        if prompts.ndim == 1:
            prompts = prompts[None]
        futs = [self.submit(row, n_new, temperature=temperature,
                            seed=seed + i, timeout_s=timeout_s, slo=slo)
                for i, row in enumerate(prompts)]
        budget = timeout_s if timeout_s is not None \
            else self.default_timeout_s
        return np.stack([f.result(timeout=budget) for f in futs])

    def stop(self) -> None:
        with self._cond:
            self._running = False
            self._cond.notify_all()
        self._worker.join(timeout=10)
        with self._cond:
            for q in self._pending.values():
                for req in q:
                    if not req.future.done():
                        req.future.set_exception(
                            RuntimeError("decoder stopped"))
                q.clear()
            for st in self._slots:
                if st is not None and not st.future.done():
                    st.future.set_exception(RuntimeError("decoder stopped"))

    def drain(self, timeout_s: float = 20.0) -> bool:
        """Graceful-drain support: bounded wait for the pending queues
        and every lane to empty (admission is the engine's to stop)."""
        deadline = time.monotonic() + max(0.0, float(timeout_s))
        with self._cond:
            while (self._total_pending()
                   or any(st is not None for st in self._slots)) \
                    and self._dead is None:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cond.wait(timeout=left)
            return self._dead is None

    # -- scheduler internals (call under self._cond) ----------------------
    def _total_pending(self) -> int:
        return sum(len(q) for q in self._pending.values())

    def _shed_for(self, cls: SLOClass) -> Optional[_PendingReq]:
        """Pop the youngest pending request of the LOWEST class strictly
        below cls; None when nothing outranks-and-yields."""
        for c in reversed(self._classes):
            if c.priority <= cls.priority:
                break
            q = self._pending[c.name]
            if q:
                return q.pop()  # youngest of the lowest class
        return None

    def _release_lane(self, i: int) -> None:
        lane = self._slots[i]
        if lane is None:
            return
        for pool, table, held in zip(self._pools, self._group_tables,
                                     lane.held):
            for b in held.blocks:
                pool.decref(b)
            table[i, :] = 0
        # a dead lane attends position 0 of the trash block, as a fresh
        # one does: left at the finished request's last position it
        # would hold up the tick's bound (max over lanes of pos) for as
        # long as the lane stays empty
        self._pos[i] = 0
        self._tok[i] = 0
        self._slots[i] = None
        self.stats.set_kv_blocks(self._blocks.in_use, self.n_blocks)

    def _kv_counts(self, active: List[int], k: int) -> Dict[str, int]:
        """What the next k-step tick's attention reads, for its span:
        ``kv_live`` the positions the active lanes can see, ``kv_read``
        the positions the program loops over for every lane, dead ones
        too (the bound follows the longest lane, step by step)."""
        pos = self._pos[active].astype(np.int64)
        top = int(self._pos.max())
        counts = {
            "kv_live": int(sum((pos + 1 + j).sum() for j in range(k))),
            "kv_read": self.lanes * sum(
                kv_read_tokens(top + j, self.block_tokens, self.table_width)
                for j in range(k))}
        if self.needs.windowed:
            # over the groups, weighted by their layers and divided by the
            # KV layers: a window layer's lane sees min(pos + 1, window)
            # positions and loops over its own span of chunks (k is 1)
            every = self._pos.astype(np.int64)
            live = read = 0
            for g in self.needs.groups:
                if g.window:
                    live += g.layers * int(
                        np.minimum(pos + 1, g.window).sum())
                    read += g.layers * self.lanes * kv_read_tokens_window(
                        every, g.window, self.block_tokens, self.table_width)
                else:
                    live += g.layers * counts["kv_live"]
                    read += g.layers * counts["kv_read"]
            counts = {"kv_live": live // self.needs.kv_layers,
                      "kv_read": read // self.needs.kv_layers}
        if self._moe_rows:
            counts["moe_rows"] = len(active) * k * self._moe_rows
        if self.needs.state:
            # live lanes whose recurrent state the tick advances, and the
            # least it moves for them: each lane's ``ssm`` leaf read once
            # and written once a step (the leaf of that name alone: the
            # conv tail beside it is a hundredth of it, and the reader of
            # these bytes times the events of the ssm leaf's shape)
            counts["ssm_lanes"] = len(active) * k
            counts["ssm_state_bytes"] = 2 * counts["ssm_lanes"] * sum(
                leaf.lane_bytes for leaf in self.needs.state
                if leaf.name == "ssm")
        return counts

    def _youngest_active(self) -> Optional[int]:
        best, best_seq = None, -1
        for i, st in enumerate(self._slots):
            if st is not None and st.admit_seq > best_seq:
                best, best_seq = i, st.admit_seq
        return best

    def _preempt(self, i: int) -> None:
        """Free lane i's blocks and re-queue the request at the FRONT of
        its class with prompt := window + generated and its live PRNG
        key saved, so the resumed stream continues bit-where it stopped
        (prefill recomputes the generated prefix's KV; the key stream
        never replays a roll)."""
        lane = self._slots[i]
        prompt = np.concatenate(
            [lane.window, np.asarray(lane.tokens, np.int32)])
        self._seq += 1
        req = _PendingReq(prompt, lane.remaining, lane.temperature,
                          lane.seed, lane.deadline, lane.slo,
                          lane.on_token, self._seq, future=lane.future,
                          tokens=lane.tokens,
                          key_override=self._keys[i].copy(),
                          enqueued=lane.enqueued, trace=lane.trace)
        if lane.trace is not None:
            lane.trace.since = time.monotonic()
            lane.trace.requeued += 1
        self._release_lane(i)
        self._pending[lane.slo].appendleft(req)
        self.stats.record_preemption()
        self.stats.set_queue_depth(self._total_pending(), "decode")

    def _grow(self, i: int, lookahead: int = 0) -> bool:
        """Ensure lane i's write blocks through position pos+lookahead
        are allocated (a k-token tick writes positions pos..pos+k-1, so
        the worker grows with lookahead=k-1); preempts the youngest
        admission (possibly lane i itself) on exhaustion. Returns False
        iff lane i was preempted."""
        lane = self._slots[i]
        reach = (int(self._pos[i]) + lookahead) // self.block_tokens
        for pool, table, held in zip(self._pools, self._group_tables,
                                     lane.held):
            while reach >= held.nxt:
                b = pool.alloc()
                if b is None and pool is self._blocks:
                    self._prefix.reclaim(1)
                    b = pool.alloc()
                if b is None:
                    j = self._youngest_active()
                    self._preempt(j)
                    if j == i:
                        return False
                    continue
                held.blocks.append(b)
                table[i, held.nxt] = b
                held.nxt += 1
        self.stats.set_kv_blocks(self._blocks.in_use, self.n_blocks)
        return True

    def _trim(self, i: int) -> None:
        """Lane i's next tick stands at ``_pos[i]``: in a window group the
        blocks that lie wholly behind ``pos - window`` go back to their
        pool and their table entries point at trash."""
        lane = self._slots[i]
        for g, pool, table, held in zip(self.needs.groups, self._pools,
                                        self._group_tables, lane.held):
            if not g.window:
                continue
            behind = (int(self._pos[i]) - g.window + 1) // self.block_tokens
            while held.first < behind:
                pool.decref(held.blocks.pop(0))
                table[i, held.first] = 0
                held.first += 1

    def _pick_admission(self):
        """Pop the single next admissible request (highest SLO class
        first, FIFO within a class) and book its lane. Returns None
        when nothing is admissible — including the head-of-line case
        where the highest waiting class cannot fund its head request's
        blocks: lower classes must not starve a blocked high class."""
        free = next((i for i in range(self.lanes)
                     if self._slots[i] is None), None)
        if free is None:
            return None
        for c in self._classes:
            q = self._pending[c.name]
            if not q:
                continue
            req = q.popleft()
            tr = req.trace
            picked = time.monotonic() if tr is not None else 0.0
            booked = self._admit_bookkeeping(free, req)
            if booked is None:
                q.appendleft(req)
                return None
            pending = self._total_pending()
            self.stats.set_queue_depth(pending, "decode")
            if tr is not None:
                # the wait ended at the pick; the booking since then is
                # the admission's own time (serve.admit)
                obs_trace.record_span(
                    "serve.queue", picked - tr.since,
                    ago=time.monotonic() - picked, parent=tr.parent,
                    rid=tr.rid, slo=req.slo, pending=pending,
                    requeued=tr.requeued)
            return (free,) + booked
        return None

    def _admit_bookkeeping(self, i: int, req: _PendingReq):
        """Host-side admission under the lock: prefix lookup, block
        allocation, table setup. Returns (buf, width, write_table,
        inserts) for the device prefill (run OUTSIDE the lock), or None
        when the arena cannot fund the prompt right now (the request
        stays at the head of its class)."""
        cfg = self.cfg
        bt = self.block_tokens
        keep = min(req.prompt.size, cfg.max_len - req.n_new)
        window = np.ascontiguousarray(req.prompt[req.prompt.size - keep:])
        wb0 = (keep - 1) // bt        # first write block: always private
        nb_prompt = wb0 + 1
        # a prefix hit restores KV blocks only: a lane whose recurrent
        # state started after tokens it never saw would be wrong, and a
        # block shared with another lane cannot be let go as one lane's
        # window passes it, so a model with such state or with a window
        # group takes no hit and counts no lookup
        hashes = [] if self.needs.state or self.needs.windowed \
            else PrefixCache.chain_hashes(window, bt, wb0)
        hits = self._prefix.lookup(hashes)
        if hashes:
            self.stats.record_prefix(len(hits), len(hashes))
        # a further KV group's blocks: from the block that position
        # keep - window lies in (a window group; the first tick, at
        # keep - 1, sees no earlier one) to the write block
        firsts = [max(0, (keep - g.window) // bt) if g.window else 0
                  for g in self.needs.groups]
        firsts[0] = max(firsts[0], len(hits))
        needs = [nb_prompt - f for f in firsts]
        if self._blocks.free_count < needs[0]:
            self._prefix.reclaim(needs[0] - self._blocks.free_count)
        if any(pool.free_count < n for pool, n in zip(self._pools, needs)):
            return None
        for b in hits:
            self._blocks.incref(b)
        fresh, *more = ([pool.alloc() for _ in range(n)]
                        for pool, n in zip(self._pools, needs))
        read_table = np.zeros((self.table_width,), np.int32)
        write_table = np.zeros((self.table_width,), np.int32)
        read_table[:len(hits)] = hits
        read_table[firsts[0]:nb_prompt] = fresh
        write_table[firsts[0]:nb_prompt] = fresh
        writes = [write_table]
        for gi, blocks in enumerate(more, 1):
            table = np.zeros((self.table_width,), np.int32)
            table[firsts[gi]:nb_prompt] = blocks
            self._group_tables[gi][i, :] = table
            writes.append(table)
        if more:
            write_table = np.stack(writes)
        # cache candidates: private FULL blocks strictly below the write
        # block — they are fully prompt-covered and never written again
        inserts = [(hashes[j], int(read_table[j]))
                   for j in range(len(hits), len(hashes))]
        width = min(max(dispatch.bucket_size(keep), keep), cfg.max_len)
        buf = np.zeros((1, width), np.int32)
        buf[0, :keep] = window
        self._tok[i] = int(window[-1])
        self._pos[i] = keep - 1  # re-consume the last prompt token
        self._temps[i] = req.temperature
        self._keys[i] = (req.key_override if req.key_override is not None
                         else seed_key(req.seed))
        self._tables[i, :] = read_table
        self._admit_seq += 1
        self._slots[i] = _Lane(
            req, hits + fresh, nb_prompt, window, self._admit_seq,
            tuple(_Held(f, b) for f, b in zip(firsts[1:], more)))
        self.stats.set_kv_blocks(self._blocks.in_use, self.n_blocks)
        return buf, width, write_table, inserts

    def _admit_prefill(self, i: int, buf: np.ndarray, width: int,
                       write_table: np.ndarray) -> None:
        # the lane index rides the signature so subclasses with per-lane
        # side state (serving/speculate.py prefills its draft cache row
        # here) share this crash-isolation boundary
        # a model with recurrent layers is also told its lane and how
        # many positions feed the lane's state: all but the last prompt
        # token, which the first tick re-consumes (self._pos[i])
        lane = (jnp.asarray([i, self._pos[i]], jnp.int32),) \
            if self.needs.state or self.needs.windowed else ()
        self._arena = self._build_admit(width)(
            self._infer_params, self._arena, jnp.asarray(buf),
            jnp.asarray(write_table), *lane)

    # -- prefill/decode disaggregation ------------------------------------
    def export_prefix(self, prompt, n_new: int):
        """Prefill-role half of the handoff (ISSUE 18): compute the
        primed KV for a prompt's FULL blocks strictly below the write
        block, plus their digest chain, without touching the arena or
        the worker. The digests are the same chained sha256 the decode
        replica's own admission computes (PrefixCache.chain_hashes over
        the re-based window), so the handoff is content-addressed: the
        importer adopts the blocks as ordinary prefix-cache entries and
        a later admission of the same window hits them — or, on any
        miss, recomputes them byte-identically (the prefix-cache
        byte-stability argument). Returns (digests, k_blocks, v_blocks)
        with blocks [L, n, bt, H, hd] in the arena dtype; n may be 0
        for short prompts (nothing worth handing off)."""
        self._refuse_state("the prefill/decode handoff (export_prefix)")
        cfg = self.cfg
        bt = self.block_tokens
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        n_new = int(n_new)
        if n_new < 1 or n_new >= cfg.max_len:
            raise ValueError(f"n_new {n_new} must be in [1, max_len)")
        keep = min(prompt.size, cfg.max_len - n_new)
        window = np.ascontiguousarray(prompt[prompt.size - keep:])
        wb0 = (keep - 1) // bt
        digests = PrefixCache.chain_hashes(window, bt, wb0)
        hd = cfg.d_model // cfg.n_heads
        if wb0 == 0:
            z = np.zeros((cfg.n_layers, 0, bt, cfg.n_heads, hd),
                         self.kv_dtype)
            return [], z, z.copy()
        width = min(max(dispatch.bucket_size(keep), keep), cfg.max_len)
        buf = np.zeros((1, width), np.int32)
        buf[0, :keep] = window
        kb, vb = _prefix_export_for(cfg, width, bt, self.kv_dtype)(
            self._infer_params, jnp.asarray(buf))
        self.stats.record_prefix_export()
        # the handoff's format names the heads; the arena's row does not
        wire = lambda a: np.asarray(a[:, :wb0]).reshape(
            cfg.n_layers, wb0, bt, cfg.n_heads, hd)
        return digests, wire(kb), wire(vb)

    def import_prefix(self, digests, k_blocks, v_blocks,
                      timeout_s: float = 60.0) -> int:
        """Decode-role half of the handoff: queue handed-off prompt
        blocks for adoption into the arena + prefix cache. The worker
        owns the donated arena, so the scatter runs on its thread
        between ticks. Returns how many blocks were actually adopted;
        correctness never depends on it — an already-cached digest, an
        exhausted free list or a device failure just shrink the adopted
        run, and the next admission's prefill recomputes the rest."""
        self._refuse_state("the prefill/decode handoff (import_prefix)")
        cfg = self.cfg
        hd = cfg.d_model // cfg.n_heads
        digests = list(digests)
        kb = np.asarray(k_blocks)
        vb = np.asarray(v_blocks)
        expect = (cfg.n_layers, len(digests), self.block_tokens,
                  cfg.n_heads, hd)
        if kb.shape != expect or vb.shape != expect:
            raise ClientRequestError(
                f"prefix blocks {kb.shape}/{vb.shape} do not match the "
                f"arena layout {expect}")
        if kb.dtype != self.kv_dtype or vb.dtype != self.kv_dtype:
            raise ClientRequestError(
                f"prefix blocks dtype {kb.dtype}/{vb.dtype} != arena kv "
                f"dtype {self.kv_dtype} (mismatched "
                "DL4J_TPU_SERVE_KV_DTYPE across roles)")
        if len(digests) >= self.table_width:
            raise ClientRequestError(
                f"{len(digests)} handed-off blocks >= table width "
                f"{self.table_width}; full blocks strictly below the "
                "write block can never reach it")
        if not digests:
            return 0
        fut = Future()
        with self._cond:
            if not self._running:
                raise RuntimeError("decoder is stopped")
            if self._dead is not None:
                raise WorkerDeadError(
                    f"decoder worker died ({self._dead}); imports would "
                    "queue forever")
            self._imports.append((digests, kb, vb, fut))
            self._cond.notify_all()
        return int(fut.result(timeout=timeout_s))

    def _apply_import(self, digests, kb, vb, fut) -> None:
        """Adopt handed-off prefix blocks (worker thread; the donated
        scatter shares the admission crash-isolation discipline)."""
        try:
            with self._cond:
                hits = self._prefix.lookup(digests)
                start = len(hits)
                need = len(digests) - start
                if need and self._blocks.free_count < need:
                    self._prefix.reclaim(need - self._blocks.free_count)
                avail = min(need, self._blocks.free_count)
                fresh = [self._blocks.alloc() for _ in range(avail)]
                self.stats.set_kv_blocks(self._blocks.in_use,
                                         self.n_blocks)
            if not fresh:
                fut.set_result(0)
                return
            cfg = self.cfg
            table = np.zeros((self.table_width,), np.int32)
            # blocks arrive [L, n, bt, H, hd] and are scattered as the
            # arena holds them, a token's heads side by side
            kb, vb = (a.reshape(a.shape[:3] + (cfg.d_model,))
                      for a in (kb, vb))
            kpad = np.zeros((cfg.n_layers, self.table_width)
                            + kb.shape[2:], self.kv_dtype)
            vpad = np.zeros_like(kpad)
            for t, j in enumerate(range(start, start + avail)):
                table[j] = fresh[t]
                kpad[:, j] = kb[:, j]
                vpad[:, j] = vb[:, j]
            try:
                self._arena = self._build_import()(
                    self._arena, jnp.asarray(kpad), jnp.asarray(vpad),
                    jnp.asarray(table))
            except Exception as e:  # noqa: BLE001 — device boundary
                with self._cond:
                    for b in fresh:
                        self._blocks.decref(b)
                if self._arena_deleted():
                    # the DONATED import died mid-execution and took the
                    # arena with it (same honesty as a crashed admit)
                    self._fail_active_lanes(e)
                fut.set_exception(e)
                return
            with self._cond:
                for t, j in enumerate(range(start, start + avail)):
                    self._prefix.insert(digests[j], fresh[t])
                    # the cache's ref is the only owner (alloc's ref was
                    # the import's working hold); a concurrent admission
                    # that beat us to the digest makes insert a no-op
                    # and this decref frees our duplicate block
                    self._blocks.decref(fresh[t])
                self.stats.set_kv_blocks(self._blocks.in_use,
                                         self.n_blocks)
                self.stats.record_prefix_import(avail)
            fut.set_result(avail)
        except Exception as e:  # noqa: BLE001 — import isolation boundary
            if not fut.done():
                fut.set_exception(e)

    # -- worker side ------------------------------------------------------
    def _run(self) -> None:
        try:
            try:
                self._run_inner()
            finally:
                self._deliver()   # what the last tick gave, whatever ended us
        except Exception as e:  # noqa: BLE001 — worker loop boundary
            with self._cond:
                self._dead = f"{type(e).__name__}: {e}"
                victims = [st for st in self._slots if st is not None]
                for i in range(self.lanes):
                    self._release_lane(i)
                for q in self._pending.values():
                    victims.extend(q)
                    q.clear()
                imports = list(self._imports)
                self._imports.clear()
                self.stats.set_queue_depth(0, "decode")
                self._cond.notify_all()
            for item in imports:
                if not item[3].done():
                    item[3].set_exception(WorkerDeadError(
                        f"decoder worker died: {self._dead}"))
            self.stats.record_worker_death()
            err = WorkerDeadError(f"decoder worker died: {self._dead}")
            for v in victims:
                if not v.future.done():
                    v.future.set_exception(err)

    def _fail_active_lanes(self, exc: Exception) -> None:
        """Pool-wide device failure (one tick program covers every
        lane): fail each active future with the real cause, return the
        blocks, keep the decoder alive for fresh traffic."""
        with self._cond:
            victims = [st for st in self._slots if st is not None]
            for i in range(self.lanes):
                self._release_lane(i)
            self._reset_arena()
            for table in self._group_tables:
                table[:, :] = 0
            self._cond.notify_all()
        for st in victims:
            if not st.future.done():
                st.future.set_exception(exc)

    def _run_inner(self) -> None:
        while True:
            with obs_trace.span("serve.sweep"):
                self._sweep()
            # admission: ONE request per pick so a request admitted
            # later in the same pass can hit the prefix blocks an
            # earlier prefill just cached — inserts land between
            # prefills, and only after the block content is actually
            # written (a crashed prefill never publishes its digests)
            self._admit_pending()
            self._gather()
            if not self._tick_phase():
                return

    def _admit_pending(self) -> bool:
        """Admit what waits and can be admitted; True if anything was."""
        any_admitted = False
        while self._admit_one():
            # the prefill is on the device: the last tick's tokens go out
            # under it
            self._deliver()
            any_admitted = True
        return any_admitted

    def _gather(self) -> None:
        """Hold the first tick of a pool that was idle back while a burst
        is still arriving: as long as no lane has given a token and the
        newest submit is younger than GATHER_S (GATHER_CAP_S at the most),
        wait for the next and admit it. What waits but cannot be admitted
        (no lane, no blocks) ends the wait: a tick frees both."""
        if any(st is not None and st.tokens for st in self._slots):
            return
        cap = time.monotonic() + GATHER_CAP_S
        while True:
            now = time.monotonic()
            until = min(self._last_submit + GATHER_S, cap)
            if now >= until:
                return
            with self._cond:
                if not self._running:
                    return
                if not self._total_pending():
                    with obs_trace.span("serve.gather"):
                        self._cond.wait(timeout=until - now)
            if not self._admit_pending() and self._total_pending():
                return

    def _sweep(self) -> None:
        """The head of a worker pass: expire what has outlived its
        deadline, in a lane or in the queue, and adopt handed-off prefix
        blocks."""
        now = time.monotonic()
        if self._undelivered is not None and any(
                st is not None and st.deadline < now for st in self._slots):
            self._deliver()     # a lane's last tokens before its timeout
        with self._cond:
            now = time.monotonic()
            for i in range(self.lanes):
                st = self._slots[i]
                if st is not None and st.deadline < now:
                    if not st.future.done():
                        self.stats.record_timeout()
                        st.future.set_exception(RequestTimeoutError(
                            "generation exceeded its deadline"))
                    self._release_lane(i)
            for name, q in self._pending.items():
                alive = deque()
                for req in q:
                    if req.deadline < now and not req.future.done():
                        self.stats.record_timeout()
                        req.future.set_exception(RequestTimeoutError(
                            "generation request expired in queue"))
                    else:
                        alive.append(req)
                self._pending[name] = alive
        # adopt handed-off prefix blocks BEFORE admissions so a
        # request admitted in this same pass hits them (the
        # prefill/decode disaggregation import path)
        while True:
            with self._cond:
                item = self._imports.popleft() if self._imports \
                    else None
            if item is None:
                break
            self._apply_import(*item)

    def _admit_one(self) -> bool:
        """Pick, book and prefill ONE request; False when the pass is
        over (nothing admissible, or the arena died with the prefill).
        The ``serve.admit`` span runs from the pick to the dispatch's
        return; less its child ``serve.admit.dispatch`` (upload and
        dispatch) it is the booking under the lock. The prefill is NOT
        waited for: its device time shows in the next tick's wait, which
        is why ``serve.batch`` counts the admissions ahead of it."""
        if not self._total_pending():
            # an unlocked look, so that a pass with nothing to admit opens
            # no span: a request that lands now is picked by the next pass
            return False
        with obs_trace.span("serve.admit") as sp:
            with self._cond:
                picked = self._pick_admission()
            if picked is None:
                sp.discard()
                return False
            i, buf, width, write_table, inserts = picked
            lane = self._slots[i]
            tr = lane.trace
            if tr is not None:
                fresh = int(np.count_nonzero(
                    write_table if write_table.ndim == 1
                    else write_table[0]))
                sp.set_parent(tr.parent)
                for key, value in (
                        ("rid", tr.rid), ("lane", i),
                        ("prompt_tokens", int(lane.window.size)),
                        ("width", width),
                        ("hit_blocks", lane.n_table - fresh),
                        ("lookup_blocks", lane.n_table - 1),
                        ("fresh_blocks", fresh)):
                    sp.set_attr(key, value)
                if self.needs.state:
                    sp.set_attr("scan_chunks", self.cfg.scan_chunks(width))
                if hasattr(self.cfg, "admit_attend"):
                    sp.set_attr("attend", self.cfg.admit_attend(width))
                if self._moe_rows:
                    sp.set_attr("moe_rows",
                                int(lane.window.size) * self._moe_rows)
            try:
                if self._chaos is not None:
                    self._chaos.on_admit()
                with obs_trace.span("serve.admit.dispatch", width=width):
                    self._admit_prefill(i, buf, width, write_table)
            except Exception as e:  # noqa: BLE001 — lane isolation boundary
                # a crashed admission evicts ONLY its own lane and
                # returns its blocks to the free list; the prefill
                # wrote (at most) trash + this lane's private
                # blocks, so co-residents' tokens are untouched
                # (the PR 8 crash-eviction contract carried onto
                # the paged pool)
                with self._cond:
                    st = self._slots[i]
                    self._release_lane(i)
                    self._cond.notify_all()
                if st is not None and not st.future.done():
                    st.future.set_exception(e)
                self.stats.record_slot_crash()
                if self._arena_deleted():
                    # the DONATED admit died mid-execution and took
                    # the arena with it: co-resident KV is gone, so
                    # honest failure beats silently garbage tokens
                    self._fail_active_lanes(e)
                    return False
                return True
            self._admits += 1
            self._admit_width_sum += width
            with self._cond:
                for digest, block in inserts:
                    self._prefix.insert(digest, block)
            return True

    def _tick_phase(self) -> bool:
        """One scheduling decision + device tick + host unpack (the tail
        of the worker iteration, factored out so serving/speculate.py can
        interpose its draft-verify round). Returns False only when the
        worker should exit (stopped and idle)."""
        with obs_trace.span("serve.tick.plan") as sp_plan, self._cond:
            self.stats.set_queue_depth(self._total_pending(), "decode")
            active = [i for i in range(self.lanes)
                      if self._slots[i] is not None]
            self.peak_active = max(self.peak_active, len(active))
            if not active:
                sp_plan.discard()   # nothing to plan; the wait has a name
                if not self._running:
                    return False
                if self._undelivered is None:
                    with obs_trace.span("serve.idle"):
                        self._cond.wait()
                    return True
            # adaptive k (ISSUE 16): a literal drop to 1 — never an
            # intermediate clamp — so only the k=1 and k=tick_k
            # programs ever compile. Pending admissions must not
            # wait out a long tick, and a lane within k tokens of
            # its budget (or of max_len) must finish at the exact
            # boundary it would under k=1 scheduling.
            k = self.tick_k
            if k > 1:
                if self._total_pending():
                    k = 1
                else:
                    for i in active:
                        st = self._slots[i]
                        if (st.remaining < k
                                or int(self._pos[i]) + k
                                > self.cfg.max_len - 1):
                            k = 1
                            break
            for i in range(self.lanes):
                if self._slots[i] is not None:
                    self._grow(i, lookahead=k - 1)
            active = [i for i in range(self.lanes)
                      if self._slots[i] is not None]
        if not active:
            # nothing goes to the device in this pass, so nothing hides
            # the last tick's delivery: it goes out now (the pool emptied
            # under a sweep or a preemption), and the next pass waits
            self._deliver()
            return True
        # one fixed-shape device tick for the whole pool (no lock
        # held): k scanned steps per dispatch, tokens [S, k]; the
        # serve.batch span joins the request spans the engine
        # opened (PR 7 tracer). The prefills dispatched since the last
        # tick run on the device ahead of this one: its wait absorbs them
        admits, width_sum = self._admits, self._admit_width_sum
        self._admits = self._admit_width_sum = 0
        kv = self._kv_counts(active, k) if obs_trace.obs_enabled() else {}
        try:
            with obs_trace.span("serve.batch", kind="decode.paged",
                                lanes=len(active), tick_k=k, admits=admits,
                                admit_width_sum=width_sum,
                                **kv) as sp_tick:
                with obs_trace.span("serve.tick.stage"):
                    # the inputs' build and upload, then the dispatch
                    # alone, inside which the program goes onto the
                    # device's queue
                    with obs_trace.span("serve.tick.upload"):
                        tables = self._tables \
                            if len(self._group_tables) == 1 \
                            else np.stack(self._group_tables)
                        inputs = (jnp.asarray(self._tok),
                                  jnp.asarray(self._pos),
                                  jnp.asarray(tables),
                                  jnp.asarray(self._keys),
                                  jnp.asarray(self._temps))
                    with obs_trace.span("serve.tick.dispatch"):
                        self._arena, nxt, keys, *more = self._tick_fn(k)(
                            self._infer_params, self._arena, *inputs)
                    del inputs
                # the device has its next program: the last tick's tokens
                # go to their clients under it
                self._deliver()
                with obs_trace.span("serve.tick.wait"):
                    nxt = np.asarray(nxt)
                    if more and kv:
                        # the tick is done: its count of the experts that
                        # got a live lane's row is there with its tokens
                        sp_tick.set_attr("moe_experts_hit", int(more[0]))
        except Exception as e:  # noqa: BLE001 — device boundary
            self._deliver()     # tokens the last tick gave come first
            self._fail_active_lanes(e)
            return True
        with obs_trace.span("serve.tick.emit", tick=sp_tick.span_id):
            self._emit(active, k, nxt, keys)
            # the tick's last device output dies HERE, inside the span:
            # freeing a device array lets go of the GIL, and where the
            # tokens went out at once the streaming threads they woke
            # take their turn (2 ms at 31 lanes) before the worker runs on
            del keys
        return True

    def _emit(self, active: List[int], k: int, nxt: np.ndarray,
              keys) -> None:
        """The host's share of a tick after the readback: unpack the
        [lanes, k] tokens and book them; the streaming callbacks and the
        futures of finished lanes are _deliver's, at once where nothing
        follows on the device (the pool emptied and nobody waits) or
        the decoder does not defer, else once the next program is on it:
        waking one streaming thread a lane costs the worker the
        interpreter for milliseconds, which the device then idles."""
        with obs_trace.span("serve.tick.read_keys"):
            self._keys = np.array(keys)  # writable copy (admits write rows)
        self.dispatch_stats.decode_ticks += 1
        self.dispatch_stats.decode_tokens += len(active) * k
        callbacks = []
        completions = []
        with self._cond:
            for i in active:
                st = self._slots[i]
                if st is None:
                    continue
                # host-side unpack of the k-vector: per-token
                # bookkeeping and streaming callbacks fire k times,
                # in emission order, exactly as k=1 ticks would
                for j in range(k):
                    t = int(nxt[i, j])
                    st.tokens.append(t)
                    self._tok[i] = t
                    self._pos[i] += 1
                    st.remaining -= 1
                    self.stats.record_tokens(1)
                    if st.on_token is not None:
                        callbacks.append((st.on_token, t))
                    if (st.remaining <= 0
                            or self._pos[i] >= self.cfg.max_len - 1):
                        completions.append(st)
                        self._release_lane(i)
                        break
                else:
                    if self.needs.windowed:
                        self._trim(i)
            follows = self.defer_delivery and (
                self._total_pending() > 0
                or any(st is not None for st in self._slots))
            self._cond.notify_all()  # drain() waiters see evictions
        self._undelivered = (callbacks, completions)
        if not follows:
            self._deliver()

    def _deliver(self) -> None:
        """Hand the last tick's tokens to their clients and resolve the
        futures of the lanes it finished (worker thread only; nothing to
        do where nothing is kept back). Stream callbacks BEFORE resolving
        futures (a client iterating tokens must see the last token before
        done), and outside the lock (a slow client must not stall the
        pool)."""
        if self._undelivered is None:
            return
        callbacks, completions = self._undelivered
        self._undelivered = None
        if not callbacks and not completions:
            return
        with obs_trace.span("serve.tick.deliver", tokens=len(callbacks)):
            for cb, t in callbacks:
                try:
                    cb(t)
                except Exception:  # noqa: BLE001 — client callback boundary
                    pass
            for st in completions:
                if not st.future.done():
                    st.future.set_result(np.asarray(st.tokens, np.int32))
                    self.stats.record_latency(
                        time.monotonic() - st.enqueued)
