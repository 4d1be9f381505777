"""Mesh-sharded inference plane: tensor-parallel decode over a sharded
KV arena (ISSUE 18).

The reference's whole reason to exist was scaleout (Spark parameter
averaging, Akka state tracking — SURVEY §2), and this repo already
proves TP training on the virtual mesh (parallel/tensor_parallel.py).
This module carries the same story into SERVING: the paged /generate
tick (serving/paged.py) runs under shard_map on a dedicated serving
mesh, with attention heads and the block arena sharded over the
``model`` axis — models whose KV pressure outgrows one chip's HBM keep
the entire PR 11–16 scheduling contract.

Sharding scheme — chosen for the BYTE-identity bar, not peak FLOPs:

  * q/k/v projections are COLUMN-parallel: each device slices its own
    head-columns out of the REPLICATED weights at trace time
    (parallel/tensor_parallel.local_head_columns — exact, because every
    output column of ``x @ W`` is an independent dot product; no float
    sum is split).
  * attention is per-head independent (the scores einsum contracts only
    head_dim; softmax and the weighted-V sum run per head), so each
    device computes its local ``H/d`` heads bit-for-bit as the dense
    program would.
  * the head outputs are reassembled with ``lax.all_gather(tiled=True)``
    — a CONCATENATION in axis-index order, not a reduction — and the Wo
    projection, MLP, final LN and logits then run REPLICATED on every
    device over identical operands. This is where we deliberately
    deviate from Megatron's row-parallel Wo (tp_block_apply): its psum
    reorders the output contraction's float sum and would break
    byte-identity with the single-device tick. The price is one
    all_gather of ``[lanes, H, hd]`` per layer and replicated Wo/MLP
    FLOPs — decode is bandwidth-bound at lane counts this plane serves,
    and what the mesh buys is KV CAPACITY: the arena head-shards, so
    per-device block bytes drop to 1/d (ops/memory.kv_block_bytes
    ``devices=``) and the same per-device HBM budget admits ~d× blocks.

  * arena: the global ``[L, n_blocks+1, bt, H*hd]`` buffers (a token's
    heads side by side, paged.py's one stored layout) shard on that
    last axis (ARENA_SPEC), where a device's heads are contiguous; each
    device owns a local ``[L, n_blocks+1, bt, (H/d)*hd]`` pool including
    its own slice of every layer's trash block 0. Block tables,
    tok/pos/keys/temps and params are
    replicated, so every device executes the identical scatter indices
    — write-then-gather and the zero-retrace contract survive
    unchanged, and ALL host-side scheduling (BlockArena, PrefixCache,
    admission, preemption, SLO classes, crash eviction, streaming) is
    inherited from PagedDecoder byte-compatibly.
  * admission prefill runs the full-window program REPLICATED inside
    the shard_map body (identical scalar program per device — GSPMD
    never gets a chance to repartition it), then each device scatters
    only its local head-slice of the resulting blocks.

Gates (the ``_reject_lowprec`` discipline — loud, never a silent dense
fallback): ``DL4J_TPU_SERVE_KV_DTYPE=bf16`` and ``DL4J_TPU_SERVE_SPEC``
both raise at decoder build; ``n_heads % devices != 0`` raises. The
sharded tick attends through the single device's own entry
(ops/pallas_paged.decode_attention over the local heads: the kernel on a
chip, in the chunks of the whole row, and the chunked gather loop
elsewhere).

Prefill/decode disaggregation rides the PagedDecoder half of this PR:
``export_prefix``/``import_prefix`` (serving/paged.py) hand
content-addressed KV blocks between a prefill-role and a decode-role
replica; serving/router.py routes /generate by the role published in
the replica-<id>.addr JSON.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    _ln,
    prefill_cache,
)
from deeplearning4j_tpu.ops import dispatch
from deeplearning4j_tpu.ops import env as envknob
from deeplearning4j_tpu.ops import memory as opsmem
from deeplearning4j_tpu.ops import lowprec
from deeplearning4j_tpu.ops.pallas_paged import decode_attention
from deeplearning4j_tpu.parallel.mesh import MODEL_AXIS, device_mesh
from deeplearning4j_tpu.parallel.tensor_parallel import local_head_columns
from deeplearning4j_tpu.serving.decode import _sample_step
from deeplearning4j_tpu.serving.paged import (
    PagedDecoder,
    refuse_window,
)

# the arena's k/v buffers shard on their last axis (dim 3 of
# [L, n_blocks+1, bt, H*hd]: heads side by side, a device's H/d heads
# contiguous); everything else the tick touches is replicated
ARENA_SPEC = P(None, None, None, MODEL_AXIS)


def serve_mesh_devices() -> int:
    """The DL4J_TPU_SERVE_MESH device count (0 = mesh serving off)."""
    return max(0, envknob.get_int("DL4J_TPU_SERVE_MESH", 0))


def serve_role() -> str:
    """The DL4J_TPU_SERVE_ROLE replica role ('' = both)."""
    role = envknob.get_str("DL4J_TPU_SERVE_ROLE", "").strip().lower()
    return role if role in ("", "prefill", "decode") else ""


def serving_mesh(devices: int) -> Mesh:
    """A 1-D ``model``-axis mesh over the first ``devices`` devices —
    resolved lazily at decoder build, never at import."""
    return device_mesh(num_devices=int(devices), axis_names=(MODEL_AXIS,))


def mesh_paged_decode_step(params, arena, tok, pos, tables,
                           cfg: TransformerConfig, n_devices: int,
                           axis: str = MODEL_AXIS):
    """Per-device decode tick body (runs INSIDE shard_map): the
    head-local mirror of paged.paged_decode_step, byte-for-byte per
    head. ``arena`` k/v arrive as local shards [L, B, bt, (H/d)*hd]
    and are carried through the layer scan as [L * B, bt, (H/d)*hd],
    layer ``l`` at rows ``l * B + block``, as the single device's are;
    params and every index input are replicated, so the scatter/gather
    indices are identical on all devices."""
    cdt = cfg.compute_dtype
    s = tok.shape[0]
    hd = cfg.d_model // cfg.n_heads
    hl = cfg.n_heads // n_devices
    n_layers, rows, bt, width = arena["k"].shape
    h = (params["embed"][tok] + params["pos"][pos])[:, None, :].astype(cdt)
    wb = jnp.take_along_axis(tables, (pos // bt)[:, None], axis=1)[:, 0]
    off = pos % bt

    def block(carry, xs):
        h, ck, cv = carry  # ck/cv: the local arena [L * B, bt, (H/d)*hd]
        bp, base = xs      # base: the layer's first row, l * B
        c = lambda a: a.astype(cdt)
        x = _ln(h, c(bp["ln1_g"]), c(bp["ln1_b"]))
        # column-parallel q/k/v over the replicated weights: exact —
        # (x @ W)[:, cols] == x @ W[:, cols] element-for-element
        q = (x @ local_head_columns(
            c(bp["Wq"]), num_heads=cfg.n_heads, head_dim=hd,
            n_devices=n_devices, axis=axis)).reshape(s, hl, hd)
        k1 = (x @ local_head_columns(
            c(bp["Wk"]), num_heads=cfg.n_heads, head_dim=hd,
            n_devices=n_devices, axis=axis)).reshape(s, width)
        v1 = (x @ local_head_columns(
            c(bp["Wv"]), num_heads=cfg.n_heads, head_dim=hd,
            n_devices=n_devices, axis=axis)).reshape(s, width)
        ck = ck.at[base + wb, off].set(k1.astype(ck.dtype))
        cv = cv.at[base + wb, off].set(v1.astype(cv.dtype))
        # per-head attention over the LOCAL arena shard — the single
        # device's own function (pallas_paged.decode_attention), just over
        # H/d heads: per-head math is device-independent (the products
        # contract hd/T only and the running softmax is per head), pos is
        # replicated, and the kernel's chunk follows the whole row's bytes
        # (shards), so every device folds the same chunks one device would
        att_l = decode_attention(q, ck, cv, tables + base, pos,
                                 shards=n_devices)
        # reassemble the full [S, H, hd] head outputs by CONCATENATION
        # (axis-index order == head order) — not a psum: Megatron's
        # row-parallel Wo would reorder the contraction's float sum and
        # break byte-identity with the single-device tick. Wo, the MLP
        # and everything downstream run replicated over identical
        # operands.
        att = lax.all_gather(att_l, axis, axis=1, tiled=True)
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


# jitted sharded programs shared across decoder instances (the
# _PAGED_TICK_CACHE discipline); the Mesh rides the key — two decoders
# on the same device set share programs, different widths don't
_MESH_TICK_CACHE: Dict[tuple, object] = {}
_MESH_ADMIT_CACHE: Dict[tuple, object] = {}
_MESH_IMPORT_CACHE: Dict[tuple, object] = {}


def _mesh_tick_for(cfg: TransformerConfig, block_tokens: int, mesh: Mesh,
                   k: int = 1):
    nd = int(mesh.shape[MODEL_AXIS])
    key = (cfg, block_tokens, mesh, int(k))
    fn = _MESH_TICK_CACHE.get(key)
    if fn is not None:
        return fn
    rep = P()

    if k == 1:
        def device_tick(params, arena, tok, pos, tables, keys, temps):
            arena, logits = mesh_paged_decode_step(
                params, arena, tok, pos, tables, cfg, nd)
            nxt, nkeys = _sample_step(logits, keys, temps)
            return arena, nxt[:, None], nkeys
    else:
        # k scanned steps in ONE dispatch, the ISSUE 16 contract carried
        # sharded: the whole scan (sampling included — threefry is
        # deterministic over replicated keys) runs inside the shard_map
        # body, so the k-tick stays byte-equal to k single ticks
        def device_tick(params, arena, tok, pos, tables, keys, temps):
            def step(carry, _):
                arena, tok, pos, keys = carry
                arena, logits = mesh_paged_decode_step(
                    params, arena, tok, pos, tables, cfg, nd)
                nxt, keys = _sample_step(logits, keys, temps)
                return (arena, nxt, pos + 1, keys), nxt

            (arena, _, _, keys), toks = lax.scan(
                step, (arena, tok, pos, keys), None, length=k)
            return arena, jnp.swapaxes(toks, 0, 1), keys

    sharded = jax.shard_map(
        device_tick, mesh=mesh,
        in_specs=(rep, ARENA_SPEC, rep, rep, rep, rep, rep),
        out_specs=(ARENA_SPEC, rep, rep),
        # the replication of the post-all_gather outputs is by
        # construction (identical replicated operands), which the
        # static rep-checker cannot see
        check_vma=False)
    tick = dispatch.arena_jit(sharded, donate=(1,))
    _MESH_TICK_CACHE[key] = tick
    return tick


def _mesh_admit_for(cfg: TransformerConfig, width: int, block_tokens: int,
                    mesh: Mesh):
    nd = int(mesh.shape[MODEL_AXIS])
    key = (cfg, width, block_tokens, mesh)
    fn = _MESH_ADMIT_CACHE.get(key)
    if fn is not None:
        return fn
    m = cfg.max_len // block_tokens
    wl = cfg.d_model // nd  # a device's heads, side by side

    def device_admit(params, arena, window, write_table):
        # the FULL prefill runs replicated on every device — the
        # identical scalar program the dense admit jits, so the block
        # bytes each device scatters are exactly the dense program's
        # head-slice; only the scatter is head-local
        c1, _ = prefill_cache(params, window, cfg)
        kb = c1["k"][:, 0].reshape(cfg.n_layers, m, block_tokens,
                                   cfg.d_model)
        vb = c1["v"][:, 0].reshape(cfg.n_layers, m, block_tokens,
                                   cfg.d_model)
        idx = lax.axis_index(MODEL_AXIS)
        kb = lax.dynamic_slice_in_dim(kb, idx * wl, wl, axis=3)
        vb = lax.dynamic_slice_in_dim(vb, idx * wl, wl, axis=3)
        ak = arena["k"].at[:, write_table].set(kb.astype(arena["k"].dtype))
        av = arena["v"].at[:, write_table].set(vb.astype(arena["v"].dtype))
        return {"k": ak, "v": av}

    sharded = jax.shard_map(
        device_admit, mesh=mesh,
        in_specs=(P(), ARENA_SPEC, P(), P()),
        out_specs=ARENA_SPEC,
        check_vma=False)
    admit = dispatch.arena_jit(sharded, donate=(1,))
    _MESH_ADMIT_CACHE[key] = admit
    return admit


def _mesh_import_for(cfg: TransformerConfig, block_tokens: int,
                     table_width: int, mesh: Mesh):
    nd = int(mesh.shape[MODEL_AXIS])
    key = (cfg, block_tokens, int(table_width), mesh)
    fn = _MESH_IMPORT_CACHE.get(key)
    if fn is not None:
        return fn
    wl = cfg.d_model // nd

    def device_imp(arena, kb, vb, table):
        # handed-off blocks arrive dense [L, tw, bt, H*hd]; each device
        # adopts its heads' columns (unadopted entries scatter into trash 0)
        idx = lax.axis_index(MODEL_AXIS)
        kb = lax.dynamic_slice_in_dim(kb, idx * wl, wl, axis=3)
        vb = lax.dynamic_slice_in_dim(vb, idx * wl, wl, axis=3)
        ak = arena["k"].at[:, table].set(kb.astype(arena["k"].dtype))
        av = arena["v"].at[:, table].set(vb.astype(arena["v"].dtype))
        return {"k": ak, "v": av}

    sharded = jax.shard_map(
        device_imp, mesh=mesh,
        in_specs=(ARENA_SPEC, P(), P(), P()),
        out_specs=ARENA_SPEC,
        check_vma=False)
    fn = dispatch.arena_jit(sharded, donate=(0,))
    _MESH_IMPORT_CACHE[key] = fn
    return fn


class MeshPagedDecoder(PagedDecoder):
    """PagedDecoder whose device programs run sharded over a serving
    mesh (module docstring above for the scheme). Every host-side
    contract — admission, eviction, prefix cache, SLO classes,
    preemption, streaming, k-ticks, crash isolation — is inherited
    unchanged: the subclass only swaps the program builders and the
    arena/params placement, so scheduler behavior is byte-compatible by
    construction and the TICK is byte-identical by the
    no-reduction-reordered argument (tests/test_serving_mesh.py pins
    it across the whole paged contract matrix)."""

    def __init__(self, lm, *, devices: Optional[int] = None,
                 mesh: Optional[Mesh] = None, **kw) -> None:
        cfg = getattr(lm, "_run_cfg", None)
        if cfg is None:
            raise ValueError(
                "MeshPagedDecoder needs a run-configured TransformerLM "
                "(call lm.init/run setup first)")
        if mesh is None:
            nd = int(devices) if devices is not None \
                else serve_mesh_devices()
            if nd < 2:
                raise ValueError(
                    f"DL4J_TPU_SERVE_MESH={nd} cannot shard the serving "
                    "tick: a mesh needs >= 2 devices (single-device "
                    "serving is PagedDecoder's job)")
            mesh = serving_mesh(nd)
        if opsmem.cache_needs(cfg).state:
            raise ValueError(
                "the serving mesh (DL4J_TPU_SERVE_MESH) cannot carry the "
                "per-lane recurrent state this model keeps: not "
                "implemented for models with recurrent layers")
        if opsmem.cache_needs(cfg).windowed:
            raise ValueError(refuse_window(
                "the serving mesh (DL4J_TPU_SERVE_MESH)"))
        self.serving_mesh = mesh
        nd = int(mesh.shape[MODEL_AXIS])
        if nd < 2:
            raise ValueError(
                f"serving mesh has {nd} device(s) on axis "
                f"{MODEL_AXIS!r}; need >= 2")
        # instance attr shadows the PagedDecoder class default (1) so
        # the base ctor's kv_arena_blocks auto-sizing and kv_capacity's
        # mesh_devices stamp see the mesh width
        self.mesh_devices = nd
        self._arena_sharding = NamedSharding(mesh, ARENA_SPEC)
        if cfg.n_heads % nd:
            raise ValueError(
                f"n_heads {cfg.n_heads} is not divisible by the serving "
                f"mesh width {nd}; head-sharding needs an even split")
        # loud lowprec gates (ISSUE 18 satellite): composition that
        # would silently change bytes REJECTS at build — never a quiet
        # fallback to the dense path (the _reject_lowprec discipline)
        if jnp.dtype(lowprec.kv_dtype(cfg)) != jnp.dtype(cfg.compute_dtype):
            raise ValueError(
                "DL4J_TPU_SERVE_KV_DTYPE does not compose with "
                "DL4J_TPU_SERVE_MESH: the sharded tick's byte-identity "
                "contract is proven at the compute dtype; unset one of "
                "them")
        if lowprec.spec_mode():
            raise ValueError(
                "DL4J_TPU_SERVE_SPEC does not compose with "
                "DL4J_TPU_SERVE_MESH: the speculative draft/verify "
                "round runs dense per-lane caches; unset one of them")
        super().__init__(lm, **kw)

    def _start_worker(self) -> None:
        # replicate params ONCE onto the serving mesh before the decode
        # thread goes live: every device runs identical scalar programs
        # over them (projections column-slice at trace time), so the
        # placement is P() for the whole tree — one HBM copy per device,
        # no resharded second tree
        self._infer_params = jax.device_put(
            self._infer_params, NamedSharding(self.serving_mesh, P()))
        super()._start_worker()

    def _build_tick(self, k: int):
        return _mesh_tick_for(self.cfg, self.block_tokens,
                              self.serving_mesh, k)

    def _build_admit(self, width: int):
        return _mesh_admit_for(self.cfg, width, self.block_tokens,
                               self.serving_mesh)

    def _build_import(self):
        return _mesh_import_for(self.cfg, self.block_tokens,
                                self.table_width, self.serving_mesh)
