"""Continuous-batching LM decode: a fixed slot pool over the KV cache.

``TransformerLM.generate`` decodes a STATIC batch: every sequence in the
call runs for the same n_new steps inside one lax.scan, so a batch's wall
time is its slowest member and a new prompt waits for the whole batch to
drain — the serving-side analog of the reference's one-record route, just
one level up. Continuous batching (the vLLM/Orca scheduling idea, applied
to this repo's own decode_step — models/transformer.py:710) fixes the
shape problem the TPU way: the DEVICE program stays a fixed-shape
single-token step over S slots (zero retrace after the first tick), and
all scheduling is host-side bookkeeping between ticks:

  * each slot holds one sequence's KV-cache rows + position;
  * a finished sequence (its n_new reached) is evicted at the tick
    boundary and its Future resolved;
  * a queued prompt is admitted into the freed slot MID-LOOP via a
    prefill that writes only that slot's cache rows.

Per-slot math is row-independent (attention reads only the slot's own
cache rows; sampling uses a per-slot PRNG key), so a sequence's tokens do
not depend on which other sequences share the pool — locked by
tests/test_serving.py (staggered == solo), the serving twin of the
distributed==serial convention.

Prompt widths are padded up to the shared bucket ladder
(ops/dispatch.bucket_size) so prefill compiles O(log max_len) programs;
pad positions carry garbage K/V that the ``arange <= pos`` decode mask
never reads before they are overwritten (same argument as
models/transformer.prefill_cache's right-padding).

Dense single-device models only: MoE routing is batch-dependent
(capacity groups) and mesh-sharded models decode through ring/GSPMD paths
— the engine falls back to ``lm.generate`` for those.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    _ln,
    prefill_cache,
)
from deeplearning4j_tpu.obs.registry import register_net
from deeplearning4j_tpu.ops import dispatch
from deeplearning4j_tpu.ops import env as envknob
from deeplearning4j_tpu.ops import memory as opsmem
from deeplearning4j_tpu.serving.batcher import RequestTimeoutError
from deeplearning4j_tpu.serving.resilience import WorkerDeadError
from deeplearning4j_tpu.serving.telemetry import ServingStats


def decode_step_slots(params, cache, tok, pos, cfg: TransformerConfig):
    """One decode tick with PER-SLOT positions: tok [S] int32, pos [S]
    int32 -> (updated cache, logits [S, V]).

    The vectorized-pos variant of models/transformer.decode_step (:710):
    the scalar ``pos`` becomes a vector, the cache write becomes a
    per-slot one-hot select, and the causal mask becomes ``arange <=
    pos[:, None]``. With all slots at the same position the two are
    numerically identical (tests/test_serving.py locks this), which is
    what makes the continuous loop an equivalence-preserving rearrangement
    of the static decode rather than a new code path."""
    cdt = cfg.compute_dtype
    s = tok.shape[0]
    hd = cfg.d_model // cfg.n_heads
    h = (params["embed"][tok] + params["pos"][pos])[:, None, :].astype(cdt)
    scale = 1.0 / float(np.sqrt(hd))
    t_idx = jnp.arange(cfg.max_len)[None, :]          # [1, T]
    visible = t_idx <= pos[:, None]                   # [S, T]
    write = (t_idx == pos[:, None])[:, :, None, None]  # [S, T, 1, 1]

    def block(h, xs):
        bp, ck, cv = xs  # ck/cv: [S, T_max, H, hd]
        c = lambda a: a.astype(cdt)
        x = _ln(h, c(bp["ln1_g"]), c(bp["ln1_b"]))
        q = (x @ c(bp["Wq"])).reshape(s, cfg.n_heads, hd)
        k1 = (x @ c(bp["Wk"])).reshape(s, 1, cfg.n_heads, hd)
        v1 = (x @ c(bp["Wv"])).reshape(s, 1, cfg.n_heads, hd)
        ck = jnp.where(write, k1.astype(ck.dtype), ck)
        cv = jnp.where(write, v1.astype(cv.dtype), cv)
        sc = jnp.einsum("nhd,nthd->nht", q.astype(jnp.float32),
                        ck.astype(jnp.float32)) * scale
        sc = jnp.where(visible[:, None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        att = jnp.einsum("nht,nthd->nhd", p,
                         cv.astype(jnp.float32)).reshape(s, 1, cfg.d_model)
        h = h + att.astype(cdt) @ c(bp["Wo"])
        x = _ln(h, c(bp["ln2_g"]), c(bp["ln2_b"]))
        h = h + jax.nn.gelu(x @ c(bp["W1"]) + c(bp["b1"])) @ c(bp["W2"]) \
            + c(bp["b2"])
        return h, (ck, cv)

    h, (ks, vs) = lax.scan(block, h, (params["blocks"], cache["k"],
                                      cache["v"]))
    h = _ln(h[:, 0].astype(jnp.float32), params["lnf_g"], params["lnf_b"])
    return {"k": ks, "v": vs}, h @ params["embed"].T


# jitted decode programs shared across decoder instances: cfg is a frozen
# (hashable) dataclass, and a per-instance @jax.jit closure would pay a
# fresh XLA compile every time an engine (re)builds its decoder — exactly
# the cost class this subsystem exists to amortize. k (tokens per tick,
# ISSUE 16) rides the cache key like a config field: the adaptive worker
# only ever asks for k=1 and k=tick_k, so at most two programs exist.
_TICK_CACHE: Dict[tuple, object] = {}
_ADMIT_CACHE: Dict[tuple, object] = {}


def _sample_step(logits, keys, temps):
    """Shared per-step sampler: per-slot key split + temperature select.
    Factored out so the k=1 direct tick and the k>1 scanned tick run the
    IDENTICAL op sequence — the byte-identity contract between them
    (tests/test_speculate.py) rests on this body being shared."""
    split = jax.vmap(jax.random.split)(keys)   # [S, 2, 2]
    nkeys, subs = split[:, 0], split[:, 1]
    tempered = logits / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(subs, tempered)
    greedy = jnp.argmax(logits, axis=-1)
    nxt = jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)
    return nxt, nkeys


def _tick_for(cfg: TransformerConfig, k: int = 1):
    """k decode steps inside ONE jitted dispatch -> tokens [S, k].

    k=1 keeps the original direct body (reshaped to [S, 1] so the host
    unpack is uniform); k>1 wraps the same body in lax.scan carrying
    (cache, tok, pos, keys) — one dispatch amortizes the fixed
    per-dispatch overhead (not measured on the attached chip) over k
    tokens. Scheduling stays per-token:
    the WORKER decides k each iteration (adaptive drop to 1), the
    program just executes it."""
    key = (cfg, int(k))
    fn = _TICK_CACHE.get(key)
    if fn is not None:
        return fn

    if k == 1:
        @jax.jit
        def tick(params, cache, tok, pos, keys, temps):
            cache, logits = decode_step_slots(params, cache, tok, pos, cfg)
            nxt, nkeys = _sample_step(logits, keys, temps)
            return cache, nxt[:, None], nkeys
    else:
        @jax.jit
        def tick(params, cache, tok, pos, keys, temps):
            def step(carry, _):
                cache, tok, pos, keys = carry
                cache, logits = decode_step_slots(params, cache, tok, pos,
                                                  cfg)
                nxt, keys = _sample_step(logits, keys, temps)
                return (cache, nxt, pos + 1, keys), nxt

            (cache, _, _, keys), toks = lax.scan(
                step, (cache, tok, pos, keys), None, length=k)
            return cache, jnp.swapaxes(toks, 0, 1), keys

    _TICK_CACHE[key] = tick
    return tick


def _admit_for(cfg: TransformerConfig, width: int):
    key = (cfg, width)
    fn = _ADMIT_CACHE.get(key)
    if fn is not None:
        return fn

    @jax.jit
    def admit(params, cache, window, slot):
        # window: [1, width]; prefill pads its K/V out to max_len
        c1, _ = prefill_cache(params, window, cfg)
        k = lax.dynamic_update_slice_in_dim(
            cache["k"], c1["k"].astype(cache["k"].dtype), slot, axis=1)
        v = lax.dynamic_update_slice_in_dim(
            cache["v"], c1["v"].astype(cache["v"].dtype), slot, axis=1)
        return {"k": k, "v": v}

    _ADMIT_CACHE[key] = admit
    return admit


class _Slot:
    __slots__ = ("future", "tokens", "remaining", "deadline", "enqueued")

    def __init__(self, future: Future, remaining: int, deadline: float,
                 enqueued: float) -> None:
        self.future = future
        self.tokens: list = []
        self.remaining = remaining
        self.deadline = deadline
        self.enqueued = enqueued


class _PendingGen:
    __slots__ = ("prompt", "n_new", "temperature", "seed", "future",
                 "deadline", "enqueued")

    def __init__(self, prompt, n_new, temperature, seed, deadline) -> None:
        self.prompt = prompt
        self.n_new = n_new
        self.temperature = temperature
        self.seed = seed
        self.future: Future = Future()
        self.deadline = deadline
        self.enqueued = time.monotonic()


class ContinuousDecoder:
    """Continuous-batching /generate engine over a TransformerLM.

    Per-request sampling controls: ``temperature`` (a traced per-slot
    vector — sweeping it never recompiles; <= 0 means greedy argmax) and
    ``seed`` (a per-slot PRNG key stream, so a request's sample is a
    function of its own seed, not of pool scheduling). Static top_k/top_p
    filtering stays on the ``lm.generate`` path (the filters are
    per-call-compiled there; the engine routes filtered requests to it).
    """

    def __init__(self, lm, slots: int = 4,
                 stats: Optional[ServingStats] = None,
                 default_timeout_s: float = 300.0,
                 chaos=None, tick_k: Optional[int] = None) -> None:
        cfg = lm._run_cfg
        if lm.mesh is not None:
            raise ValueError("continuous decode needs a single-device LM "
                             "(mesh-sharded models generate via ring/GSPMD)")
        if opsmem.cache_needs(cfg).state:
            raise ValueError(
                "the fixed-slot pool (DL4J_TPU_SERVE_KV_BLOCK=0) holds keys "
                "and values only: not implemented for models with "
                "recurrent layers")
        if hasattr(cfg, "paged_decode_step"):
            raise ValueError(
                "the fixed-slot pool (DL4J_TPU_SERVE_KV_BLOCK=0) steps the "
                "GPT-2-shaped TransformerLM alone: a model that brings its "
                "own tick is served by the paged pool")
        if cfg.moe_experts:
            raise ValueError(
                "continuous decode does not serve capacity-routed experts "
                "(a token dropped past an expert's capacity makes a slot's "
                "output depend on its batch)")
        self.lm = lm
        self.cfg = cfg
        self.slots = int(slots)
        self.stats = stats if stats is not None else ServingStats()
        self.default_timeout_s = float(default_timeout_s)
        L, H = cfg.n_layers, cfg.n_heads
        hd = cfg.d_model // H
        zeros = jnp.zeros((L, self.slots, cfg.max_len, H, hd),
                          cfg.compute_dtype)
        self._cache = {"k": zeros, "v": zeros}
        self._tok = np.zeros((self.slots,), np.int32)
        self._pos = np.zeros((self.slots,), np.int32)
        self._temps = np.ones((self.slots,), np.float32)
        # np.array (not asarray): jax array views are read-only and the
        # admit path writes per-slot key rows in place
        self._keys = np.array(
            jax.vmap(jax.random.PRNGKey)(jnp.zeros((self.slots,),
                                                   jnp.uint32)))
        self._slots: list = [None] * self.slots
        self._pending: deque = deque()
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._running = True
        # serving resilience (ISSUE 8): deterministic fault injection at
        # slot admission (resilience/chaos.ServingChaos.on_admit) and a
        # dead-worker marker so submit() fast-fails instead of queueing
        # prompts nobody will decode
        self._chaos = chaos
        self._dead: Optional[str] = None
        self.peak_active = 0  # high-water concurrent sequences (bench)
        # multi-token ticks (ISSUE 16): steady-state decode scans tick_k
        # steps per dispatch; the worker adaptively drops to k=1 whenever
        # admissions are pending or any lane is within k tokens of its
        # budget, so scheduling semantics stay per-token
        self.tick_k = max(1, int(
            tick_k if tick_k is not None
            else envknob.get_int("DL4J_TPU_SERVE_TICK_K", 1)))
        # decoder-owned dispatch ledger (TransformerLM carries only
        # memory_stats): decode_ticks / decode_tokens make the
        # amortization win visible at /metrics beside serving_stats
        self.dispatch_stats = dispatch.DispatchStats()
        register_net(self)
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="continuous-decoder")
        self._worker.start()

    def kv_capacity(self) -> Dict[str, object]:
        """/models KV report (the paged pool's richer twin lives on
        PagedDecoder.kv_capacity): the fixed pool pre-allocates every
        slot at max_len, so capacity is slots * max_len regardless of
        what requests actually use — the over-allocation the paged
        arena exists to fix."""
        with self._cond:
            active = [int(self._pos[i]) + 1
                      for i, st in enumerate(self._slots) if st is not None]
        return {
            "scheme": "fixed-slot",
            "slots": self.slots,
            "capacity_tokens": self.slots * self.cfg.max_len,
            "tokens_in_use": sum(active),
            "lanes": self.slots,
        }

    # -- client side ------------------------------------------------------
    def submit(self, prompt, n_new: int, temperature: float = 1.0,
               seed: int = 0,
               timeout_s: Optional[float] = None) -> Future:
        """Queue one prompt ([T] int ids) for n_new sampled tokens; returns
        a Future of the [n_new] int32 continuation."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if n_new < 1 or n_new >= self.cfg.max_len:
            raise ValueError(f"n_new {n_new} must be in [1, max_len)")
        deadline = time.monotonic() + (timeout_s if timeout_s is not None
                                       else self.default_timeout_s)
        req = _PendingGen(prompt, int(n_new), float(temperature), int(seed),
                          deadline)
        self.stats.record_request()
        with self._cond:
            if not self._running:
                raise RuntimeError("decoder is stopped")
            if self._dead is not None:
                raise WorkerDeadError(
                    f"decoder worker died ({self._dead}); prompts would "
                    "queue forever")
            self._pending.append(req)
            self.stats.set_queue_depth(len(self._pending), "decode")
            self._cond.notify_all()
        return req.future

    def generate(self, prompts, n_new: int, temperature: float = 1.0,
                 seed: int = 0,
                 timeout_s: Optional[float] = None) -> np.ndarray:
        """Batch convenience: [N, T] prompts -> [N, n_new] continuations
        (each row an independent request; seeds offset per row so rows
        differ, matching generate()'s per-call-seed contract)."""
        prompts = np.asarray(prompts, np.int32)
        if prompts.ndim == 1:
            prompts = prompts[None]
        futs = [self.submit(row, n_new, temperature=temperature,
                            seed=seed + i, timeout_s=timeout_s)
                for i, row in enumerate(prompts)]
        budget = timeout_s if timeout_s is not None else self.default_timeout_s
        return np.stack([f.result(timeout=budget) for f in futs])

    def stop(self) -> None:
        with self._cond:
            self._running = False
            self._cond.notify_all()
        self._worker.join(timeout=10)
        with self._cond:
            for req in list(self._pending):
                if not req.future.done():
                    req.future.set_exception(RuntimeError("decoder stopped"))
            self._pending.clear()
            for st in self._slots:
                if st is not None and not st.future.done():
                    st.future.set_exception(RuntimeError("decoder stopped"))

    # -- worker side ------------------------------------------------------
    def _admit_bookkeeping(self, slot_idx: int, req: _PendingGen):
        """Cheap host-side slot setup (safe under the lock); returns the
        (buf, width) the device prefill needs. The prefill itself — which
        can be a seconds-long XLA compile on a new width bucket — runs
        OUTSIDE the lock so submit()/stop() never block on it."""
        cfg = self.cfg
        keep = min(req.prompt.size, cfg.max_len - req.n_new)
        window = req.prompt[req.prompt.size - keep:]
        width = min(max(dispatch.bucket_size(keep), keep), cfg.max_len)
        buf = np.zeros((1, width), np.int32)
        buf[0, :keep] = window
        self._tok[slot_idx] = int(window[-1])
        self._pos[slot_idx] = keep - 1  # re-consume the last prompt token
        self._temps[slot_idx] = req.temperature
        self._keys[slot_idx] = np.asarray(jax.random.PRNGKey(req.seed))
        self._slots[slot_idx] = _Slot(req.future, req.n_new, req.deadline,
                                      req.enqueued)
        return buf, width

    def _admit_prefill(self, slot_idx: int, buf: np.ndarray,
                       width: int) -> None:
        self._cache = _admit_for(self.cfg, width)(
            self.lm.params, self._cache, jnp.asarray(buf),
            jnp.asarray(slot_idx, jnp.int32))

    def _run(self) -> None:
        try:
            self._run_inner()
        except Exception as e:  # noqa: BLE001 — worker loop boundary
            # an uncaught error in the decode loop used to kill the
            # worker silently (every active slot and queued prompt then
            # waited out its full deadline). Fail everything with the
            # real cause and mark the decoder dead so submit fast-fails.
            with self._cond:
                self._dead = f"{type(e).__name__}: {e}"
                victims = [st for st in self._slots if st is not None]
                self._slots = [None] * self.slots
                victims.extend(self._pending)
                self._pending.clear()
                # reset the gauge with the queue: a dead decoder must
                # not report the phantom backlog it just failed
                self.stats.set_queue_depth(0, "decode")
                self._cond.notify_all()
            self.stats.record_worker_death()
            err = WorkerDeadError(f"decoder worker died: {self._dead}")
            for v in victims:
                if not v.future.done():
                    v.future.set_exception(err)

    def _fail_active_slots(self, exc: Exception) -> None:
        """Pool-wide device failure (the tick program covers every slot):
        fail each active future with the real cause and free the pool —
        the decoder itself stays alive for fresh traffic."""
        with self._cond:
            victims = [st for st in self._slots if st is not None]
            self._slots = [None] * self.slots
            self._cond.notify_all()
        for st in victims:
            if not st.future.done():
                st.future.set_exception(exc)

    def drain(self, timeout_s: float = 20.0) -> bool:
        """Graceful-drain support (admission is the engine's to stop):
        bounded wait for the pending queue and every slot to empty."""
        deadline = time.monotonic() + max(0.0, float(timeout_s))
        with self._cond:
            while (self._pending or any(st is not None
                                        for st in self._slots)) \
                    and self._dead is None:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cond.wait(timeout=left)
            return self._dead is None

    def _run_inner(self) -> None:
        while True:
            with self._cond:
                now = time.monotonic()
                # evict ACTIVE slots whose deadline passed: the client
                # already got (or will get) a 504 — ticking out the rest
                # of n_new for nobody would hold the slot against queued
                # prompts
                for i in range(self.slots):
                    st = self._slots[i]
                    if st is not None and st.deadline < now:
                        if not st.future.done():
                            self.stats.record_timeout()
                            st.future.set_exception(RequestTimeoutError(
                                "generation exceeded its deadline"))
                        self._slots[i] = None
                # fail pending requests whose deadline passed in queue
                alive = deque()
                for req in self._pending:
                    if req.deadline < now and not req.future.done():
                        self.stats.record_timeout()
                        req.future.set_exception(RequestTimeoutError(
                            "generation request expired in queue"))
                    else:
                        alive.append(req)
                self._pending = alive
                # admission: FIFO prompts into free slots, mid-loop —
                # bookkeeping only here; the device prefill runs below,
                # after the lock is released
                admits = []
                for i in range(self.slots):
                    if self._slots[i] is None and self._pending:
                        req = self._pending.popleft()
                        admits.append((i,) + self._admit_bookkeeping(i, req))
                self.stats.set_queue_depth(len(self._pending), "decode")
                active = [i for i in range(self.slots)
                          if self._slots[i] is not None]
                self.peak_active = max(self.peak_active, len(active))
                if not active:
                    if not self._running:
                        return
                    self._cond.wait()
                    continue
                # adaptive k (ISSUE 16): a literal drop to 1 — never an
                # intermediate clamp — so only the k=1 and k=tick_k
                # programs ever compile. Pending admissions must not wait
                # out a long tick, and a lane within k tokens of its
                # budget (or of max_len) must finish at its exact
                # boundary, token-for-token identical to k=1 scheduling.
                k = self.tick_k
                if k > 1:
                    if self._pending:
                        k = 1
                    else:
                        for i in active:
                            st = self._slots[i]
                            if (st.remaining < k
                                    or int(self._pos[i]) + k
                                    > self.cfg.max_len - 1):
                                k = 1
                                break
            for i, buf, width in admits:
                try:
                    if self._chaos is not None:
                        self._chaos.on_admit()
                    self._admit_prefill(i, buf, width)
                except Exception as e:  # noqa: BLE001 — slot isolation boundary
                    # a crashed admission evicts ONLY its own slot: the
                    # prefill wrote (at most) that slot's cache rows, and
                    # per-slot math is row-independent, so co-residents'
                    # tokens are untouched (the slot-independence
                    # contract, tests/test_serving_resilience.py)
                    with self._cond:
                        st, self._slots[i] = self._slots[i], None
                        self._cond.notify_all()
                    if st is not None and not st.future.done():
                        st.future.set_exception(e)
                    self.stats.record_slot_crash()
                    active = [j for j in active if j != i]
            if not active:
                continue
            # one fixed-shape device tick for the whole pool (no lock
            # held): k scanned steps per dispatch, tokens [S, k]
            try:
                self._cache, nxt, keys = _tick_for(self.cfg, k)(
                    self.lm.params, self._cache, jnp.asarray(self._tok),
                    jnp.asarray(self._pos), jnp.asarray(self._keys),
                    jnp.asarray(self._temps))
                nxt = np.asarray(nxt)
            except Exception as e:  # noqa: BLE001 — device boundary
                self._fail_active_slots(e)
                continue
            self._keys = np.array(keys)  # writable copy (slot admits write)
            self.dispatch_stats.decode_ticks += 1
            self.dispatch_stats.decode_tokens += len(active) * k
            with self._cond:
                for i in active:
                    st = self._slots[i]
                    # host-side unpack of the k-vector: per-token
                    # bookkeeping fires k times, so eviction lands at the
                    # exact token boundary it would under k=1 (the
                    # adaptive rule guarantees no lane finishes mid-tick,
                    # but the break keeps the invariant local)
                    for j in range(k):
                        st.tokens.append(int(nxt[i, j]))
                        self._tok[i] = nxt[i, j]
                        self._pos[i] += 1
                        st.remaining -= 1
                        self.stats.record_tokens(1)
                        done = (st.remaining <= 0
                                or self._pos[i] >= self.cfg.max_len - 1)
                        if done:
                            if not st.future.done():
                                st.future.set_result(
                                    np.asarray(st.tokens, np.int32))
                                self.stats.record_latency(
                                    time.monotonic() - st.enqueued)
                            self._slots[i] = None  # evict; slot is free
                            break
                self._cond.notify_all()  # drain() waiters see evictions
