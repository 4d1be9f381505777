"""Self-speculative decoding over the paged KV plane.

Speculative decoding (Leviathan et al. 2023, "Fast Inference from
Transformers via Speculative Decoding") attacks the same cost the
multi-token tick does — the fixed per-dispatch overhead (not measured on
the attached chip) that can dominate single-stream decode — from the other
side: instead of scanning k GUARANTEED-sequential target steps, a cheap
DRAFT model proposes k tokens autoregressively and the full-precision
target scores all k+1 positions in ONE batched dispatch. Greedy
acceptance (the longest proposal prefix matching the target's own
argmax, then the target's first correction) makes the committed stream
BYTE-IDENTICAL to target-only greedy decode — the draft can only ever
change how many target dispatches the transcript costs, never its
content (tests/test_speculate.py locks this, chaos-forced all-reject
rounds included).

"Self-speculative" because the draft is derived from the target itself
(ops/lowprec.draft_lm): ``int8`` fake-quantizes the block matmul
weights (the serving-quantization path of etl/calibrate, weight-only),
``layers:m`` truncates to the first m blocks under the target's own
final LN/head — no second model to train, ship, or keep in sync, and
the registry hands one cached draft per record (ModelRecord.draft_net).

Mechanics per speculative round (positions follow the decode convention
of serving/decode.py: ``pos`` is the NEXT CONSUME position — admission
leaves the last prompt token to be re-consumed at pos):

  * draft runs k+1 scanned steps on its own DENSE fixed-slot cache
    (decode._tick_for — plain jit, never donated): consuming
    t0@p, d1@(p+1), .. dk@(p+k) proposes d1..d_{k+1}; d_{k+1} is
    discarded, but its step writes the draft KV at p+k, which a fully
    accepted round needs valid next round.
  * the target verifies [t0, d1, .., dk] at positions p..p+k in one
    scanned dispatch over the block arena (_verify_for — the donated
    sibling of paged._paged_tick_for), emitting its greedy argmax at
    every position.
  * acceptance: a = longest prefix with d_j == g_j; commit d1..da plus
    the target's correction g_{a+1} — between 1 and k+1 tokens, each
    unpacked host-side through the same per-token bookkeeping /
    streaming-callback / eviction path as a k=1 tick.
  * REJECTED-SUFFIX ROLLBACK IS FREE: the verify wrote target KV at
    p..p+k, but every position >= the new consume position p+a+1 is
    overwritten inside a later dispatch before its layer attends
    (write-then-gather per layer), and the causal ``arange <= pos``
    mask hides it until then — the same trash-visibility argument
    paged.py makes for block 0, so block tables and refcounts need no
    rewind. The identical argument covers the draft cache's stale
    suffix.

Eligibility is decided PER ITERATION (the adaptive-k discipline of
PagedDecoder._tick_phase): a round runs only when no admissions are
pending, every active lane is greedy (temperature <= 0 — acceptance is
exact only against argmax; sampled lanes fall back to the base tick,
and PRNG keys are untouched either way since greedy never consumes
them), and every lane has >= k+1 tokens of budget and max_len headroom.
Anything else delegates to the inherited tick phase, so mixed pools
degrade to the multi-token tick rather than to wrong samples.

Reference parity anchor: the reference's serving route decodes strictly
one token per model call (dl4j-streaming's DL4JServeRouteBuilder.java
predict round-trip); this module and serving/paged.py:119 are the
beyond-reference replacements measured by bench.py --only=decode_amortize.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.models.transformer import TransformerConfig
from deeplearning4j_tpu.obs import trace as obs_trace
from deeplearning4j_tpu.ops import dispatch
from deeplearning4j_tpu.ops import env as envknob
from deeplearning4j_tpu.ops import memory as opsmem
from deeplearning4j_tpu.serving import decode
from deeplearning4j_tpu.serving.paged import (
    PagedDecoder,
    attention_path,
    paged_decode_step,
    refuse_window,
)

_VERIFY_CACHE: Dict[tuple, object] = {}


def _verify_for(cfg: TransformerConfig, block_tokens: int, k: int):
    """Target-side verify program: score k+1 supplied tokens in ONE
    dispatch over the block arena. toks [S, k+1] (last committed token,
    then the k draft proposals), pos [S] (first consume position),
    tables [S, m] -> (updated arena, greedy argmax [S, k+1]).

    The scan body is paged.paged_decode_step — the SAME per-position
    scatter/gather/attend the k=1 tick runs, so the emitted argmax at
    step j is byte-equal to what a plain greedy tick would have sampled
    after committing the first j proposals (the acceptance-exactness
    contract). Keyed like paged._paged_tick_for: the resolved attention
    path rides the cache key so a knob flip rebuilds the program."""
    path = attention_path(cfg, block_tokens)
    key = (cfg, block_tokens, path, int(k))
    fn = _VERIFY_CACHE.get(key)
    if fn is not None:
        return fn

    def verify(params, arena, toks, pos, tables):
        def step(carry, tok):
            arena, pos = carry
            arena, logits = paged_decode_step(params, arena, tok, pos,
                                              tables, cfg, attention=path)
            return (arena, pos + 1), \
                jnp.argmax(logits, axis=-1).astype(jnp.int32)

        (arena, _), greedy = lax.scan(step, (arena, pos),
                                      jnp.swapaxes(toks, 0, 1))
        return arena, jnp.swapaxes(greedy, 0, 1)

    # same single-owner donation contract as the paged tick: the worker
    # rebinds the arena every round, and an un-donated verify would
    # memcpy the whole arena per round
    verify = dispatch.arena_jit(verify, donate=(1,))
    _VERIFY_CACHE[key] = verify
    return verify


class SpeculativeDecoder(PagedDecoder):
    """PagedDecoder that interposes a draft-k-then-verify round whenever
    the pool is eligible (see module docstring; reference anchor
    serving/paged.py:416 — submit/generate/drain/stop, SLO classes,
    prefix cache, preemption and crash isolation are all inherited
    unchanged, and every inherited byte contract holds because the
    committed stream equals target-only greedy by construction).

    ``draft`` is any single-device TransformerLM sharing the target's
    vocab and max_len — in practice ops/lowprec.draft_lm's int8 or
    truncated-layer derivation via ModelRecord.draft_net.
    ``spec_chaos`` (resilience/chaos.SpecChaos) corrupts proposals at
    acceptance-comparison time — AFTER the verify ran on the true
    proposals — forcing all-reject rounds deterministically; config-
    driven, never ambient."""

    # a round streams its own tokens as it commits them, and reads
    # nothing of a tick's that is kept back: hand every tick's over at once
    defer_delivery = False

    def __init__(self, lm, *, draft, spec_k: Optional[int] = None,
                 spec_chaos=None, **kw) -> None:
        if draft is None:
            raise ValueError("SpeculativeDecoder needs a draft model "
                             "(ops/lowprec.draft_lm or record.draft_net)")
        if getattr(draft, "mesh", None) is not None:
            raise ValueError("speculative drafts must be single-device")
        dcfg = draft._run_cfg
        cfg = lm._run_cfg
        if opsmem.cache_needs(cfg).state or opsmem.cache_needs(dcfg).state:
            raise ValueError(
                "speculative decoding (DL4J_TPU_SERVE_SPEC) cannot carry "
                "per-lane recurrent state: a rejected draft token would "
                "have to be taken back out of it; not implemented for "
                "models with recurrent layers")
        if opsmem.cache_needs(cfg).windowed or \
                opsmem.cache_needs(dcfg).windowed:
            raise ValueError(refuse_window(
                "speculative decoding (DL4J_TPU_SERVE_SPEC)"))
        if (dcfg.vocab_size != cfg.vocab_size
                or dcfg.max_len != cfg.max_len):
            raise ValueError(
                f"draft config (V={dcfg.vocab_size}, T={dcfg.max_len}) "
                f"must match target (V={cfg.vocab_size}, T={cfg.max_len})")
        self._draft = draft
        self._draft_cfg = dcfg
        self.spec_k = max(1, int(
            spec_k if spec_k is not None
            else envknob.get_int("DL4J_TPU_SERVE_SPEC_K", 4)))
        self._spec_chaos = spec_chaos
        self.spec_rounds = 0
        # super().__init__ ends by calling _start_worker (overridden
        # below), so every field the worker reads must exist by here
        super().__init__(lm, **kw)

    def _start_worker(self) -> None:
        # dense fixed-slot draft cache, one stripe per lane — the draft
        # re-uses serving/decode's programs wholesale (plain jit, NOT
        # donated: no arena-death probe needed, and the draft pays the
        # copy at test scale where it is noise)
        dcfg = self._draft_cfg
        hd = dcfg.d_model // dcfg.n_heads
        zeros = jnp.zeros((dcfg.n_layers, self.lanes, dcfg.max_len,
                           dcfg.n_heads, hd), dcfg.compute_dtype)
        self._draft_cache = {"k": zeros, "v": zeros}
        # greedy never consumes the key stream, but _sample_step's
        # signature still wants per-lane keys — a frozen zero bank
        self._draft_keys = jnp.asarray(np.zeros((self.lanes, 2), np.uint32))
        self._zero_temps = np.zeros((self.lanes,), np.float32)
        super()._start_worker()

    def _admit_prefill(self, i: int, buf: np.ndarray, width: int,
                       write_table: np.ndarray) -> None:
        # target prefill first (the donated call that can kill the
        # arena), then the draft's dense-slot prefill — both inside the
        # caller's crash-isolation boundary, so a draft prefill failure
        # evicts exactly this lane like any admission crash
        super()._admit_prefill(i, buf, width, write_table)
        self._draft_cache = decode._admit_for(self._draft_cfg, width)(
            self._draft.params, self._draft_cache, jnp.asarray(buf),
            jnp.asarray(i, jnp.int32))

    def _tick_phase(self) -> bool:
        k = self.spec_k
        with self._cond:
            active = [i for i in range(self.lanes)
                      if self._slots[i] is not None]
            # eligibility, decided per iteration: pending admissions
            # must not wait out a draft+verify round; acceptance is
            # exact only for greedy lanes; and a lane must be able to
            # absorb a full k+1-token commit without crossing its
            # budget or max_len mid-round
            eligible = bool(active) and not self._total_pending()
            if eligible:
                for i in active:
                    st = self._slots[i]
                    if (st.temperature > 0.0
                            or st.remaining < k + 1
                            or int(self._pos[i]) + k + 1
                            > self.cfg.max_len - 1):
                        eligible = False
                        break
            if eligible:
                # the verify writes target KV at pos..pos+k, so grow
                # every lane's table k positions ahead; growth can
                # preempt (re-queueing work), which voids eligibility
                for i in range(self.lanes):
                    if self._slots[i] is not None:
                        self._grow(i, lookahead=k)
                active = [i for i in range(self.lanes)
                          if self._slots[i] is not None]
                if not active or self._total_pending():
                    eligible = False
        if not eligible:
            return super()._tick_phase()
        self.peak_active = max(self.peak_active, len(active))
        try:
            with obs_trace.span("serve.batch", kind="decode.spec",
                                lanes=len(active), spec_k=k):
                dtick = decode._tick_for(self._draft_cfg, k + 1)
                self._draft_cache, dtoks, _ = dtick(
                    self._draft.params, self._draft_cache,
                    jnp.asarray(self._tok), jnp.asarray(self._pos),
                    self._draft_keys, jnp.asarray(self._zero_temps))
                dtoks = np.asarray(dtoks)          # [lanes, k+1]
                toks = np.concatenate(
                    [self._tok[:, None], dtoks[:, :k]], axis=1)
                self._arena, greedy = _verify_for(
                    self.cfg, self.block_tokens, k)(
                    self._infer_params, self._arena, jnp.asarray(toks),
                    jnp.asarray(self._pos), jnp.asarray(self._tables))
                greedy = np.asarray(greedy)        # [lanes, k+1]
        except Exception as e:  # noqa: BLE001 — device boundary
            self._fail_active_lanes(e)
            return True
        # two dispatches (draft + verify) per round, honest about the
        # draft's cost; decode_tokens counts what actually committed
        self.dispatch_stats.decode_ticks += 2
        rnd = self.spec_rounds
        self.spec_rounds += 1
        callbacks = []
        completions = []
        committed_total = 0
        with self._cond:
            for i in active:
                st = self._slots[i]
                if st is None:
                    continue
                d = dtoks[i, :k]
                g = greedy[i]                      # [k+1]
                if self._spec_chaos is not None:
                    d = self._spec_chaos.corrupt(rnd, d, g,
                                                 self.cfg.vocab_size)
                a = 0
                while a < k and int(d[a]) == int(g[a]):
                    a += 1
                # commit the accepted prefix plus the target's own
                # correction: 1..k+1 tokens, all from the target's
                # greedy stream by construction
                commit = [int(d[j]) for j in range(a)] + [int(g[a])]
                self.stats.record_draft(k, a)
                committed_total += len(commit)
                for t in commit:
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
            self._cond.notify_all()
        self.dispatch_stats.decode_tokens += committed_total
        # same ordering discipline as the base tick: stream callbacks
        # before futures resolve, both outside the lock
        for cb, t in callbacks:
            try:
                cb(t)
            except Exception:  # noqa: BLE001 — client callback boundary
                pass
        for st in completions:
            if not st.future.done():
                st.future.set_result(np.asarray(st.tokens, np.int32))
                self.stats.record_latency(time.monotonic() - st.enqueued)
        return True
