#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a user
calls, at the full width of the one model with chip history:

    TransformerLM(cfg) -> lm.fit(x, y) x5 -> ServingEngine(model=lm).start()
    -> HTTP POST /generate answered by PagedDecoder

Phases (any assertion, exception or non-200 -> non-zero exit, the phase
named on the last line; no phase's failure is ever reported under exit 0):

  1. device   jax version, platform, device_kind, count, bytes_limit; refuses
              anything that is not a TPU in the peaks table (ops/device.py)
  2. train    >= 5 lm.fit steps on one seeded, repeated batch, fenced with
              block_until_ready: finite, falling loss on the COMPILED flash
              kernel, optimizer state donated
  3. flash    flash_attention against dense_attention at the step's own
              attention shape, forward and gradient, on the chip
  4. serve    ServingEngine on the trained model, real HTTP: one greedy and
              one sampled request alone, the same again co-scheduled with two
              that share an 80-token prefix, one streamed; solo ==
              co-scheduled, paged scheme, decode ticks, a prefix hit, clean
              drain
  5. four     only where the machine has >= 4 devices: the same config on a
              (data=2, model=2) mesh, and MeshPagedDecoder over 4 devices
              byte-identical to the one-chip decoder

`--rehearsal` selects a tiny size for a CPU dress rehearsal (interpreted
flash in phase 3). It is chosen by that argument only, never by which device
was found; without it a machine with no TPU fails in phase 1.

Timings printed here are set-up information labelled with the device. They
are not performance claims and go under no metric's name.

Last line of stdout on success:
    {"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import sys
import threading
import time
import traceback
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# the driver allows 1200 s; past this the run is hung — dump every thread's
# stack and exit non-zero instead of waiting to be killed silently
_HANG_DEADLINE_S = 1150

FULL = dict(
    model=dict(vocab_size=8192, d_model=2048, n_layers=4, n_heads=32,
               d_ff=8192, max_len=1024, dtype_policy="performance"),
    batch=16, steps=5, n_new=16)
REHEARSAL = dict(
    model=dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
               d_ff=128, max_len=128, dtype_policy="performance"),
    batch=4, steps=5, n_new=8)

PREFIX_TOKENS = 80   # >= 64: five full 16-token KV blocks shared
SEED = 21


class Ctx:
    """What one phase leaves for the next."""

    def __init__(self, size: dict, rehearsal: bool) -> None:
        self.size = size
        self.rehearsal = rehearsal
        self.cfg = None
        self.lm = None
        self.batch = None          # (x, y) the train phases repeat
        self.first_loss = None     # one-chip first-step loss
        self.prompts = None        # (a, b, c) sharing PREFIX_TOKENS
        self.solo = None           # one-chip solo transcripts of prompt a
        self.device = None         # the JSON result's device object


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    # not `assert`: the smoke must check under python -O too
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------


def phase_device(ctx: Ctx) -> None:
    import jax

    from deeplearning4j_tpu.ops import device, dispatch, memory

    devs = jax.devices()
    d0 = devs[0]
    ctx.device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(devs)}
    stats = d0.memory_stats() or {}
    say(f"jax {jax.__version__}  platform={d0.platform}  "
        f"device_kind={d0.device_kind!r}  count={len(devs)}  "
        f"bytes_limit={stats.get('bytes_limit')}")
    say(f"compile cache: {dispatch.compile_cache_dir()}")
    if ctx.rehearsal:
        say("rehearsal size, by explicit argument: the device check is "
            "not applied")
        return
    check(d0.platform == "tpu",
          f"platform is {d0.platform!r}, not 'tpu': chip_smoke.py proves "
          "the chip path and does not run on anything else "
          "(--rehearsal is the tiny CPU dress rehearsal)")
    row = device.peaks(d0.device_kind)   # raises for an unknown kind
    say(f"peaks[{d0.device_kind!r}] = {row['bf16_flops'] / 1e12:.0f} "
        f"TFLOP/s bf16, {row['hbm_gb']:.0f} GB at "
        f"{row['hbm_bytes_per_s'] / 1e9:.0f} GB/s ({row['source']})")
    say(f"HBM budget the sizers use: {memory.hbm_budget_gb():.3f} GiB from "
        f"the device's bytes_limit; published: {row['hbm_gb']:.0f} GB")


# ---------------------------------------------------------------------------
# phase 2: train
# ---------------------------------------------------------------------------


def _seeded_batch(cfg, batch: int):
    import jax.numpy as jnp

    toks = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (batch, cfg.max_len + 1))
    return (jnp.asarray(toks[:, :-1], jnp.int32),
            jnp.asarray(toks[:, 1:], jnp.int32))


def _fit_steps(lm, x, y, steps: int, label: str):
    """`steps` fenced lm.fit calls -> (losses, first-call s, later-step s)."""
    import jax

    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = jax.block_until_ready(lm.fit(x, y))
        secs.append(time.perf_counter() - t0)
        losses.append(float(loss))
    say(f"{label}: losses {[round(v, 4) for v in losses]}")
    check(all(np.isfinite(losses)), f"{label}: non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"{label}: loss did not fall over {steps} steps on a repeated "
          f"batch: {losses}")
    return losses, secs[0], float(np.median(secs[1:]))


def phase_train(ctx: Ctx) -> None:
    import jax

    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from deeplearning4j_tpu.ops import dispatch
    from deeplearning4j_tpu.ops.pallas_attention import (
        flash_fits,
        pallas_enabled,
    )

    cfg = ctx.cfg = TransformerConfig(**ctx.size["model"])
    lm = ctx.lm = TransformerLM(cfg)
    x, y = ctx.batch = _seeded_batch(cfg, ctx.size["batch"])
    hd = cfg.d_model // cfg.n_heads

    # which attention path the step traces: the gate's own predicate, and
    # the lowered program itself (a Mosaic kernel lowers to tpu_custom_call)
    gate = bool(cfg.use_flash and pallas_enabled()
                and flash_fits(cfg.max_len, hd))
    lowered = lm._step.lower(lm.params, lm.opt, x, y).as_text()
    in_program = "tpu_custom_call" in lowered
    say(f"attention path: {'flash (compiled pallas)' if gate else 'dense'}"
        f"  [gate={gate}, tpu_custom_call in lowered step={in_program}]")
    if not ctx.rehearsal:
        check(gate and in_program,
              "the train step did not trace the compiled flash kernel "
              f"(pallas_enabled and flash_fits({cfg.max_len}, {hd}) -> "
              f"{gate}; tpu_custom_call in program -> {in_program})")

    donating = dispatch.donation_enabled()
    old_m = jax.tree_util.tree_leaves(lm.opt["m"])[0]
    losses, first_s, step_s = _fit_steps(lm, x, y, ctx.size["steps"],
                                         "train")
    ctx.first_loss = losses[0]
    say(f"optimizer state donated: policy={donating}, "
        f"old Adam buffer deleted={old_m.is_deleted()}")
    check(old_m.is_deleted() == donating,
          "donation policy and what happened to the old optimizer buffer "
          "disagree")
    if not ctx.rehearsal:
        check(donating, "donation is off on a TPU backend")
    stats = jax.devices()[0].memory_stats() or {}
    say(f"set-up on {ctx.device['kind']}: first fit (trace + compile + "
        f"step) {first_s:.1f} s, later steps {step_s:.3f} s each, compile "
        f"~{first_s - step_s:.1f} s; peak_bytes_in_use="
        f"{stats.get('peak_bytes_in_use')}")


# ---------------------------------------------------------------------------
# phase 3: flash against dense
# ---------------------------------------------------------------------------

# bf16 keeps 8 significand bits. Both paths take bf16 q/k/v and accumulate
# in f32, but the dense twin rounds the probabilities to bf16 before P.V
# (and its autodiff rounds dP/dS the same way) while the flash kernel and
# its blocked backward keep them f32; both round the result to bf16. So
# element-wise they may differ by a few bf16 ulps of the tensor's largest
# element, and norm-wise by about one: the forward bound is the one
# tests/test_pallas_attention.py uses for bf16 on CPU.
FWD_MAX_ABS = 2e-2            # ~1 ulp at |out| <= 4 (2^-6)
GRAD_REL_FRO = 2.0 ** -6      # ||flash - dense|| / ||dense||
GRAD_MAX_REL = 2.0 ** -4      # max|flash - dense| / max|dense|


def phase_flash(ctx: Ctx) -> None:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas_attention import (
        dense_attention,
        flash_attention,
    )

    cfg = ctx.cfg
    n, t, h = ctx.size["batch"], cfg.max_len, cfg.n_heads
    hd = cfg.d_model // h
    interpret = ctx.rehearsal   # interpret mode only ever by this argument
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(SEED), 4)
    shape = (n, t, h, hd)
    q = jax.random.normal(kq, shape, jnp.bfloat16)
    k = jax.random.normal(kk, shape, jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.bfloat16)
    w = jax.random.normal(kw, shape, jnp.bfloat16)

    def probe(attend):
        def loss(q, k, v, w):
            out = attend(q, k, v)
            return (out.astype(jnp.float32) * w.astype(jnp.float32)).sum(), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    flash = probe(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=interpret))
    dense = probe(lambda q, k, v: dense_attention(q, k, v, causal=True))

    t0 = time.perf_counter()
    (_, f_out), f_grads = jax.block_until_ready(flash(q, k, v, w))
    say(f"flash fwd+grad at [{n}*{h}, {t}, {hd}] bf16 causal, "
        f"interpret={interpret}: compiled and ran in "
        f"{time.perf_counter() - t0:.1f} s")
    # the dense twin materializes [n*h, t, t] f32 scores: run it a slice
    # of the batch at a time (attention is independent per batch row)
    chunk = max(1, n // 4)
    d_out, d_grads = [], [[], [], []]
    for i in range(0, n, chunk):
        s = slice(i, i + chunk)
        (_, o), g = dense(q[s], k[s], v[s], w[s])
        d_out.append(o)
        for acc, gi in zip(d_grads, g):
            acc.append(gi)
    d_out = jnp.concatenate(d_out)
    d_grads = [jnp.concatenate(g) for g in d_grads]

    f32 = lambda a: np.asarray(a, np.float32)
    fo, do = f32(f_out), f32(d_out)
    check(np.isfinite(fo).all(), "flash forward has non-finite values")
    fwd = float(np.abs(fo - do).max())
    say(f"forward: max|flash - dense| = {fwd:.4g} (bound {FWD_MAX_ABS})")
    check(fwd <= FWD_MAX_ABS, f"flash forward disagrees with dense: {fwd}")
    for name, fg, dg in zip("qkv", f_grads, d_grads):
        fg, dg = f32(fg), f32(dg)
        check(np.isfinite(fg).all(), f"flash d{name} has non-finite values")
        rel = float(np.linalg.norm(fg - dg) / np.linalg.norm(dg))
        mx = float(np.abs(fg - dg).max() / np.abs(dg).max())
        say(f"d{name}: rel Frobenius {rel:.4g} (bound {GRAD_REL_FRO:.4g}), "
            f"max rel-to-max {mx:.4g} (bound {GRAD_MAX_REL:.4g})")
        check(rel <= GRAD_REL_FRO and mx <= GRAD_MAX_REL,
              f"flash d{name} disagrees with dense: {rel}, {mx}")


# ---------------------------------------------------------------------------
# phase 4: serve
# ---------------------------------------------------------------------------

_HTTP = urllib.request.build_opener(urllib.request.ProxyHandler({}))


def _post(url: str, payload: dict):
    req = urllib.request.Request(
        url + "/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with _HTTP.open(req, timeout=600) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:   # a non-200: the caller fails on it
        return e.code, e.read()


def _get(url: str, path: str) -> dict:
    with _HTTP.open(url + path, timeout=60) as r:
        check(r.status == 200, f"GET {path} -> {r.status}")
        return json.loads(r.read())


def _generate(url: str, prompt, n_new: int, vocab: int, stream=False,
              temperature=0.0):
    """One /generate over HTTP (greedy unless a temperature is given; the
    sampled stream is fixed by SEED) -> the n_new tokens (checked)."""
    status, body = _post(url, {"tokens": [prompt], "n_new": n_new,
                               "temperature": temperature, "seed": SEED,
                               "stream": stream})
    check(status == 200, f"/generate -> {status}: {body[:200]!r}")
    if stream:
        lines = [json.loads(ln) for ln in body.decode().splitlines() if ln]
        check(lines and lines[-1].get("done") is True,
              f"stream did not end with done: {lines[-1:]}")
        toks = [ln["token"] for ln in lines[:-1]]
        check(toks == lines[-1]["tokens"],
              "streamed tokens differ from the stream's own summary")
    else:
        toks = json.loads(body)["tokens"][0]
    check(len(toks) == n_new, f"asked {n_new} tokens, got {len(toks)}")
    check(all(isinstance(t, int) and 0 <= t < vocab for t in toks),
          f"token outside the vocabulary: {toks}")
    return toks


def _prompts(cfg):
    rng = np.random.default_rng(SEED + 1)
    prefix = rng.integers(0, cfg.vocab_size, PREFIX_TOKENS).tolist()
    return tuple(prefix + rng.integers(0, cfg.vocab_size, 4).tolist()
                 for _ in range(3))


def _serve(ctx: Ctx, mesh_devices: int):
    """Start an engine on ctx.lm, answer the request set, drain, stop.
    Returns (solo transcripts, kv report row, the decoder)."""
    from deeplearning4j_tpu.serving.engine import ServingEngine

    cfg, n_new = ctx.cfg, ctx.size["n_new"]
    a, b, c = ctx.prompts
    engine = ServingEngine(model=ctx.lm, port=0,
                           mesh_devices=mesh_devices or None).start()
    try:
        url = engine.url
        t0 = time.perf_counter()
        solo = _generate(url, a, n_new, cfg.vocab_size)
        say(f"greedy solo ({time.perf_counter() - t0:.1f} s incl. compile)"
            f": {solo}")
        # a sampled lane too: a briefly trained model's greedy transcript
        # can be one repeated token, which no neighbour could visibly
        # disturb; the seeded sampled one moves with every logit
        sampled = _generate(url, a, n_new, cfg.vocab_size, temperature=1.0)
        say(f"sampled solo (temperature 1, seed {SEED}): {sampled}")

        jobs = {"a": (a, 0.0), "a_sampled": (a, 1.0), "b": (b, 0.0),
                "c": (c, 0.0)}
        with ThreadPoolExecutor(len(jobs)) as pool:
            futs = {nm: pool.submit(_generate, url, prompt, n_new,
                                    cfg.vocab_size, temperature=temp)
                    for nm, (prompt, temp) in jobs.items()}
            # every future is read: a failed client fails the phase
            results = {nm: f.result(timeout=600) for nm, f in futs.items()}
        check(results["a"] == solo,
              "request independence broken: greedy transcript differs solo "
              f"vs co-scheduled\n  solo {solo}\n  co   {results['a']}")
        check(results["a_sampled"] == sampled,
              "request independence broken: sampled transcript differs solo "
              f"vs co-scheduled\n  solo {sampled}\n  co   "
              f"{results['a_sampled']}")
        say("greedy and sampled, co-scheduled with two prefix-sharing "
            "requests: identical to solo")
        streamed = _generate(url, a, n_new, cfg.vocab_size, stream=True)
        check(streamed == solo, "streamed greedy transcript differs from "
              f"solo\n  solo   {solo}\n  stream {streamed}")
        say(f"streamed: {n_new} ndjson tokens, identical to solo")

        kv = _get(url, "/models")["kv"]["default@v1"]
        serving = _get(url, "/metrics")["serving"]
        decoder = engine._decoders["default@v1"]
        ticks = decoder.dispatch_stats.decode_ticks
        say(f"kv: {kv}")
        say(f"decode_ticks={ticks}  prefix_hits={serving['prefix_hits']}"
            f"/{serving['prefix_lookups']} lookups")
        check(kv.get("scheme") == "paged",
              f"/generate was not served by the paged pool: {kv}")
        check(ticks > 0, "no decode tick was dispatched")
        check(serving["prefix_hits"] > 0, "the prefix cache recorded no hit")
        return {"greedy": solo, "sampled": sampled}, kv, decoder
    finally:
        engine.stop(drain=True)
        say("engine.stop(drain=True) returned")


def _check_no_stray_threads() -> None:
    time.sleep(0.2)
    stray = [t for t in threading.enumerate()
             if t is not threading.main_thread() and t.is_alive()
             and (not t.daemon or t.name.startswith(("paged-decoder",
                                                     "serve-")))]
    check(not stray, "threads that would keep the process or the chip "
          f"alive after stop(): {[t.name for t in stray]}")


def phase_serve(ctx: Ctx) -> None:
    from deeplearning4j_tpu.ops import memory

    ctx.prompts = _prompts(ctx.cfg)
    ctx.solo, kv, decoder = _serve(ctx, mesh_devices=0)
    sized = memory.kv_arena_blocks(ctx.cfg, decoder.block_tokens,
                                   params=ctx.lm.params,
                                   dtype=decoder.kv_dtype)
    check(kv["blocks_total"] == sized,
          f"arena holds {kv['blocks_total']} blocks, the sizer says {sized}")
    _check_no_stray_threads()


# ---------------------------------------------------------------------------
# phase 5: four chips
# ---------------------------------------------------------------------------

# One-chip step: flash attention, no collectives. Mesh step: dense
# attention (pallas calls do not partition under GSPMD) and a split
# contraction summed by all-reduce over 'model', all in bf16 compute. The
# first loss is a mean over batch*seq f32 NLLs of ~ln(vocab); bf16's 2^-8
# relative rounding of the logits averages down well below one bf16 ulp of
# the loss itself, which is the bound.
MESH_LOSS_REL = 2.0 ** -8


def _per_device_bytes(tree):
    import jax

    per = {}
    whole = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        whole += leaf.nbytes
        for sh in leaf.addressable_shards:
            per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
    return whole, per


def phase_four(ctx: Ctx) -> None:
    import jax

    from deeplearning4j_tpu.models.transformer import TransformerLM
    from deeplearning4j_tpu.parallel.mesh import (
        DATA_AXIS,
        MODEL_AXIS,
        device_mesh,
    )

    n_dev = len(jax.devices())
    if n_dev < 4:
        say(f"skipped: {n_dev} devices")
        return
    mesh = device_mesh(num_devices=4, shape=(2, 2),
                       axis_names=(DATA_AXIS, MODEL_AXIS))
    lm4 = TransformerLM(ctx.cfg, mesh=mesh)
    x, y = ctx.batch
    losses, first_s, step_s = _fit_steps(lm4, x, y, ctx.size["steps"],
                                         "mesh(data=2, model=2) train")
    rel = abs(losses[0] - ctx.first_loss) / abs(ctx.first_loss)
    say(f"first loss: one chip {ctx.first_loss:.6f}, mesh {losses[0]:.6f}, "
        f"rel diff {rel:.3g} (bound {MESH_LOSS_REL:.3g})")
    check(rel <= MESH_LOSS_REL, "mesh first loss disagrees with one chip")
    for name, tree in (("params", lm4.params),
                       ("opt m+v", (lm4.opt["m"], lm4.opt["v"]))):
        whole, per = _per_device_bytes(tree)
        say(f"{name}: whole {whole} B; per device "
            f"{dict(sorted(per.items()))}")
        check(len(per) == 4, f"{name} live on {len(per)} devices, not 4")
        check(all(b < whole for b in per.values()),
              f"a device holds all of {name}: {per} of {whole}")
    say(f"set-up on 4x {ctx.device['kind']}: first fit {first_s:.1f} s, "
        f"later steps {step_s:.3f} s each")
    del lm4

    mesh_solo, kv, decoder = _serve(ctx, mesh_devices=4)
    check(kv.get("mesh_devices") == 4, f"not a 4-device arena: {kv}")
    whole, per = _per_device_bytes(decoder._arena)
    say(f"arena: whole {whole} B; per device {dict(sorted(per.items()))}")
    check(len(per) == 4 and all(b * 4 == whole for b in per.values()),
          f"arena is not split in four: {per} of {whole}")
    for kind, one in ctx.solo.items():
        four = mesh_solo[kind]
        if four != one:
            part = next((i for i, (p, q) in enumerate(zip(four, one))
                         if p != q), min(len(four), len(one)))
            raise AssertionError(
                "MeshPagedDecoder is not byte-identical to the one-chip "
                f"decoder: {kind} transcripts part at token {part}\n"
                f"  one chip {one}\n  mesh     {four}")
    say("mesh-decoder tokens == one-chip tokens (greedy and sampled)")
    _check_no_stray_threads()
    for d in jax.devices()[:4]:
        st = d.memory_stats() or {}
        say(f"device {d.id}: peak_bytes_in_use="
            f"{st.get('peak_bytes_in_use')} of bytes_limit="
            f"{st.get('bytes_limit')}")


PHASES = (("device", phase_device), ("train", phase_train),
          ("flash", phase_flash), ("serve", phase_serve),
          ("four", phase_four))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny size for a CPU dress rehearsal (interpreted "
                         "flash); never chosen by the device found")
    args = ap.parse_args(argv)
    ctx = Ctx(REHEARSAL if args.rehearsal else FULL, args.rehearsal)
    t_all = time.perf_counter()
    for name, fn in PHASES:
        say(f"== phase {name}")
        t0 = time.perf_counter()
        try:
            fn(ctx)
        except Exception:
            traceback.print_exc()
            sys.stderr.flush()
            say(f"chip_smoke: FAILED in phase {name}")
            return 1
        say(f"-- phase {name} done in {time.perf_counter() - t0:.1f} s")
    say(f"all phases done in {time.perf_counter() - t_all:.1f} s")
    result = {"ok": True, "device": ctx.device}
    if args.rehearsal:
        result["rehearsal"] = True
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    faulthandler.dump_traceback_later(_HANG_DEADLINE_S, exit=True)
    sys.exit(main())
