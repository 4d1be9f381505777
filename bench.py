#!/usr/bin/env python
"""Benchmark driver — prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extras": {...}}

Covers all five BASELINE.json configs plus the north-star equivalence bar:
  configs[0] LeNet-5 MNIST      -> lenet5 samples/sec/chip; the headline is
                                   the fused training loop (fit_batches — K
                                   steps per lax.scan), the framework's
                                   sustained fit(DataSetIterator) speed;
                                   the per-step number is reported alongside
  configs[1] MLP+LSTM char-RNN  -> char_rnn train samples/sec + tokens/sec
                                   + rnn_time_step generation chars/sec
  configs[2] ResNet-50          -> samples/sec/chip + MFU (XLA-counted step
                                   FLOPs / peak chip FLOPs)
  configs[3] Word2Vec SGNS      -> skip-gram pairs/sec
  configs[4] 1→8 scaling        -> the equal-work DP overhead ratio on the
                                   virtual 8-device CPU mesh (a count of
                                   partitioning overhead, not a speedup)
  north_star                    -> 100-step CPU-vs-TPU float32-strict loss
                                   curve deviation (written to
                                   NORTHSTAR_r.json artifact)

vs_baseline: measured against a faithful torch-CPU LeNet-5 reimplementation
of the reference's nd4j-native CPU training path (the reference itself is
2016 Java/ND4J and cannot run here; torch-cpu is a GENEROUS stand-in — BLAS
conv + hand-tuned kernels, no per-op JVM dispatch — so the ratio understates
our advantage over real dl4j). Reference comparison path:
MultiLayerNetwork.fit :1017 (see BASELINE.md).

Data provenance is reported per dataset ("local"/"downloaded"/"synthetic");
this host is zero-egress so MNIST falls back to the deterministic synthetic
stand-in unless idx files are provided via DL4J_TPU_DATA_DIR.

Timing policy: batches are device-resident (training throughput, not the
host->device transfer) and every timed region ends in
jax.block_until_ready on a value that depends on the final step.

Process policy: `python bench.py --only=<leg> [--only=<leg> ...]` runs the
named legs IN THIS PROCESS and prints one JSON line. A chip belongs to one
process: a leg that measures the device fails unless jax's platform is
"tpu", and a leg whose child process picks its own backend is refused once
this process has imported jax. The exit status is non-zero if any leg
raised, returned an error, or found no chip it needs.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

from deeplearning4j_tpu.ops import env as envknob

os.environ.setdefault("DL4J_TPU_OFFLINE", "")  # downloads attempted once


def _log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


def _require_chip() -> None:
    """For legs that measure the device: fail unless jax runs on a TPU that
    is in the peaks table (ops/device.py), and wire the compile cache."""
    import jax

    from deeplearning4j_tpu.ops import device, dispatch

    d0 = jax.devices()[0]
    if d0.platform != "tpu":
        raise RuntimeError(
            f"this leg measures the chip and jax found platform "
            f"{d0.platform!r}; a CPU number is never written under a "
            "device metric's name")
    device.peaks(d0.device_kind)   # an unknown chip is an error
    dispatch.enable_compile_cache()


def _time_steps(fn, warmup: int, steps: int):
    """Time `steps` calls of fn. fn must RETURN a device value that depends
    on the whole step (e.g. the loss); the timed region ends in
    block_until_ready on it."""
    out = None
    for _ in range(warmup):
        out = fn()
    _force(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn()
    _force(out)
    return time.perf_counter() - t0


def _force(x):
    """Completion fence: wait until x (and everything it depends on) has
    been computed on the device."""
    import jax

    if x is not None:
        jax.block_until_ready(x)


# ---------------------------------------------------------------------------
# configs[0]: LeNet-5 MNIST
# ---------------------------------------------------------------------------


def bench_lenet(batch=512, steps=30):
    import jax

    from deeplearning4j_tpu.datasets.fetchers import load_mnist_info
    from deeplearning4j_tpu.models.lenet import build_lenet5

    net = build_lenet5()
    x, y, prov = load_mnist_info(train=True, num_examples=batch * 4)
    # device-resident rotating batches: measures training throughput, not
    # the host->device transfer (input pipelining is the AsyncDataSetIterator's
    # job and is benched by its own tests)
    xs = [jax.device_put(x[i * batch : (i + 1) * batch]) for i in range(4)]
    ys = [jax.device_put(y[i * batch : (i + 1) * batch]) for i in range(4)]
    i = [0]

    def step():
        loss = net.fit(xs[i[0] % 4], ys[i[0] % 4])
        i[0] += 1
        return loss

    dt = _time_steps(step, 3, steps)
    return {
        "samples_per_sec": round(batch * steps / dt, 1),
        "data": prov,
        "batch": batch,
        "sync": "loss readback",
    }


def bench_lenet_fused(batch=512, k=32, reps=3):
    """Sustained training throughput with the fused multi-step path
    (MultiLayerNetwork.fit_batches: K optimizer steps in ONE lax.scan) —
    the framework's answer to per-step dispatch latency; the reference's
    fit(DataSetIterator) loop compiled end-to-end."""
    import jax

    from deeplearning4j_tpu.datasets.fetchers import load_mnist_info
    from deeplearning4j_tpu.models.lenet import build_lenet5

    net = build_lenet5()
    x, y, prov = load_mnist_info(train=True, num_examples=batch * 4)
    xs = np.stack([x[(i % 4) * batch:((i % 4) + 1) * batch] for i in range(k)])
    ys = np.stack([y[(i % 4) * batch:((i % 4) + 1) * batch] for i in range(k)])
    xs, ys = jax.device_put(xs), jax.device_put(ys)

    losses = net.fit_batches(xs, ys)  # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        losses = net.fit_batches(xs, ys)  # ends in host readback of losses
    dt = time.perf_counter() - t0
    return {
        "samples_per_sec": round(batch * k * reps / dt, 1),
        "steps_fused": k, "batch": batch, "data": prov,
    }


def bench_torch_lenet_cpu(batch=512, steps=8):
    """Reference-CPU baseline: LeNet-5 (same topology as models/lenet.py /
    the dl4j LenetMnistExample) trained on torch-cpu. Stands in for the
    nd4j-native CPU path of MultiLayerNetwork.fit :1017."""
    import torch
    import torch.nn as nn

    torch.manual_seed(0)
    model = nn.Sequential(
        nn.Conv2d(1, 20, 5), nn.MaxPool2d(2),
        nn.Conv2d(20, 50, 5), nn.MaxPool2d(2),
        nn.Flatten(), nn.Linear(50 * 4 * 4, 500), nn.ReLU(),
        nn.Linear(500, 10),
    )
    opt = torch.optim.SGD(model.parameters(), lr=0.01, momentum=0.9)
    lossf = nn.CrossEntropyLoss()
    x = torch.randn(batch, 1, 28, 28)
    y = torch.randint(0, 10, (batch,))

    def step():
        opt.zero_grad()
        loss = lossf(model(x), y)
        loss.backward()
        opt.step()
        return loss.detach().numpy()

    dt = _time_steps(step, 2, steps)
    return {"samples_per_sec": round(batch * steps / dt, 1), "batch": batch}


# ---------------------------------------------------------------------------
# configs[1]: char-RNN (LSTM) train + generation
# ---------------------------------------------------------------------------


def bench_char_rnn(batch=32, seq=100, vocab=80, lstm=200, steps=10):
    import jax

    from deeplearning4j_tpu.models.char_rnn import char_rnn_conf
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    net = MultiLayerNetwork(
        char_rnn_conf(vocab, lstm_size=lstm, num_layers=2, tbptt_length=50)
    ).init(input_shape=(1, vocab))
    rng = np.random.default_rng(0)
    eye = np.eye(vocab, dtype=np.float32)
    ids = rng.integers(0, vocab, (batch, seq + 1))
    x = jax.device_put(eye[ids[:, :seq]])
    y = jax.device_put(eye[ids[:, 1:]])

    def step():
        return net.fit(x, y)  # 2 TBPTT windows of 50

    dt = _time_steps(step, 2, steps)
    train_samples = batch * steps / dt
    train_tokens = train_samples * seq

    # streaming generation throughput (reference rnnTimeStep :2152 hot path)
    net.rnn_clear_previous_state()
    x1 = jax.device_put(eye[0][None, None, :])
    gen_steps = 200
    out = None
    for _ in range(3):
        out = net.rnn_time_step(x1)
    _force(out)  # warmup (incl. compile) must finish before the timer starts
    t0 = time.perf_counter()
    for _ in range(gen_steps):
        out = net.rnn_time_step(x1)
    _force(out)
    gen_dt = time.perf_counter() - t0
    return {
        "train_samples_per_sec": round(train_samples, 1),
        "train_tokens_per_sec": round(train_tokens, 1),
        "generation_chars_per_sec": round(gen_steps / gen_dt, 1),
        "batch": batch, "seq": seq, "lstm": lstm,
    }


# ---------------------------------------------------------------------------
# configs[2]: ResNet-50 + MFU
# ---------------------------------------------------------------------------


def _peak_flops_per_chip() -> float:
    """bf16 peak of the attached chip from the one peaks table
    (ops/device.py; an unknown device_kind raises). f32 inputs hit the MXU
    through bf16 passes under jax's DEFAULT matmul precision."""
    from deeplearning4j_tpu.ops import device

    return device.peaks()["bf16_flops"]


def bench_resnet50(batch=128, steps=10, input_size=224,
                   dtype_policy="strict"):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.resnet import build_resnet50

    net = build_resnet50(input_size=input_size, num_classes=1000,
                         updater="nesterovs", learning_rate=0.05,
                         dtype_policy=dtype_policy)
    rng = np.random.default_rng(0)
    x = jax.device_put(
        rng.random((batch, input_size, input_size, 3)).astype(np.float32)
    )
    y = jax.device_put(
        np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)]
    )

    def step():
        return net.fit(x, y)

    dt = _time_steps(step, 2, steps)
    samples_per_sec = batch * steps / dt

    # XLA-counted FLOPs of the whole compiled train step (fwd+bwd+update)
    flops = None
    try:
        step_fn = net._get_train_step(1, False)
        inputs = net._as_inputs(jnp.asarray(x))
        labels = [jnp.asarray(y)]
        from deeplearning4j_tpu.ops import rng as rng_mod

        lowered = step_fn.lower(
            net.params, net.states, net.updater_state, inputs, labels,
            jnp.asarray(0, jnp.int32), rng_mod.step_key(net._rng, 0), {}, None,
        )
        cost = lowered.compile().cost_analysis()
        if cost:
            c = cost[0] if isinstance(cost, (list, tuple)) else cost
            flops = float(c.get("flops", 0.0)) or None
    except Exception as e:  # noqa: BLE001 — cost analysis is best-effort
        print(f"cost_analysis unavailable: {e}", file=sys.stderr)
    mfu = None
    if flops:
        # FLOPs per step / (seconds per step * peak FLOPs/sec)
        mfu = (flops / (dt / steps)) / _peak_flops_per_chip()
    return {
        "samples_per_sec": round(samples_per_sec, 2),
        "step_flops": flops,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "batch": batch, "input": input_size,
        "dtype_policy": dtype_policy,
    }


# ---------------------------------------------------------------------------
# beyond-reference flagship: transformer LM (tokens/sec + MFU + flash kernel)
# ---------------------------------------------------------------------------


def bench_mxu_calibration(steps=10):
    """Pure-matmul rate of THIS accelerator on ideal 4096^3 / 8192^3 bf16
    matmuls, beside the published peak — the denominator context for the
    MFU numbers."""
    import jax
    import jax.numpy as jnp

    out = {}
    for n in (4096, 8192):
        a = jax.device_put(jnp.ones((n, n), jnp.bfloat16))
        b = jax.device_put(jnp.ones((n, n), jnp.bfloat16))
        f = jax.jit(lambda a, b: a @ b)
        o = f(a, b)
        _force(o)
        t0 = time.perf_counter()
        for _ in range(steps):
            o = f(o, b)
        _force(o)
        dt = time.perf_counter() - t0
        out[f"bf16_{n}cubed_tflops"] = round(2 * n**3 * steps / dt / 1e12, 1)
    out["nominal_peak_tflops"] = round(_peak_flops_per_chip() / 1e12, 1)
    return out


def _transformer_bench_cfg(seq, d_model, n_layers, heads, vocab=8192,
                           dtype_policy="performance", remat="auto"):
    """Single source of truth for the bench transformer's architecture —
    bench_transformer runs it, transformer_hbm_preflight sizes it; sharing
    the builder keeps the OOM guard modeling the exact network it guards."""
    from deeplearning4j_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_layers=n_layers, n_heads=heads,
        d_ff=4 * d_model, max_len=seq, dtype_policy=dtype_policy,
        learning_rate=1e-4, remat=remat,
    )


def bench_transformer(batch=16, seq=1024, d_model=2048, n_layers=4, heads=32,
                      steps=5, dtype_policy="performance", remat="auto"):
    """Decoder-only LM train throughput (models/transformer.py): the model
    family whose scale needs the parallelism stack. Runs the flash-attention
    pallas kernel when on TPU (ops/pallas_attention.py); MFU from
    XLA-counted step FLOPs."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import TransformerLM

    cfg = _transformer_bench_cfg(seq, d_model, n_layers, heads,
                                 dtype_policy=dtype_policy, remat=remat)
    lm = TransformerLM(cfg)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (batch, seq + 1))
    x = jax.device_put(jnp.asarray(toks[:, :-1], jnp.int32))
    y = jax.device_put(jnp.asarray(toks[:, 1:], jnp.int32))

    dt = _time_steps(lambda: lm.fit(x, y), 2, steps)
    tokens_per_sec = batch * seq * steps / dt

    # fused multi-step (fit_batches: K steps per XLA program) — removes the
    # per-step dispatch round-trip
    xs = jnp.broadcast_to(x, (steps,) + x.shape)
    ys = jnp.broadcast_to(y, (steps,) + y.shape)
    losses = lm.fit_batches(xs, ys)  # compile + warm
    _force(losses)
    t0 = time.perf_counter()
    losses = lm.fit_batches(xs, ys)
    _force(losses)
    fused_tokens_per_sec = batch * seq * steps / (time.perf_counter() - t0)

    flops = None
    try:
        lowered = lm._step.lower(lm.params, lm.opt, x, y)
        cost = lowered.compile().cost_analysis()
        if cost:
            c = cost[0] if isinstance(cost, (list, tuple)) else cost
            flops = float(c.get("flops", 0.0)) or None
    except Exception as e:  # noqa: BLE001 — cost analysis is best-effort
        _log(f"transformer cost_analysis unavailable: {e}")
    mfu = None
    if flops:
        mfu = (flops / (dt / steps)) / _peak_flops_per_chip()
    from deeplearning4j_tpu.ops.pallas_attention import flash_fits, pallas_enabled

    # generation throughput: KV-cache decode (O(T) per token) vs the
    # full-forward sampler (O(T^2) per token) — the rnnTimeStep-style
    # streaming win for the flagship
    gen = {}
    prompt = x[:, :128]
    gen_reps = 3  # mean over repeats — one dispatch hiccup must not skew
    # the committed speedup (matches the other legs' methodology)
    for uc, label in ((True, "kv"), (False, "full")):
        out = lm.generate(prompt, n_new=64, temperature=1.0, seed=0,
                          use_cache=uc)  # compile + warm
        _force(out)
        t0 = time.perf_counter()
        for rep in range(gen_reps):
            out = lm.generate(prompt, n_new=64, temperature=1.0,
                              seed=1 + rep, use_cache=uc)
            _force(out)
        gen[label] = batch * 64 * gen_reps / (time.perf_counter() - t0)

    return {
        "gen_tokens_per_sec_kv": round(gen["kv"], 1),
        "gen_tokens_per_sec_full": round(gen["full"], 1),
        "kv_cache_speedup": round(gen["kv"] / gen["full"], 2),
        "tokens_per_sec": round(tokens_per_sec, 1),
        "tokens_per_sec_fused": round(fused_tokens_per_sec, 1),
        # K steps per XLA program vs one dispatch per step
        "fused_over_per_step": round(fused_tokens_per_sec / tokens_per_sec,
                                     2),
        "samples_per_sec": round(batch * steps / dt, 2),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "step_flops": flops,
        "flash_kernel": bool(pallas_enabled()
                             and flash_fits(seq, d_model // heads)),
        "batch": batch, "seq": seq, "d_model": d_model, "layers": n_layers,
        "dtype_policy": dtype_policy,
        # resolved remat rung (ops/remat.py ladder) — measurement provenance
        "remat": _resolved_remat(remat),
    }


def _resolved_remat(remat) -> str:
    from deeplearning4j_tpu.ops.remat import remat_policy

    return remat_policy(remat)


def transformer_hbm_preflight(batch, seq, d_model, n_layers, heads,
                              vocab=8192, hbm_gb=16.0, remat="none",
                              accum_steps=1):
    """HBM preflight for one transformer training step — the guard that
    keeps the MFU-chase leg (transformer_lm_big) from dying with an OOM
    (an untested config must not waste a chip run).

    The accounting guts now live in the AOT memory plane
    (ops/memory.transformer_preflight): params/optimizer/grads EXACT via
    jax.eval_shape on the real inits; activations a remat- and
    accum-aware analytic model of the bf16+flash regime (``remat`` picks
    the ladder rung — none/dots/block, ops/remat.py); measured
    memory_analysis numbers merged in when the config is small enough to
    AOT-compile on the CPU substrate. Returns (fits, report_dict)."""
    from deeplearning4j_tpu.ops.memory import transformer_preflight

    # the SAME config builder bench_transformer uses: the estimate must
    # model the exact network the leg will run, or the guard drifts
    cfg = _transformer_bench_cfg(seq, d_model, n_layers, heads, vocab,
                                 dtype_policy="performance", remat=remat)
    return transformer_preflight(cfg, batch, accum_steps=accum_steps,
                                 remat=remat, hbm_gb=hbm_gb)


def bench_transformer_big(steps=3, seq=1024, d_model=2048, n_layers=8,
                          heads=32):
    """The MFU-chase leg with the HBM preflight in front: the auto-fit
    sizer (ops/memory.auto_fit_transformer) picks the largest
    (batch, remat policy) pair whose estimate fits this chip's 16GB —
    largest batch first, weakest remat rung first (each rung down the
    ladder costs backward recompute), so an on-chip run can't OOM on an
    untested shape."""
    from deeplearning4j_tpu.ops.memory import auto_fit_transformer

    hbm_gb = envknob.get_float("DL4J_TPU_HBM_GB", 16.0)
    cfg = _transformer_bench_cfg(seq, d_model, n_layers, heads,
                                 dtype_policy="performance")
    # accum pinned to 1 for the leg: the MFU number must stay a
    # one-dispatch-per-step measurement (accum changes the program shape)
    choice = auto_fit_transformer(cfg, batches=(32, 16, 8, 4),
                                  accum_steps=(1,), hbm_gb=hbm_gb)
    if choice is None:
        # keep the diagnostic: the per-component breakdown of the MOST
        # affordable candidate says WHY nothing fit (triage from the
        # artifact instead of re-running the preflight by hand)
        _, report = transformer_hbm_preflight(
            4, seq, d_model, n_layers, heads, hbm_gb=hbm_gb, remat="block")
        return {"error": "no (batch, remat) candidate fits HBM",
                "preflight": report}
    out = bench_transformer(batch=choice["batch"], seq=seq, d_model=d_model,
                            n_layers=n_layers, heads=heads, steps=steps,
                            remat=choice["remat"])
    out["preflight"] = choice["report"]
    return out


def bench_ring_attention(n=1, t=4096, h=8, d=64, steps=5, interpret=False):
    """Long-context ring attention: local block product through the pallas
    flash kernel (ops/pallas_attention.flash_attention_block) vs the einsum
    body, on a 1-device 'seq' mesh — the only ring THIS host can run (one
    chip); the multi-device collective schedule is validated on the virtual
    mesh (tests + dryrun), and what changes between the two paths is
    exactly the per-device local block compute timed here. The einsum body
    materializes the [N,H,T,T] score block; the kernel streams it through
    VMEM."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from deeplearning4j_tpu.parallel.sequence_parallel import (
        ring_attention_sharded,
    )

    rng = np.random.default_rng(0)
    q, k, v = (
        jax.device_put(jnp.asarray(
            rng.standard_normal((n, t, h, d)), jnp.bfloat16))
        for _ in range(3)
    )
    mesh = Mesh(np.array(jax.devices()[:1]), ("seq",))
    out = {"shape": f"n{n} t{t} h{h} d{d}",
           "note": ("1-device ring (one real chip on this host): times the "
                    "local block product the kernel replaces; collective "
                    "schedule equivalence is proven on the virtual mesh")}
    for name, uf in (("einsum", False), ("flash", True)):
        fn = jax.jit(lambda q, k, v, uf=uf: ring_attention_sharded(
            q, k, v, mesh, causal=True, use_flash=uf,
            interpret=interpret))
        o = fn(q, k, v)
        _force(o)
        t0 = time.perf_counter()
        for _ in range(steps):
            o = fn(q, k, v)
        _force(o)
        out[f"ring_{name}_ms"] = round(
            (time.perf_counter() - t0) / steps * 1000, 3)
    out["flash_speedup"] = round(
        out["ring_einsum_ms"] / out["ring_flash_ms"], 2)
    # feed the measured-win gate: ring_attention_sharded's auto path turns
    # the kernel on only when this committed row proves it (kernel_gate).
    # Record the ACTUAL backend/interpret so a CPU or interpret invocation
    # can never masquerade as an on-chip row (measured_win filters those).
    from deeplearning4j_tpu.ops.kernel_gate import record_win

    record_win("attention", "ring_local_flash", {
        "speedup": out["flash_speedup"], "shape": out["shape"],
        "einsum_ms": out["ring_einsum_ms"],
        "flash_ms": out["ring_flash_ms"],
        "backend": jax.default_backend(), "interpret": bool(interpret),
    })
    return out


def bench_flash_attention(n=4, t=2048, h=8, d=64, steps=10):
    """Flash pallas kernel vs dense XLA attention, same shapes, fwd only."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas_attention import (
        flash_attention,
        flash_fits,
        pallas_enabled,
    )

    rng = np.random.default_rng(0)
    q, k, v = (
        jax.device_put(jnp.asarray(
            rng.standard_normal((n, t, h, d)), jnp.bfloat16))
        for _ in range(3)
    )

    # q/k/v as traced ARGS (a nullary closure would bake them in as
    # jaxpr constants that XLA may fold away, timing nothing)
    from deeplearning4j_tpu.ops.pallas_attention import dense_attention

    dense_j = jax.jit(lambda q, k, v: dense_attention(q, k, v, causal=True))
    dt_dense = _time_steps(lambda: dense_j(q, k, v), 2, steps)
    out = {"dense_ms": round(dt_dense / steps * 1000, 3),
           "shape": f"n{n} t{t} h{h} d{d}"}
    if pallas_enabled() and flash_fits(t, d):
        flash_j = jax.jit(
            lambda q, k, v: flash_attention(q, k, v, causal=True))
        dt_flash = _time_steps(lambda: flash_j(q, k, v), 2, steps)
        out["flash_ms"] = round(dt_flash / steps * 1000, 3)
        out["flash_speedup"] = round(dt_dense / dt_flash, 2)
    else:
        out["flash_ms"] = None
        out["note"] = "pallas off or shape unfit; dense path only"

    # masked variant: extended kernel (key bias) vs dense-masked — the row
    # that gates attention_auto's masked default (kernel_gate rent rule)
    from deeplearning4j_tpu.ops.pallas_attention import (
        _dense_masked,
        ext_fits,
        flash_attention_masked,
    )

    km = jax.device_put(jnp.asarray(rng.random((n, t)) > 0.2))
    dm_j = jax.jit(lambda q, k, v, km: _dense_masked(q, k, v, km,
                                                     causal=True))
    dt_dm = _time_steps(lambda: dm_j(q, k, v, km), 2, steps)
    out["masked_dense_ms"] = round(dt_dm / steps * 1000, 3)
    if pallas_enabled() and ext_fits(t, t, d):
        fm_j = jax.jit(lambda q, k, v, km: flash_attention_masked(
            q, k, v, km, causal=True))
        dt_fm = _time_steps(lambda: fm_j(q, k, v, km), 2, steps)
        out["masked_flash_ms"] = round(dt_fm / steps * 1000, 3)
        out["masked_speedup"] = round(dt_dm / dt_fm, 2)
        from deeplearning4j_tpu.ops.kernel_gate import record_win

        record_win("attention", "masked_flash", {
            "speedup": out["masked_speedup"], "shape": out["shape"],
            "dense_ms": out["masked_dense_ms"],
            "flash_ms": out["masked_flash_ms"],
            "backend": jax.default_backend(), "interpret": False,
        })
    return out


# ---------------------------------------------------------------------------
# dispatch efficiency: retrace telemetry + buffer-donation win
# ---------------------------------------------------------------------------

_DISPATCH_SCRIPT = r"""
import json, os, sys, time
import numpy as np

steps = int(sys.argv[1])
import jax, jax.numpy as jnp
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
from deeplearning4j_tpu.nn.conf import (DenseLayer, NeuralNetConfiguration,
                                        OutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


def build(seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.01)
            .updater("adam").list()
            .layer(0, DenseLayer(n_in=256, n_out=256, activation="relu"))
            .layer(1, DenseLayer(n_in=256, n_out=128, activation="relu"))
            .layer(2, OutputLayer(n_in=128, n_out=10, activation="softmax",
                                  loss_function="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


rng = np.random.default_rng(0)
x = rng.standard_normal((324, 256)).astype(np.float32)
y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 324)]

# --- retrace telemetry: ragged batch sizes {96, 100, 128} through
# fit_iterator. Bucketed: 100 pads to 128, 96 IS a bucket -> <= 2 traces.
# Unbucketed: one trace per distinct shape (the seed behavior).
def feed(bucketing):
    os.environ["DL4J_TPU_BUCKET_BATCHES"] = "1" if bucketing else "0"
    net = build()
    for b in (96, 100, 128, 100, 96, 128):  # repeats must be cache hits
        i = {96: 0, 100: 96, 128: 196}[b]
        net.fit_iterator(ListDataSetIterator(x[i:i + b], y[i:i + b], b))
    s = net.dispatch_stats
    return {"traces": s.traces.get("train_step", 0),
            "dispatches": s.calls.get("train_step", 0),
            "cache_hits": s.cache_hits("train_step"),
            "padded_batches": s.padded_batches,
            # wall-seconds spent in calls that traced (trace + XLA
            # compile) — the per-program compile budget
            "trace_seconds": round(s.trace_seconds.get("train_step", 0.0),
                                   3)}

bucketed = feed(True)
unbucketed = feed(False)
os.environ["DL4J_TPU_BUCKET_BATCHES"] = "1"

# --- donation win: steps/sec of the SAME fixed-shape train step with and
# without params/states/upd_state donation (fresh net per setting — the
# donation decision is read at jit construction). jax implements donation
# on CPU too (buffer reuse instead of copy), but the HBM-copy-per-step
# the chip saves is the point of this leg. INTERLEAVED paired reps with a
# median-pair commit, exactly like the scaling_virtual8 leg: on this
# shared 1-core host a single A-then-B timing swings wildly with
# background load (measured 0.79-1.35 on back-to-back CPU runs).
xb = jax.device_put(jnp.asarray(x[:128]))
yb = jax.device_put(jnp.asarray(y[:128]))

def build_timed(donate):
    os.environ["DL4J_TPU_DONATE"] = "force" if donate else "0"
    net = build()
    np.asarray(net.fit(xb, yb))  # compile + warm
    return net

def timed(net):
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = net.fit(xb, yb)
    jax.block_until_ready(loss)
    return steps / (time.perf_counter() - t0)

net_d, net_c = build_timed(True), build_timed(False)
pairs = [(timed(net_d), timed(net_c)) for _ in range(3)]
donated_n = net_d.dispatch_stats.donated_steps
ratios = [d / c for d, c in pairs]
mi = sorted(range(3), key=lambda i: ratios[i])[1]
sps_donated, sps_copied = pairs[mi]
del os.environ["DL4J_TPU_DONATE"]

print(json.dumps({
    "backend": jax.default_backend(),
    "device": str(jax.devices()[0]),
    "data": "synthetic",
    "batch_sizes": [96, 100, 128],
    "bucketed": bucketed,
    "unbucketed": unbucketed,
    "steps_per_sec_donated": round(sps_donated, 2),
    "steps_per_sec_copied": round(sps_copied, 2),
    "donation_speedup": round(ratios[mi], 3),
    "speedup_reps": [round(r, 3) for r in ratios],
    "speedup_stat": "median of 3 interleaved pair ratios; committed "
                    "steps/sec are the median pair's own halves",
    "donated_steps_counted": int(donated_n),
    "train_step_trace_seconds": round(
        net_d.dispatch_stats.trace_seconds.get("train_step", 0.0), 3),
    "timed_steps": steps,
}))
"""


def bench_dispatch_overhead(steps=40):
    """Dispatch-efficiency leg (ops/dispatch.py): proves the retrace count
    stays at one-per-bucket across ragged batch sizes, and measures the
    buffer-donation steps/sec delta on a fixed shape. The child runs on
    the backend jax gives it and labels its row with it — the retrace
    telemetry is backend-independent."""
    return _run_chosen_backend_child(_DISPATCH_SCRIPT, [str(steps)])


# ---------------------------------------------------------------------------
# remat: AOT memory ladder + step-time overhead (computable without a chip)
# ---------------------------------------------------------------------------

_REMAT_SCRIPT = r"""
import dataclasses, json, os, sys, time
steps = int(sys.argv[1])
import jax
mode = "cpu" if jax.default_backend() == "cpu" else "auto"
import jax.numpy as jnp
import numpy as np

import deeplearning4j_tpu.models.transformer as tfm
from deeplearning4j_tpu.ops import memory as mem

# the d512 L8 evidence config (ISSUE 4 acceptance): big enough that the
# activation ladder dominates temp bytes, small enough that the CPU
# substrate compiles each rung in seconds. Strict f32 on CPU (bf16 is a
# pessimization there); the chip regime (bf16) rides the same ladder.
d, L, heads, seq, batch, vocab = 512, 8, 8, 256, 8, 8192
dtype = "strict" if mode == "cpu" else "performance"
cfg0 = tfm.TransformerConfig(
    vocab_size=vocab, d_model=d, n_layers=L, n_heads=heads, d_ff=4 * d,
    max_len=seq, dtype_policy=dtype, learning_rate=1e-4)

rng = np.random.default_rng(0)
toks = rng.integers(0, vocab, (batch, seq + 1))
x = jax.device_put(jnp.asarray(toks[:, :-1], jnp.int32))
y = jax.device_put(jnp.asarray(toks[:, 1:], jnp.int32))

rows = {}
for pol in ("none", "dots", "block"):
    cfg = dataclasses.replace(cfg0, remat=pol)
    step = tfm.make_train_step(cfg)
    # ONE compile serves both the AOT ledger and the timed run (the
    # ledger comes first: the memory claim must not depend on the timed
    # run surviving)
    p_sh = jax.eval_shape(lambda: tfm.init_params(cfg))
    o_sh = jax.eval_shape(tfm.init_opt_state, p_sh)
    compiled = step.lower(p_sh, o_sh, x, y).compile()
    a = mem.analyze_compiled(compiled)
    params = tfm.init_params(cfg)
    opt = tfm.init_opt_state(params)
    step = compiled  # the AOT executable IS the step from here on
    params, opt, loss = step(params, opt, x, y)  # warm
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt, loss = step(params, opt, x, y)
    final = float(loss)  # host readback: also the completion fence
    rows[pol] = {
        "temp_bytes": None if a is None else a["temp_bytes"],
        "temp_gb": None if a is None else round(a["temp_bytes"] / 2**30, 3),
        "peak_gb": None if a is None else round(a["peak_bytes"] / 2**30, 3),
        "step_ms": round((time.perf_counter() - t0) / steps * 1000, 1),
        "loss": round(final, 4),
    }

def ratio(num, den):
    return None if not num or not den else round(num / den, 2)

out = {
    "backend": jax.default_backend(),
    "device": str(jax.devices()[0]),
    "data": "synthetic",
    "config": f"d{d} L{L} h{heads} b{batch} s{seq} v{vocab} {dtype}",
    "timed_steps": steps,
    "policies": rows,
    # the headline: AOT temp bytes (activations + workspace) per rung
    "temp_reduction_dots_x": ratio(rows["none"]["temp_bytes"],
                                   rows["dots"]["temp_bytes"]),
    "temp_reduction_block_x": ratio(rows["none"]["temp_bytes"],
                                    rows["block"]["temp_bytes"]),
    # recompute cost per rung (>1 = slower than none, the expected trade)
    "step_overhead_dots": ratio(rows["dots"]["step_ms"],
                                rows["none"]["step_ms"]),
    "step_overhead_block": ratio(rows["block"]["step_ms"],
                                 rows["none"]["step_ms"]),
    "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
}
# committed artifact (the PALLAS_BENCH.json pattern): the ladder evidence
# survives independently of the merged bench artifact
tmp = "REMAT_MEMORY.json.tmp"
with open(tmp, "w") as f:
    json.dump(out, f, indent=1, sort_keys=True)
os.replace(tmp, "REMAT_MEMORY.json")
print(json.dumps(out))
"""


def bench_remat_memory(steps=2):
    """Remat-ladder leg (ops/remat.py + ops/memory.py): AOT
    ``memory_analysis`` temp bytes and measured step time for the d512 L8
    train step under each remat rung (none/dots/block). CPU-measurable —
    the AOT ledger is exactly as valid on the CPU substrate as on the
    chip (it accounts the program XLA compiled for THAT backend) — with
    an honest backend label either way; on-chip rows additionally report
    real HBM. Writes REMAT_MEMORY.json beside the bench artifact."""
    return _run_chosen_backend_child(_REMAT_SCRIPT, [str(steps)])


# ---------------------------------------------------------------------------
# serving: dynamic batcher vs the naive per-request path under load
# ---------------------------------------------------------------------------

_SERVING_SCRIPT = r"""
import json, os, sys, threading, time
import numpy as np

clients, per_client = int(sys.argv[1]), int(sys.argv[2])
import jax
from concurrent.futures import ThreadPoolExecutor
from deeplearning4j_tpu.nn.conf import (DenseLayer, NeuralNetConfiguration,
                                        OutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving import DynamicBatcher, ServingStats
from deeplearning4j_tpu.serving.registry import bucket_ladder

conf = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.01)
        .updater("adam").list()
        .layer(0, DenseLayer(n_in=256, n_out=256, activation="relu"))
        .layer(1, DenseLayer(n_in=256, n_out=128, activation="relu"))
        .layer(2, OutputLayer(n_in=128, n_out=10, activation="softmax",
                              loss_function="mcxent"))
        .build())
net = MultiLayerNetwork(conf).init()
rng = np.random.default_rng(0)
rows = rng.standard_normal((clients, 256)).astype(np.float32)
n_requests = clients * per_client

# steady-state measurement: pre-compile every program either path can hit
# (batch-1 for naive; the bucket ladder for the batcher) — first-request
# compile latency is warmup's job (serving/registry.py), not this leg's
max_batch = 64
for b in sorted(set(bucket_ladder(max_batch)) | {1}):
    np.asarray(net.output(np.zeros((b, 256), np.float32)))

# naive path: the pre-rewrite ModelServer.predict — one locked batch-1
# output() dispatch per request (streaming/serving.py before this PR)
lock = threading.Lock()

def naive_one(i):
    with lock:
        out = net.output(rows[i % clients][None])
    return np.asarray(out)

def run_naive():
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=clients) as ex:
        list(ex.map(naive_one, range(n_requests)))
    return n_requests / (time.perf_counter() - t0)

def run_batched():
    stats = ServingStats()
    batcher = DynamicBatcher(lambda x: np.asarray(net.output(x)),
                             max_batch=max_batch, max_wait_ms=4,
                             queue_capacity=4096, stats=stats)
    try:
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=clients) as ex:
            list(ex.map(
                lambda i: batcher.predict(rows[i % clients][None]),
                range(n_requests)))
        rps = n_requests / (time.perf_counter() - t0)
    finally:
        batcher.stop()
    return rps, stats

run_naive(); run_batched()  # warm thread pools + any residual compiles

# INTERLEAVED paired reps with a median-pair commit (the scaling_virtual8
# methodology): single A-then-B timings on this shared 1-core host swing
# wildly with background load. The committed latency/fill telemetry is
# the MEDIAN PAIR'S OWN rep — quoting rep-3 percentiles against rep-1
# rps would mix measurement regimes in one row.
pairs = []
for _ in range(3):
    nv = run_naive()
    bt, st = run_batched()
    pairs.append((nv, bt, st))
ratios = [b / n for n, b, _ in pairs]
mi = sorted(range(3), key=lambda i: ratios[i])[1]
naive_rps, batched_rps, stats = pairs[mi]
lat = stats.latency_ms()

print(json.dumps({
    "backend": jax.default_backend(),
    "device": str(jax.devices()[0]),
    "data": "synthetic",
    "clients": clients,
    "requests_per_rep": n_requests,
    "naive_rps": round(naive_rps, 1),
    "batched_rps": round(batched_rps, 1),
    "batcher_speedup": round(ratios[mi], 3),
    "speedup_reps": [round(r, 3) for r in ratios],
    "speedup_stat": "median of 3 interleaved pair ratios; committed rps "
                    "are the median pair's own halves",
    "p50_ms": lat["p50"], "p95_ms": lat["p95"], "p99_ms": lat["p99"],
    "batch_fill_ratio": stats.batch_fill_ratio(),
    "batches_last_rep": stats.batches,
    "max_batch": max_batch,
}))
"""


def bench_serving_throughput(clients=32, per_client=16):
    """Serving-engine leg (deeplearning4j_tpu/serving/): requests/sec of
    the dynamic batcher vs the naive per-request path (one locked batch-1
    dispatch per request — the pre-rewrite streaming/serving.py and the
    reference's DL4jServeRouteBuilder granularity) under `clients`
    concurrent clients, plus the batcher's p50/p95/p99 latency and
    batch-fill ratio. The child runs on the backend jax gives it and
    labels its row with it — the batching win is about dispatch count,
    which exists on every backend."""
    return _run_chosen_backend_child(_SERVING_SCRIPT,
                                     [str(clients), str(per_client)])


# ---------------------------------------------------------------------------
# serving_resilience: breaker+watchdog accounting cost on the batcher hot
# path, and time-to-recover after an injected hang (ISSUE 8 —
# serving/resilience.py). CPU-only by design: the plane is host-side
# bookkeeping (a lock-guarded state machine per dispatch and an armed
# deadline per batch), so its cost exists on every backend. What fraction
# of a chip dispatch it is: not measured. Bar: < 3% rps on XLA:CPU.
# ---------------------------------------------------------------------------

_SERVING_RESILIENCE_SCRIPT = r"""
import json, sys, threading, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from concurrent.futures import ThreadPoolExecutor
from deeplearning4j_tpu.nn.conf import (DenseLayer, NeuralNetConfiguration,
                                        OutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.resilience import ServingChaos, ServingChaosConfig
from deeplearning4j_tpu.serving import (CircuitBreaker, DynamicBatcher,
                                        ServingEngine, ServingStats)
from deeplearning4j_tpu.serving.registry import bucket_ladder

clients, per_client = int(sys.argv[1]), int(sys.argv[2])
conf = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.01)
        .updater("adam").list()
        .layer(0, DenseLayer(n_in=256, n_out=256, activation="relu"))
        .layer(1, DenseLayer(n_in=256, n_out=128, activation="relu"))
        .layer(2, OutputLayer(n_in=128, n_out=10, activation="softmax",
                              loss_function="mcxent"))
        .build())
net = MultiLayerNetwork(conf).init()
rng = np.random.default_rng(0)
rows = rng.standard_normal((clients, 256)).astype(np.float32)
n_requests = clients * per_client
max_batch = 64
for b in sorted(set(bucket_ladder(max_batch)) | {1}):
    np.asarray(net.output(np.zeros((b, 256), np.float32)))


def run_batched(plane_on):
    stats = ServingStats()
    breaker = (CircuitBreaker(fails=5, key="bench", stats=stats)
               if plane_on else None)

    def on_outcome(ok, exc):
        if ok:
            breaker.record_success()
        else:
            breaker.record_failure(str(exc))

    batcher = DynamicBatcher(
        lambda x: np.asarray(net.output(x)), max_batch=max_batch,
        max_wait_ms=4, queue_capacity=4096, stats=stats,
        watchdog_s=(5.0 if plane_on else 0.0),
        on_outcome=(on_outcome if plane_on else None))
    try:
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=clients) as ex:
            list(ex.map(
                lambda i: batcher.predict(rows[i % clients][None]),
                range(n_requests)))
        rps = n_requests / (time.perf_counter() - t0)
    finally:
        batcher.stop()
    assert stats.wedged_batches == 0  # a false positive would taint the row
    return rps


run_batched(False); run_batched(True)  # warm thread pools

# interleaved off/on pairs, median-of-ratios (the serving_throughput
# methodology: single A-then-B swings with load on this shared 1-core
# host)
pairs = []
for _ in range(3):
    off = run_batched(False)
    on = run_batched(True)
    pairs.append((off, on))
ratios = sorted(off / on for off, on in pairs)
ratio = ratios[len(ratios) // 2]
mi = [i for i, p in enumerate(pairs) if p[0] / p[1] == ratio][0]
rps_off, rps_on = pairs[mi]

# time-to-recover after an injected hang: the engine-level wedge ->
# watchdog verdict -> breaker trip -> cooldown -> half-open probe ->
# serving again, measured end to end through the public predict API
chaos = ServingChaos(ServingChaosConfig(infer_hang_at=1, infer_hang_s=60.0))
eng = ServingEngine(model=net, max_wait_ms=2, watchdog_s=0.3,
                    breaker_fails=3, breaker_cooldown_s=0.2, chaos=chaos)
row = rows[0][None]
t0 = time.monotonic()
wedge_kind = None
try:
    eng.predict(row, timeout_s=30)
except Exception as e:
    wedge_kind = type(e).__name__
wedge_detect_s = time.monotonic() - t0
recover_s = None
t_limit = time.monotonic() + 30
while time.monotonic() < t_limit:
    try:
        eng.predict(row, timeout_s=5)
        recover_s = time.monotonic() - t0
        break
    except Exception:
        time.sleep(0.05)
snap = eng.stats.snapshot()
chaos.release_hangs()
eng.stop(drain=False)

print(json.dumps({
    "backend": jax.default_backend(),
    "device": str(jax.devices()[0]),
    "data": "synthetic",
    "clients": clients,
    "requests_per_rep": n_requests,
    "rps_plane_off": round(rps_off, 1),
    "rps_plane_on": round(rps_on, 1),
    "overhead_pct": round((ratio - 1.0) * 100.0, 2),
    "overhead_reps_pct": [round((r - 1.0) * 100.0, 2) for r in ratios],
    "overhead_bar_pct": 3.0,
    "wedge_error": wedge_kind,
    "wedge_detect_s": round(wedge_detect_s, 3),
    "time_to_recover_s": (round(recover_s, 3) if recover_s is not None
                          else None),
    "watchdog_s": 0.3,
    "breaker_cooldown_s": 0.2,
    "wedged_batches": snap["wedged_batches"],
    "watchdog_restarts": snap["watchdog_restarts"],
    "breaker_opens": snap["breaker_opens"],
    "breaker_closes": snap["breaker_closes"],
    "stat": "median of 3 interleaved plane-off/on pair ratios; recovery "
            "timed through the public predict API (wedge -> watchdog -> "
            "breaker cooldown -> probe -> first success)",
    "note": "host-side accounting only (no device sync added); the "
            "on-chip overhead fraction is not measured",
}))
"""


def bench_serving_resilience(clients=16, per_client=8):
    """Serving resilience leg (serving/resilience.py): steady-state rps
    cost of the breaker+watchdog accounting on the DynamicBatcher hot
    path (bar < 3% vs the plane-off batcher), plus the end-to-end
    time-to-recover after a deterministically injected infer-hang:
    watchdog verdict -> breaker trip -> half-open
    probe -> serving again. Subprocess-isolated, CPU-only by design —
    the plane is host-side bookkeeping on every backend."""
    parsed, err = _run_subprocess_json(
        [sys.executable, "-c", _SERVING_RESILIENCE_SCRIPT, str(clients),
         str(per_client)], 900)
    if parsed is None:
        return {"error": err}
    return parsed


# ---------------------------------------------------------------------------
# serving_fleet: router+replica tier (ISSUE 12 — serving/fleet.py +
# serving/router.py). CPU-only by design: on this 1-core host replicas
# share the core, so the replica-count sweep measures ROUTER overhead
# (proxy hop + breaker/SLO accounting per request) staying flat as the
# tier widens — not parallel speedup — and the kill leg measures the
# failover machinery (connect-failure verdict -> breaker vote ->
# retry-on-survivor -> board expiry -> restart -> re-admission), all of
# which is host-side bookkeeping that exists unchanged on every backend.
# Acceptance bar: ZERO failed admitted requests across the chaos kill,
# with the end-to-end time-to-recover committed in the row.
# ---------------------------------------------------------------------------

_SERVING_FLEET_SCRIPT = r"""
import json, sys, threading, time
import jax
jax.config.update("jax_platforms", "cpu")
import urllib.error, urllib.request
import numpy as np
from concurrent.futures import ThreadPoolExecutor
from deeplearning4j_tpu.nn.conf import (DenseLayer, NeuralNetConfiguration,
                                        OutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.resilience import RouterChaos, RouterChaosConfig
from deeplearning4j_tpu.serving.fleet import ServingFleet
from deeplearning4j_tpu.serving.registry import bucket_ladder

clients, per_client = int(sys.argv[1]), int(sys.argv[2])
conf = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.01)
        .updater("adam").list()
        .layer(0, DenseLayer(n_in=256, n_out=256, activation="relu"))
        .layer(1, DenseLayer(n_in=256, n_out=128, activation="relu"))
        .layer(2, OutputLayer(n_in=128, n_out=10, activation="softmax",
                              loss_function="mcxent"))
        .build())
net = MultiLayerNetwork(conf).init()
rng = np.random.default_rng(0)
rows = rng.standard_normal((clients, 256)).astype(np.float32)
n_requests = clients * per_client
# thread-mode replicas share the model object, so one warm pass fills the
# jit cache for every replica count (the bucket ladder + batch-1)
for b in sorted(set(bucket_ladder(64)) | {1}):
    np.asarray(net.output(np.zeros((b, 256), np.float32)))


def post(url, payload, timeout=60):
    req = urllib.request.Request(
        url + "/predict", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            r.read()
            return r.status
    except urllib.error.HTTPError as e:
        e.read()
        return e.code
    except OSError:
        return -1


def drive(url, n):
    lat, codes, lock = [], [], threading.Lock()

    def one(i):
        t0 = time.perf_counter()
        c = post(url, {"batch": rows[i % clients][None].tolist()})
        dt = time.perf_counter() - t0
        with lock:
            lat.append(dt)
            codes.append(c)

    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=clients) as ex:
        list(ex.map(one, range(n)))
    return time.perf_counter() - t0, sorted(lat), codes


replica_rows = {}
for n_rep in (1, 2, 4):
    fleet = ServingFleet(model=net, replicas=n_rep,
                         heartbeat_s=0.5).start()
    try:
        drive(fleet.url, clients * 2)  # warm every replica + the router
        wall, lat, codes = drive(fleet.url, n_requests)
        bad = sum(1 for c in codes if c != 200)
        assert bad == 0, f"{bad} non-200s at {n_rep} replicas"
        replica_rows[str(n_rep)] = {
            "rps": round(n_requests / wall, 1),
            "p50_ms": round(lat[len(lat) // 2] * 1e3, 2),
            "p99_ms": round(
                lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3, 2),
        }
    finally:
        fleet.stop()

# chaos kill mid-stream: r0 hard-dies after `kill_after` proxied requests
# (RouterChaos verdict, enacted by the fleet's kill hook); the bar is
# ZERO failed admitted requests. Recovery is timed end to end through
# the PUBLIC router API: kill instant -> restart_replica -> first
# /health scrape whose routable set includes r0 again.
kill_after = max(4, n_requests // 4)
chaos = RouterChaos(RouterChaosConfig(
    kill_replica={"replica": "r0", "after_proxied": kill_after}))
fleet = ServingFleet(model=net, replicas=2, heartbeat_s=0.25, chaos=chaos,
                     router_kwargs={"poll_s": 0.1})
times = {}
enact = fleet.router.on_kill


def on_kill(rid):
    times["kill"] = time.monotonic()
    enact(rid)


fleet.router.on_kill = on_kill
fleet.start()
result = {}
t = threading.Thread(
    target=lambda: result.update(
        zip(("wall", "lat", "codes"), drive(fleet.url, n_requests))))
t.start()
while "kill" not in times and t.is_alive():
    time.sleep(0.01)
assert "kill" in times, "chaos kill never fired"
recover_s = None
deadline = time.monotonic() + 30
while time.monotonic() < deadline:
    try:
        fleet.restart_replica("r0")
        break
    except ValueError:
        time.sleep(0.005)  # kill() may still be mid-enactment
# recovered == the router's PUBLIC /replicas view shows r0 at the NEW
# incarnation's address, probed ready, breaker serving — the stale
# pre-kill table entry (optimistic ready, unopened breaker) must not
# count as recovery
new_url = fleet.engines()["r0"].url
while time.monotonic() < deadline:
    try:
        with urllib.request.urlopen(fleet.url + "/replicas",
                                    timeout=5) as r:
            body = json.loads(r.read())
        d = body.get("r0")
        if (d and d["url"] == new_url and d["ready"]
                and d["breaker"]["state"] == "serving"):
            recover_s = time.monotonic() - times["kill"]
            break
    except OSError:
        pass
    time.sleep(0.02)
t.join()
failed = sum(1 for c in result["codes"] if c != 200)
snap = fleet.router.stats.snapshot()
fleet.stop()
assert failed == 0, f"{failed} admitted requests failed across the kill"
assert recover_s is not None, "killed replica never re-admitted"

r1 = replica_rows["1"]["rps"]
print(json.dumps({
    "backend": jax.default_backend(),
    "device": str(jax.devices()[0]),
    "data": "synthetic",
    "clients": clients,
    "requests_per_leg": n_requests,
    "replicas": replica_rows,
    "router_rps_ratio_2v1": round(replica_rows["2"]["rps"] / r1, 3),
    "router_rps_ratio_4v1": round(replica_rows["4"]["rps"] / r1, 3),
    "kill": {
        "requests": n_requests,
        "failed": failed,
        "kill_after_proxied": kill_after,
        "retries": snap["retries"],
        "replica_failures": snap["replica_failures"],
        "breaker_opens": snap["breaker_opens"],
        "time_to_recover_s": round(recover_s, 3),
    },
    "stat": "rps + latency through the public router HTTP API per "
            "replica count; recovery = kill instant -> restart -> first "
            "/replicas scrape showing the NEW incarnation's address "
            "ready with a serving breaker",
    "note": "1-core host: replicas share the core, so the sweep bounds "
            "ROUTER overhead (ratios ~1.0 == the proxy hop scales), not "
            "parallel speedup; failover/recover timings are host-side "
            "and backend-independent",
}))
"""


def bench_serving_fleet(clients=8, per_client=12):
    """Serving fleet leg (serving/fleet.py + serving/router.py): rps/p99
    through the public FleetRouter API at 1/2/4 replicas, plus the
    zero-loss chaos-kill contract — a replica hard-killed mid-stream
    must fail ZERO admitted requests (retry-on-survivor) — with the
    end-to-end time-to-recover (kill -> restart -> routable again).
    Subprocess-isolated, CPU-only by design: router accounting and
    failover are host-side on every backend."""
    parsed, err = _run_subprocess_json(
        [sys.executable, "-c", _SERVING_FLEET_SCRIPT, str(clients),
         str(per_client)], 900)
    if parsed is None:
        return {"error": err}
    return parsed


# ---------------------------------------------------------------------------
# autoscale: the ISSUE 20 control loop — scripted load wave -> scale-up
# (time-to-scale measured wave start -> second replica ready), quiet
# ticks -> scale-down draining the victim through the goodbye path while
# live /predict traffic keeps flowing (zero failed admitted requests),
# deterministic decision replay from the recorded signals_log, and the
# per-tenant token-bucket fairness proof (one tenant's burst sheds 429
# while the other's admission is untouched). CPU-only by design: every
# measured quantity is host-side control-plane work.
# ---------------------------------------------------------------------------

_AUTOSCALE_SCRIPT = r"""
import json, sys, threading, time
import jax
jax.config.update("jax_platforms", "cpu")
import urllib.error, urllib.request
import numpy as np
from deeplearning4j_tpu.nn.conf import (DenseLayer, NeuralNetConfiguration,
                                        OutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.resilience import (AutoscaleChaos,
                                           AutoscaleChaosConfig)
from deeplearning4j_tpu.serving.autoscale import FleetAutoscaler, ScaleConfig
from deeplearning4j_tpu.serving.fleet import ServingFleet
from deeplearning4j_tpu.serving.placement import model_footprint
from deeplearning4j_tpu.serving.registry import bucket_ladder
from deeplearning4j_tpu.serving.router import read_replica_addr

hammers, burst_n = int(sys.argv[1]), int(sys.argv[2])
N_IN = 64
conf = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.01)
        .updater("adam").list()
        .layer(0, DenseLayer(n_in=N_IN, n_out=64, activation="relu"))
        .layer(1, OutputLayer(n_in=64, n_out=8, activation="softmax",
                              loss_function="mcxent"))
        .build())
net = MultiLayerNetwork(conf).init()
for b in sorted(set(bucket_ladder(64)) | {1}):
    np.asarray(net.output(np.zeros((b, N_IN), np.float32)))

fleet = ServingFleet(model=net, replicas=1, heartbeat_s=0.5,
                     router_kwargs={
                         "tenant_quotas": "burst:0.001:3,steady:1e9:1e9"})
fleet.start()
cfg = ScaleConfig(min_replicas=1, max_replicas=2, up_queue=10.0,
                  up_shed=0, window=2, down_queue=2.0, cooldown=1)
auto = FleetAutoscaler(fleet, config=cfg, chaos=AutoscaleChaos(
    AutoscaleChaosConfig(load_wave={"at_tick": 0, "ticks": 2,
                                    "queue_depth": 50})))
plan = auto.plan_placement([model_footprint("default", net)])


def wait_ready(n, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if len(fleet.router.signals()["ready_replicas"]) >= n:
            return
        time.sleep(0.05)
    raise RuntimeError("fleet never reached %d ready replicas" % n)


def post(payload, timeout=60):
    req = urllib.request.Request(
        fleet.url + "/predict", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            r.read()
            return r.status
    except urllib.error.HTTPError as e:
        e.read()
        return e.code
    except OSError:
        return -1


wait_ready(1)
row = [[0.1] * N_IN]
codes, lock, stop = [], threading.Lock(), threading.Event()


def hammer():
    while not stop.is_set():
        c = post({"batch": row})
        with lock:
            codes.append(c)
        time.sleep(0.004)


threads = [threading.Thread(target=hammer) for _ in range(hammers)]
for t in threads:
    t.start()
t_wave = time.perf_counter()
while auto.tick()["action"] != "up":
    time.sleep(0.02)
wait_ready(2)
time_to_scale = time.perf_counter() - t_wave
t_down_start = time.perf_counter()
down = None
for _ in range(30):
    d = auto.tick()
    if d["action"] == "down":
        down = d
        break
    time.sleep(0.05)
assert down is not None and down.get("enacted") == down["victim"]
time_to_drain = time.perf_counter() - t_down_start
time.sleep(0.3)  # a last window of traffic on the survivor
stop.set()
for t in threads:
    t.join(timeout=30)
failed = sum(1 for c in codes if c != 200)
stale_addr = read_replica_addr(fleet.fleet_dir, down["victim"]) is not None

replay = FleetAutoscaler.replay(auto.signals_log, config=cfg)
stripped = [{k: v for k, v in d.items()
             if k not in ("enacted", "enact_error")}
            for d in auto.decisions]
replay_match = stripped == replay

tenant_codes = {"burst": [], "steady": []}
for i in range(burst_n):
    tenant_codes["burst"].append(post({"batch": row, "tenant": "burst"}))
    tenant_codes["steady"].append(post({"batch": row, "tenant": "steady"}))
tsnap = fleet.router.stats.snapshot()
fleet.stop()

snap = auto.stats.snapshot()
print(json.dumps({
    "backend": jax.default_backend(),
    "device": str(jax.devices()[0]),
    "data": "synthetic",
    "decisions": [d["action"] for d in auto.decisions],
    "time_to_scale_s": round(time_to_scale, 3),
    "time_to_drain_s": round(time_to_drain, 3),
    "scale_down": {"requests": len(codes), "failed": failed,
                   "victim": down["victim"],
                   "stale_addr_left": stale_addr},
    "replay_match": replay_match,
    "tenant": {"admitted": tsnap["tenant_admitted"],
               "shed": tsnap["tenant_shed"],
               "burst_429": sum(1 for c in tenant_codes["burst"]
                                if c == 429),
               "steady_429": sum(1 for c in tenant_codes["steady"]
                                 if c == 429)},
    "placement": {"models": plan.models(), "unplaced": plan.unplaced,
                  "utilization": plan.describe()["utilization"]},
    "autoscale_stats": snap,
    "stat": "scripted load wave -> scale-up (wave start -> second "
            "replica ready) -> quiet -> scale-down draining the victim "
            "under live /predict traffic; failed counts every non-200 "
            "answer an admitted client saw; replay_match re-runs the "
            "decision layer over the recorded signals_log",
    "note": "1-core host, CPU-only by design: every measured quantity "
            "is host-side control-plane work (decisions, drain, "
            "routing), identical on every backend",
}))
"""


def bench_autoscale(hammers=3, burst_n=10):
    """Autoscaling control-plane leg (ISSUE 20 — serving/autoscale.py):
    scripted load wave -> scale-up time, zero-loss scale-down under
    live traffic, bit-exact decision replay, tenant-bucket fairness.
    Subprocess-isolated, CPU-only by design (host-side control plane)."""
    parsed, err = _run_subprocess_json(
        [sys.executable, "-c", _AUTOSCALE_SCRIPT, str(hammers),
         str(burst_n)], 900)
    if parsed is None:
        return {"error": err}
    return parsed


# ---------------------------------------------------------------------------
# serving_decode: paged block-pool /generate vs the fixed slot pool at
# EQUAL KV HBM budget (ISSUE 11 — serving/paged.py). CPU-only by design:
# the contested resource is KV capacity and the win is scheduling
# (admission by free blocks + prefix sharing lets ~4x the streams
# co-reside in the same bytes), which exists on every backend; the tick
# arithmetic is the same jitted program either way.
# ---------------------------------------------------------------------------

_SERVING_DECODE_SCRIPT = r"""
import json, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

streams, n_new = int(sys.argv[1]), int(sys.argv[2])

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
from deeplearning4j_tpu.ops import env as envknob
from deeplearning4j_tpu.ops import lowprec
from deeplearning4j_tpu.serving.decode import ContinuousDecoder
from deeplearning4j_tpu.serving.engine import ServingEngine
from deeplearning4j_tpu.serving.paged import PagedDecoder, attention_path

SLOTS, BLOCK, PREFIX = 4, 16, 48
cfg = TransformerConfig(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
                        d_ff=128, max_len=128, use_flash=False)
lm = TransformerLM(cfg)
budget_tokens = SLOTS * cfg.max_len   # the fixed 4-slot pool's KV bytes,
n_blocks = budget_tokens // BLOCK     # handed to the paged arena instead

rng = np.random.default_rng(0)
system = rng.integers(1, 64, PREFIX)  # shared system prompt: 3 full blocks
prompts = [np.concatenate([system, rng.integers(1, 64, 8)]).astype(np.int32)
           for _ in range(streams)]


def pooled(make):
    d = make()
    try:
        t0 = time.perf_counter()
        futs = [d.submit(p, n_new, temperature=0.0, timeout_s=600)
                for p in prompts]
        outs = [np.asarray(f.result(timeout=600)) for f in futs]
        wall = time.perf_counter() - t0
        snap = d.stats.snapshot()
        lat = snap["latency_ms"]
        return outs, {
            "wall_s": round(wall, 3),
            "tokens_per_sec": round(streams * n_new / wall, 1),
            "concurrent_streams": d.peak_active,
            "p50_ms": lat["p50"],
            "p99_ms": lat["p99"],
        }, snap
    finally:
        d.stop()


make_paged = lambda: PagedDecoder(lm, block_tokens=BLOCK, n_blocks=n_blocks)
make_fixed = lambda: ContinuousDecoder(lm, slots=SLOTS)

# solo baselines (single-request path — the byte-identity reference)
d = make_paged()
try:
    solo = np.asarray(d.generate(prompts[0][None], n_new,
                                 temperature=0.0)[0])
finally:
    d.stop()
d = make_fixed()
try:
    solo_fixed = np.asarray(d.generate(prompts[0][None], n_new,
                                       temperature=0.0)[0])
finally:
    d.stop()
assert (solo == solo_fixed).all()

# warm pass: compiles the preemption path's re-admission prefill widths
# so the timed pass measures scheduling, not XLA
pooled(make_paged)

outs_p, row_p, snap_p = pooled(make_paged)
outs_f, row_f, snap_f = pooled(make_fixed)

assert (outs_p[0] == solo).all()        # pool-independence, paged
assert (outs_f[0] == solo_fixed).all()  # pool-independence, fixed slot
for a, b in zip(outs_p, outs_f):
    assert (a == b).all()               # cross-decoder identity

hit_rate = (snap_p["prefix_hits"] / snap_p["prefix_lookups"]
            if snap_p["prefix_lookups"] else None)

# span evidence AFTER the timed runs (the tracer never rides the hot
# path): serve.request (engine) parents serve.batch (paged tick)
obs.set_enabled(True)
eng = ServingEngine(model=lm, kv_block=BLOCK, kv_blocks=n_blocks)
try:
    eng.generate(prompts[0][None], 4, temperature=0.0)
finally:
    eng.stop()
reqs = obs.tracer().spans("serve.request")
batches = [s for s in obs.tracer().spans("serve.batch")
           if s["attrs"].get("kind") == "decode.paged"]
assert reqs and batches, "span evidence missing"
obs.set_enabled(None)

print(json.dumps({
    "backend": jax.default_backend(),
    "device": str(jax.devices()[0]),
    "data": "synthetic",
    "streams": streams,
    "n_new": n_new,
    "shared_prefix_tokens": PREFIX,
    "kv_budget_tokens": budget_tokens,
    "block_tokens": BLOCK,
    "n_blocks": n_blocks,
    "paged": row_p,
    "fixed_slot": row_f,
    "stream_ratio": round(row_p["concurrent_streams"]
                          / max(1, row_f["concurrent_streams"]), 2),
    "stream_ratio_bar": 4.0,
    "tokens_per_sec_ratio": round(row_p["tokens_per_sec"]
                                  / max(1e-9, row_f["tokens_per_sec"]), 2),
    "prefix_hit_rate": (round(hit_rate, 3) if hit_rate is not None
                        else None),
    "preemptions": snap_p["preemptions"],
    "attention_path": attention_path(cfg, BLOCK),
    "tick_k": envknob.get_int("DL4J_TPU_SERVE_TICK_K", 1),
    "spec": lowprec.spec_mode() or None,
    "byte_identical": True,
    "span_evidence": {"serve_request": len(reqs),
                      "serve_batch_paged": len(batches)},
    "stat": "one timed pass per pool over the same prompts (greedy), "
            "after a warm pass; latency percentiles from the decoder's "
            "own enqueue-to-completion ledger",
    "note": "equal KV budget: the fixed pool's slots*max_len tokens "
            "re-housed as a block arena (+1 trash block); the stream "
            "win is admission-by-free-blocks + prefix sharing, the "
            "byte-identity asserts are the independence contract",
}))
"""


def bench_serving_decode(streams=16, n_new=24):
    """Paged-KV decode leg (serving/paged.py): concurrent streams,
    aggregate tokens/s, and p50/p99 latency of the block-pool /generate
    plane vs the fixed 4-slot pool at EQUAL KV HBM budget, on a
    shared-system-prompt workload (prefix-cache hit rate and preemption
    count stamped). Asserts greedy outputs byte-identical to the
    single-request path on both pools, and serve.request -> serve.batch
    span evidence through the engine. Subprocess-isolated, CPU-only by
    design — the win is scheduling, not arithmetic."""
    parsed, err = _run_subprocess_json(
        [sys.executable, "-c", _SERVING_DECODE_SCRIPT, str(streams),
         str(n_new)], 900)
    if parsed is None:
        return {"error": err}
    return parsed


# ---------------------------------------------------------------------------
# decode_amortize: multi-token ticks + self-speculative decoding (ISSUE 16
# — serving/speculate.py). CPU-only by design: the claim provable off-chip
# is DISPATCH-COUNT reduction at byte-identical transcripts (the fixed
# per-dispatch overhead this amortizes is a chip number, not measured;
# the CPU tokens/s rows are XLA:CPU arithmetic).
# ---------------------------------------------------------------------------

_DECODE_AMORTIZE_SCRIPT = r"""
import json, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

k, n_new = int(sys.argv[1]), int(sys.argv[2])

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
from deeplearning4j_tpu.ops import lowprec
from deeplearning4j_tpu.serving.paged import PagedDecoder
from deeplearning4j_tpu.serving.speculate import SpeculativeDecoder

BLOCK, STREAMS = 8, 4
cfg = TransformerConfig(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
                        d_ff=128, max_len=128, use_flash=False)
lm = TransformerLM(cfg)
n_blocks = STREAMS * cfg.max_len // BLOCK
rng = np.random.default_rng(0)
prompts = [rng.integers(1, 64, 12).astype(np.int32) for _ in range(STREAMS)]
draft = lowprec.draft_lm(lm, "int8")


def run(make):
    # warm pass on a throwaway decoder compiles every program (the jit
    # caches are module-level), then a fresh decoder for the timed pass
    # so tick counters cover exactly the measured work
    for timed in (False, True):
        d = make()
        try:
            t0 = time.perf_counter()
            futs = [d.submit(p, n_new, temperature=0.0, timeout_s=600)
                    for p in prompts]
            outs = [np.asarray(f.result(timeout=600)).tolist()
                    for f in futs]
            wall = time.perf_counter() - t0
            # single-stream pass: the latency shape the dispatch
            # amortization actually targets
            t0 = time.perf_counter()
            solo = np.asarray(d.submit(prompts[0], n_new, temperature=0.0,
                                       timeout_s=600).result(timeout=600))
            solo_wall = time.perf_counter() - t0
            if timed:
                ds = d.dispatch_stats.snapshot()
                return outs, solo.tolist(), {
                    "wall_s": round(wall, 3),
                    "tokens_per_sec": round(STREAMS * n_new / wall, 1),
                    "solo_tokens_per_sec": round(n_new / solo_wall, 1),
                    "decode_ticks": ds["decode_ticks"],
                    "decode_tokens": ds["decode_tokens"],
                    "tokens_per_dispatch": ds["tokens_per_dispatch"],
                }, d.stats.snapshot()
        finally:
            d.stop()


base_o, base_solo, base_row, _ = run(lambda: PagedDecoder(
    lm, block_tokens=BLOCK, n_blocks=n_blocks, tick_k=1))
tick_o, tick_solo, tick_row, _ = run(lambda: PagedDecoder(
    lm, block_tokens=BLOCK, n_blocks=n_blocks, tick_k=k))
spec_o, spec_solo, spec_row, spec_snap = run(lambda: SpeculativeDecoder(
    lm, draft=draft, spec_k=k, block_tokens=BLOCK, n_blocks=n_blocks))

# equal transcripts are the contract the dispatch reduction rides on
assert tick_o == base_o and tick_solo == base_solo
assert spec_o == base_o and spec_solo == base_solo

tick_ratio = round(base_row["decode_ticks"]
                   / max(1, tick_row["decode_ticks"]), 2)
spec_ratio = round(base_row["decode_ticks"]
                   / max(1, spec_row["decode_ticks"]), 2)

print(json.dumps({
    "backend": jax.default_backend(),
    "device": str(jax.devices()[0]),
    "data": "synthetic",
    "streams": STREAMS,
    "n_new": n_new,
    "tick_k": k,
    "spec_k": k,
    "draft": "int8",
    "k1": base_row,
    "tick": tick_row,
    "spec": spec_row,
    "tick_dispatch_ratio": tick_ratio,
    "tick_dispatch_ratio_bar": round(k / 2, 2),
    "spec_dispatch_ratio": spec_ratio,
    "acceptance_rate": spec_snap.get("acceptance_rate"),
    "byte_identical": True,
    "stat": "one timed pass per decoder (greedy, pooled then "
            "single-stream) after a warm pass; tick counters from the "
            "decoder's own dispatch ledger",
    "note": "CPU proof is the dispatch-count reduction at equal "
            "transcripts; per-dispatch overhead here is XLA:CPU's, so "
            "tokens/s gains are muted — the chip row is not measured "
            "(spec counts draft+verify as 2 dispatches, honest about "
            "the draft's cost)",
}))
"""


def bench_decode_amortize(k=4, n_new=24):
    """Multi-token tick + self-speculative decode leg
    (serving/speculate.py): dispatch-count reduction of the k-scanned
    paged tick and the int8 draft-verify round vs k=1 ticking, at
    byte-identical greedy transcripts (pooled AND single-stream), plus
    honest CPU tokens/s and the acceptance-rate ledger. Subprocess-
    isolated, CPU-only by design — the amortized dispatch overhead is a
    chip number, not measured; the reduction ratio is backend-invariant."""
    parsed, err = _run_subprocess_json(
        [sys.executable, "-c", _DECODE_AMORTIZE_SCRIPT, str(k),
         str(n_new)], 900)
    if parsed is None:
        return {"error": err}
    return parsed


# ---------------------------------------------------------------------------
# serving_mesh: mesh-sharded decode + prefill/decode disaggregation
# (ISSUE 18 — serving/mesh.py). CPU-only by design: the byte-identity
# claim and the per-device capacity closed form are backend-invariant,
# and the virtual 8-device mesh exercises the real shard_map programs.
# ---------------------------------------------------------------------------

_SERVING_MESH_SCRIPT = r"""
import json, os, sys, time

# the sharded tick needs the virtual multi-device CPU platform BEFORE
# jax initializes (same discipline as tests/conftest.py)
os.environ["XLA_FLAGS"] = " ".join(
    [f for f in os.environ.get("XLA_FLAGS", "").split()
     if "xla_force_host_platform_device_count" not in f]
    + ["--xla_force_host_platform_device_count=8"])
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

mesh_d, n_new = int(sys.argv[1]), int(sys.argv[2])

import urllib.request

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
from deeplearning4j_tpu.ops import memory as opsmem
from deeplearning4j_tpu.serving.engine import ServingEngine
from deeplearning4j_tpu.serving.mesh import MeshPagedDecoder
from deeplearning4j_tpu.serving.paged import PagedDecoder
from deeplearning4j_tpu.serving.router import FleetRouter

BLOCK, STREAMS = 8, 4
cfg = TransformerConfig(vocab_size=64, d_model=64, n_layers=2,
                        n_heads=mesh_d, d_ff=128, max_len=128,
                        use_flash=False)
lm = TransformerLM(cfg)
n_blocks = STREAMS * cfg.max_len // BLOCK
rng = np.random.default_rng(0)
prompts = [rng.integers(1, 64, 12).astype(np.int32) for _ in range(STREAMS)]


def run(make):
    # warm pass compiles every program, then a fresh decoder for the
    # timed pass (the decode_amortize methodology)
    for timed in (False, True):
        d = make()
        try:
            t0 = time.perf_counter()
            futs = [d.submit(p, n_new, temperature=0.0, timeout_s=600)
                    for p in prompts]
            futs.append(d.submit(prompts[0], n_new, temperature=0.8,
                                 seed=11, timeout_s=600))
            outs = [np.asarray(f.result(timeout=600)).tolist()
                    for f in futs]
            wall = time.perf_counter() - t0
            if timed:
                return outs, {
                    "wall_s": round(wall, 3),
                    "tokens_per_sec": round(
                        (STREAMS + 1) * n_new / wall, 1),
                }
        finally:
            d.stop()


dense_o, dense_row = run(lambda: PagedDecoder(
    lm, block_tokens=BLOCK, n_blocks=n_blocks))
mesh_o, mesh_row = run(lambda: MeshPagedDecoder(
    lm, devices=mesh_d, block_tokens=BLOCK, n_blocks=n_blocks))
# the contract everything rides on: sharded tick == solo tick, bitwise,
# greedy AND sampled lanes co-resident
assert mesh_o == dense_o

# per-device arena accounting: same per-device HBM budget admits ~d x
# the global blocks (ops/memory closed form over shapes; budget small
# enough that neither side clamps at max_blocks)
blocks_1 = opsmem.kv_arena_blocks(cfg, BLOCK, hbm_gb=0.002)
blocks_d = opsmem.kv_arena_blocks(cfg, BLOCK, hbm_gb=0.002,
                                  devices=mesh_d)

# disaggregation: prefill-role + decode-role engines behind the
# role-aware router; every admitted /generate answered, byte-equal to
# a solo engine
prompt = [int(t) for t in prompts[0]]


def post(url, path, payload):
    req = urllib.request.Request(
        url + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=600) as r:
        return json.loads(r.read())


solo = ServingEngine(model=lm, kv_block=BLOCK,
                     kv_blocks=n_blocks).start()
try:
    want = post(solo.url, "/generate",
                {"tokens": prompt, "n_new": n_new,
                 "temperature": 0.0})["tokens"][0]
finally:
    solo.stop()

pre = ServingEngine(model=lm, kv_block=BLOCK, kv_blocks=n_blocks,
                    role="prefill").start()
dec = ServingEngine(model=lm, kv_block=BLOCK, kv_blocks=n_blocks,
                    role="decode").start()
router = FleetRouter(replicas={
    "p0": {"url": pre.url, "role": "prefill"},
    "d0": {"url": dec.url, "role": "decode"},
}).start()
n_req, walls = 8, []
try:
    for _ in range(n_req):
        t0 = time.perf_counter()
        got = post(router.url, "/generate",
                   {"tokens": prompt, "n_new": n_new,
                    "temperature": 0.0})["tokens"][0]
        walls.append(time.perf_counter() - t0)
        assert got == want
    rsnap = router.stats.snapshot()
    dsnap = dec.stats.snapshot()
    psnap = pre.stats.snapshot()
finally:
    router.stop()
    pre.stop()
    dec.stop()

walls.sort()
print(json.dumps({
    "backend": jax.default_backend(),
    "device": str(jax.devices()[0]),
    "data": "synthetic",
    "mesh_devices": mesh_d,
    "streams": STREAMS + 1,
    "n_new": n_new,
    "dense": dense_row,
    "mesh": mesh_row,
    "byte_identical": True,
    "kv_blocks_1dev": blocks_1,
    "kv_blocks_mesh": blocks_d,
    "kv_capacity_ratio": round(blocks_d / max(1, blocks_1), 2),
    "disagg_requests": n_req,
    "disagg_failed": n_req - dsnap["completed"],
    "prefill_handoffs": rsnap["prefill_handoffs"],
    "prefill_fallbacks": rsnap["prefill_fallbacks"],
    "prefix_imports": dsnap["prefix_imports"],
    "prefill_decode_tokens": psnap["generated_tokens"],
    "disagg_p50_ms": round(walls[len(walls) // 2] * 1e3, 1),
    "disagg_p99_ms": round(walls[-1] * 1e3, 1),
    "stat": "one timed pass per decoder after a warm pass (4 greedy + "
            "1 sampled co-resident lanes); handoff counters from the "
            "router/serving ledgers",
    "note": "CPU row — the virtual mesh shards over one physical core, "
            "so mesh tokens/s bounds program overhead, not the TP win; "
            "byte-identity and the capacity closed form are the "
            "backend-invariant proof; chip tokens/s: not measured",
}))
"""


def bench_serving_mesh(mesh_devices=4, n_new=16):
    """Mesh-sharded inference leg (serving/mesh.py): sharded-tick ==
    solo-tick byte-identity with greedy + sampled lanes co-resident,
    the per-device KV capacity closed form (capacity scales with the
    mesh at a fixed per-device budget), and the prefill/decode
    disaggregated fleet answering every admitted /generate byte-equal
    to a solo engine (handoff counters as evidence). Subprocess-
    isolated, CPU-only by design — the virtual 8-device mesh runs the
    real shard_map programs; chip tokens/s: not measured."""
    parsed, err = _run_subprocess_json(
        [sys.executable, "-c", _SERVING_MESH_SCRIPT, str(mesh_devices),
         str(n_new)], 900)
    if parsed is None:
        return {"error": err}
    return parsed


# ---------------------------------------------------------------------------
# checkpoint_overhead: sync vs async checkpointing cost (resilience/)
# ---------------------------------------------------------------------------

_CKPT_SCRIPT = r"""
import json, os, shutil, sys, tempfile, time

steps = int(sys.argv[1])
import jax
import numpy as np

from deeplearning4j_tpu.nn.conf import (DenseLayer, NeuralNetConfiguration,
                                        OutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.resilience import CheckpointManager

conf = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.01)
        .updater("adam").list()
        .layer(0, DenseLayer(n_in=784, n_out=256, activation="relu"))
        .layer(1, DenseLayer(n_in=256, n_out=256, activation="relu"))
        .layer(2, OutputLayer(n_in=256, n_out=10, activation="softmax",
                              loss_function="mcxent"))
        .build())
net = MultiLayerNetwork(conf).init()
rng = np.random.default_rng(0)
x = rng.standard_normal((256, 784)).astype(np.float32)
y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 256)]
net.fit(x, y)  # compile outside the timed region
cadence = max(1, steps // 6)
work = tempfile.mkdtemp(prefix="ckpt_bench_")

def run(m):
    mgr, blocks = None, []
    if m != "none":
        mgr = CheckpointManager(tempfile.mkdtemp(dir=work),
                                async_save=(m == "async"), keep_last=2)
    t0 = time.perf_counter()
    for s in range(1, steps + 1):
        net.fit(x, y)
        if mgr is not None and s % cadence == 0:
            tb = time.perf_counter()
            mgr.save(net, step=s, block=(m == "sync"))
            blocks.append(time.perf_counter() - tb)
    if mgr is not None:
        mgr.flush()  # async wall honestly includes the deferred IO drain
    wall = time.perf_counter() - t0
    stats = dict(mgr.stats) if mgr is not None else {}
    if mgr is not None:
        mgr.close()
    return wall, blocks, stats

for m in ("none", "sync", "async"):
    run(m)  # warm fs caches + writer thread

# interleaved reps + per-metric median (the scaling_virtual8 methodology:
# single A-then-B timings swing with background load on this shared host)
reps = [{m: run(m) for m in ("none", "sync", "async")} for _ in range(3)]
med = lambda vals: sorted(vals)[len(vals) // 2]
wall = {m: med([r[m][0] for r in reps]) for m in ("none", "sync", "async")}
block_ms = {
    m: med([1e3 * sum(r[m][1]) / max(1, len(r[m][1])) for r in reps])
    for m in ("sync", "async")
}
sync_stats = reps[-1]["sync"][2]
async_stats = reps[-1]["async"][2]
saves = max(1, sync_stats.get("saves", 1))
shutil.rmtree(work, ignore_errors=True)

print(json.dumps({
    "backend": jax.default_backend(),
    "device": str(jax.devices()[0]),
    "data": "synthetic",
    "steps": steps,
    "ckpt_every": cadence,
    "ckpt_mb": round(sync_stats.get("bytes", 0) / saves / 1e6, 2),
    # headline (the satellite's "step-time delta"): how long the train
    # loop STALLS per checkpoint — sync pays serialize+write+fsync
    # inline, async pays the host snapshot only
    "overhead_sync_ms_per_ckpt": round(block_ms["sync"], 2),
    "overhead_async_ms_per_ckpt": round(block_ms["async"], 2),
    "async_lt_sync": block_ms["async"] < block_ms["sync"],
    # secondary: whole-run wall overhead per step (async includes its
    # flush; on this 1-core host CPU-bound zip work cannot truly overlap,
    # so the wall delta narrows while the stall delta stays structural)
    "overhead_sync_ms_per_step": round(
        1e3 * (wall["sync"] - wall["none"]) / steps, 3),
    "overhead_async_ms_per_step": round(
        1e3 * (wall["async"] - wall["none"]) / steps, 3),
    "steps_per_sec_baseline": round(steps / wall["none"], 2),
    "writer_mb_per_sec": round(
        sync_stats.get("bytes", 0) / 1e6 / max(1e-9,
                                               sync_stats.get("write_s", 0)),
        1),
    "async_saves": async_stats.get("saves", 0),
    "async_skipped_busy": async_stats.get("skipped_busy", 0),
    "stat": "per-metric median of 3 interleaved none/sync/async reps",
}))
"""


def bench_checkpoint_overhead(steps=30):
    """Resilience leg (deeplearning4j_tpu/resilience/): the train-loop
    cost of checkpointing — per-checkpoint stall (sync = inline
    serialize+write+fsync, async = host snapshot only), whole-run wall
    overhead, checkpoint size and writer throughput. Subprocess-isolated
    like dispatch_overhead; honest CPU row (backend labeled) when the
    accelerator is unreachable — the sync-vs-async stall structure exists
    on every backend; on chip the snapshot adds the device->host
    readback, which this leg then measures for real."""
    return _run_chosen_backend_child(_CKPT_SCRIPT, [str(steps)])


# ---------------------------------------------------------------------------
# input_pipeline: naive single-thread feed vs the overlapped InputPipeline
# (deeplearning4j_tpu/etl/ — ISSUE 5). CPU-measurable by design: ingest
# throughput is host-side work, so this proof never needs the chip.
# ---------------------------------------------------------------------------

_INPUT_PIPELINE_SCRIPT = r"""
import json, os, shutil, sys, tempfile, time

batches = int(sys.argv[1])
import jax
import numpy as np

from deeplearning4j_tpu.datasets.records import (CSVRecordReader,
                                                 RecordReaderDataSetIterator)
from deeplearning4j_tpu.etl import InputPipeline, NormalizerStandardize
from deeplearning4j_tpu.nn.conf import (DenseLayer, NeuralNetConfiguration,
                                        OutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

# ETL-heavy regime ON PURPOSE: the leg measures the INPUT plane, so the
# per-batch host work (CSV decode + one-hot + normalize) must be a real
# fraction of the step — exactly the regime where fit_iterator starves
# without staging. The model is a small MLP; the data is a real on-disk
# CSV parsed for real every pass.
F, C, batch = 96, 10, 256
work = tempfile.mkdtemp(prefix="etl_bench_")
path = os.path.join(work, "data.csv")
rng = np.random.default_rng(0)
with open(path, "w") as f:
    for _ in range(batch * batches):
        f.write(",".join(f"{v:.6f}" for v in rng.standard_normal(F))
                + f",{int(rng.integers(0, C))}\n")

conf = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.01)
        .updater("adam").list()
        .layer(0, DenseLayer(n_in=F, n_out=32, activation="relu"))
        .layer(1, OutputLayer(n_in=32, n_out=C, activation="softmax",
                              loss_function="mcxent"))
        .build())
net = MultiLayerNetwork(conf).init()
norm = NormalizerStandardize().fit(RecordReaderDataSetIterator(
    CSVRecordReader(path), batch, label_index=F, num_possible_labels=C))
workers, prefetch = 2, 4


def run_naive():
    # today's single-thread feed: reader -> per-record float() assembly
    # -> normalizer -> fit, ALL on the training thread
    t0 = time.perf_counter()
    it = RecordReaderDataSetIterator(CSVRecordReader(path), batch,
                                     label_index=F, num_possible_labels=C)
    for ds in it:
        norm.transform(ds)
        net.fit(ds.features, ds.labels)
    np.asarray(net._score_dev)  # true data-dependent completion fence
    return time.perf_counter() - t0, None


def run_pipeline():
    t0 = time.perf_counter()
    pipe = InputPipeline.from_reader(
        CSVRecordReader(path), batch, label_index=F, num_possible_labels=C,
        normalizer=norm, workers=workers, prefetch=prefetch)
    for ds in pipe:
        net.fit(ds.features, ds.labels)
    np.asarray(net._score_dev)
    return time.perf_counter() - t0, pipe.pipeline_stats.snapshot()


run_naive(); run_pipeline()  # compile + warm page cache + threads
# interleaved pair reps, median-of-ratios (the serving_throughput
# methodology: single A-then-B timings swing with background load)
reps = [(run_naive(), run_pipeline()) for _ in range(3)]
ratios = sorted(((n[0] / p[0]), n, p) for n, p in reps)
ratio, n_med, p_med = ratios[len(ratios) // 2]
samples = batch * batches
stats = p_med[1]
shutil.rmtree(work, ignore_errors=True)

print(json.dumps({
    "backend": jax.default_backend(),
    "device": str(jax.devices()[0]),
    "data": "synthetic",
    "rows": samples, "features": F, "batch": batch,
    "workers": workers, "prefetch": prefetch,
    "naive_samples_per_sec": round(samples / n_med[0], 1),
    "pipeline_samples_per_sec": round(samples / p_med[0], 1),
    "pipeline_speedup": round(ratio, 3),
    "speedup_reps": [round(r[0], 3) for r in ratios],
    # the stall ledger (etl/stats.py): how much of the pass the TRAINING
    # thread still waited on input, and how long producers blocked on
    # full buffers — the two numbers that say who the bottleneck is
    "stall_fraction": stats["stall_fraction"],
    "producer_stall_seconds": stats["producer_stall_seconds"],
    "pipeline_batches_per_sec": stats["batches_per_sec"],
    "pipeline_mb_per_sec": stats["mb_per_sec"],
    "stat": "median of 3 interleaved naive/pipeline pair ratios; "
            "committed sps are the median pair's own halves",
    "note": "1-core host: the win is the pipeline's vectorized off-thread "
            "assembly (byte-identical C-level parse), not overlap — "
            "parse/compute overlap needs a second core and is structural "
            "on real hosts; stall_fraction shows the feed is still the "
            "bottleneck at this ETL weight",
}))
"""


def bench_input_pipeline(batches=20):
    """ETL subsystem leg (deeplearning4j_tpu/etl/): samples/sec of the
    naive single-thread feed (reader -> per-record assembly -> fit on ONE
    thread — the pre-ISSUE-5 ingest plane) vs the overlapped
    InputPipeline (parallel vectorized assembly + reorder + staged
    device_put), plus the pipeline_stats stall ledger. Subprocess-
    isolated like dispatch_overhead; honest CPU row (backend labeled)
    when the accelerator is unreachable — ingest is host-side work, so
    the number is real on every backend."""
    return _run_chosen_backend_child(_INPUT_PIPELINE_SCRIPT,
                                     [str(batches)])


# ---------------------------------------------------------------------------
# elastic_dp: averaging-round overhead of the elastic fleet runtime
# (deeplearning4j_tpu/parallel/fleet.py — ISSUE 6). CPU-measurable by
# design: the fleet's control plane (membership, split dispatch, reclaim,
# host-side averaging) is host work, so this proof never needs the chip.
# ---------------------------------------------------------------------------

_ELASTIC_DP_SCRIPT = r"""
import json, sys, time

rounds, workers = int(sys.argv[1]), int(sys.argv[2])
import jax
import numpy as np

from deeplearning4j_tpu.nn.conf import (DenseLayer, NeuralNetConfiguration,
                                        OutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.fleet import ElasticParameterAveragingTrainer
from deeplearning4j_tpu.resilience import FleetChaos, FleetChaosConfig

F, H, C = 32, 64, 10
# the faulted run shrinks to workers-1 members: the round batch must
# divide BOTH sizes (the loud-ValueError divisibility contract)
gb = workers * (workers - 1) * 4 if workers > 1 else 16


def build():
    conf = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.05)
            .list()
            .layer(0, DenseLayer(n_in=F, n_out=H, activation="tanh"))
            .layer(1, OutputLayer(n_in=H, n_out=C, activation="softmax",
                                  loss_function="mcxent"))
            .build())
    return MultiLayerNetwork(conf)


rng = np.random.default_rng(0)
x = rng.standard_normal(((rounds + 2) * gb, F)).astype(np.float32)
y = np.eye(C, dtype=np.float32)[rng.integers(0, C, (rounds + 2) * gb)]
batch = lambda r: (x[r * gb:(r + 1) * gb], y[r * gb:(r + 1) * gb])

# serial big-batch baseline (the denominator: what a round costs with no
# fleet control plane at all)
serial = build()
serial.fit(*batch(0)); serial.fit(*batch(1))  # compile + warm
t0 = time.perf_counter()
for r in range(rounds):
    serial.fit(*batch(r))
np.asarray(serial._score_dev)
serial_s = time.perf_counter() - t0

# elastic fleet, steady membership
fleet = ElasticParameterAveragingTrainer(build(), num_workers=workers,
                                         averaging_frequency=1,
                                         heartbeat_s=1.0)
fleet.fit(*batch(0)); fleet.fit(*batch(1))  # compile + warm
t0 = time.perf_counter()
for r in range(rounds):
    fleet.fit(*batch(r))
fleet_s = time.perf_counter() - t0
fleet.close()

# same run WITH one worker lost mid-round (detection + reclaim +
# re-execution + re-formed smaller rounds afterwards)
chaos = FleetChaos(FleetChaosConfig(kill_split={"round": 3, "split": 1}))
faulted = ElasticParameterAveragingTrainer(build(), num_workers=workers,
                                           averaging_frequency=1,
                                           heartbeat_s=0.5, chaos=chaos)
faulted.fit(*batch(0)); faulted.fit(*batch(1))
t0 = time.perf_counter()
for r in range(rounds):
    faulted.fit(*batch(r))
faulted_s = time.perf_counter() - t0
stats = dict(faulted.resilience_stats)
faulted.close()

print(json.dumps({
    "backend": jax.default_backend(),
    "device": str(jax.devices()[0]),
    "data": "synthetic",
    "workers": workers, "rounds": rounds, "global_batch": gb,
    "serial_rounds_per_sec": round(rounds / serial_s, 2),
    "fleet_rounds_per_sec": round(rounds / fleet_s, 2),
    # headline: what the elastic control plane (membership poll, split
    # dispatch over the tracker, host-side averaging) costs per round
    "fleet_overhead_ms_per_round": round(
        1e3 * (fleet_s - serial_s) / rounds, 2),
    "faulted_rounds_per_sec": round(rounds / faulted_s, 2),
    # one-time price of losing a worker: heartbeat-expiry detection +
    # split reclaim + re-execution, amortized into the faulted run
    "worker_loss_extra_s": round(faulted_s - fleet_s, 3),
    "reclaims": stats["reclaims"],
    "membership_epochs": stats["epoch"],
    "stat": "single timed run per condition after a 2-round warm "
            "(control-plane overhead, not chip throughput)",
    "note": "1-core host: worker threads serialize on the core, so "
            "fleet vs serial also pays thread scheduling; on a real pod "
            "each member owns its chip and the overhead is the control "
            "plane alone",
}))
"""


def bench_elastic_dp(rounds=10, workers=4):
    """Elastic fleet leg (parallel/fleet.py): averaging-round overhead of
    the fleet control plane at N workers vs the serial big-batch round,
    and the one-time cost of losing a worker mid-round (heartbeat
    detection + split reclaim + re-formed rounds). Subprocess-isolated;
    honest CPU row when the accelerator is unreachable — the control
    plane is host-side work on every backend."""
    return _run_chosen_backend_child(_ELASTIC_DP_SCRIPT,
                                     [str(rounds), str(workers)])


# ---------------------------------------------------------------------------
# online_loop: the full online-learning cycle (ISSUE 14 —
# deeplearning4j_tpu/online/): streaming ingest -> continuous fit ->
# candidate export -> shadow stage -> gated promotion, timed per phase,
# plus the shadow-mirror cost on the /predict answer path (bar < 3%).
# CPU-only by design: every phase is host-side orchestration (stream
# buffering, checkpoint commits, registry lifecycle, the offer-path
# stride) around tiny-model dispatches that exist unchanged on every
# backend.
# ---------------------------------------------------------------------------

_ONLINE_LOOP_SCRIPT = r"""
import json, os, sys, tempfile, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from deeplearning4j_tpu.datasets.iterator import DataSet
from deeplearning4j_tpu.etl.normalize import NormalizerStandardize
from deeplearning4j_tpu.nn.conf import (DenseLayer, NeuralNetConfiguration,
                                        OutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.online import (ContinuousTrainer, DriftMonitor,
                                       ShadowPromoter, StreamSource)
from deeplearning4j_tpu.resilience import CheckpointManager
from deeplearning4j_tpu.serving.engine import ServingEngine

batches, predicts = int(sys.argv[1]), int(sys.argv[2])
F, B, C = 16, 32, 3
rng = np.random.default_rng(0)
X = rng.standard_normal((batches * B, F)).astype(np.float32)
Y = np.eye(C, dtype=np.float32)[rng.integers(0, C, batches * B)]
norm = NormalizerStandardize().fit(X)


def net(seed):
    conf = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.05)
            .updater("adam").list()
            .layer(0, DenseLayer(n_in=F, n_out=32, activation="tanh"))
            .layer(1, OutputLayer(n_in=32, n_out=C, activation="softmax",
                                  loss_function="mcxent"))
            .build())
    return MultiLayerNetwork(conf)


tmp = tempfile.mkdtemp(prefix="bench_online_")
cand_zip = os.path.join(tmp, "candidate.zip")

# -- phase 1: ingest + fit round + candidate export ------------------------
mgr = CheckpointManager(os.path.join(tmp, "ckpt"), every_steps=0,
                        keep_last=2)
src = StreamSource(watermark=batches + 1, idle_s=0.05)
drift = DriftMonitor(norm, min_rows=B)
ct = ContinuousTrainer(net(7), src, manager=mgr, drift=drift,
                       normalizer=norm, workers=1, shard=None,
                       candidate_path=cand_zip, snapshot_rounds=1,
                       handle_signals=False)
ct.fit_round()  # warm round: jit compiles + checkpoint machinery
for i in range(batches):
    src.push(DataSet(X[i * B:(i + 1) * B], Y[i * B:(i + 1) * B]))
t0 = time.perf_counter()
losses = ct.fit_round()
fit_s = time.perf_counter() - t0
assert len(losses) == batches and os.path.exists(cand_zip)
drift_verdict = drift.check()["verdict"]
src.close()
mgr.close()

# -- phase 2/3: serve a prior default, stage the candidate, measure the
# shadow-mirror cost on the answered /predict path ------------------------
eng = ServingEngine(model=net(3).init(), input_shape=(F,), max_batch=16)
rows = X[:8]
for _ in range(4):
    eng.predict(rows)  # warm the primary's ladder

promoter = ShadowPromoter(eng, drift=drift, min_mirrored=1, fraction=1.0)
t0 = time.perf_counter()
rec = promoter.stage("candidate", model_path=cand_zip, input_shape=(F,),
                     max_batch=16)
stage_s = time.perf_counter() - t0
mirror = promoter.mirror
eng.predict(rows); mirror.wait_idle()  # warm the candidate dispatch too


def median_predict_s(mirror_on):
    # interleave-friendly single pass; the mirror worker is drained
    # OUTSIDE the timer after every predict (1-core host: leaving the
    # shadow dispatch in flight would time core contention, not the
    # offer-path stride the client actually pays)
    ts = []
    for _ in range(predicts):
        t0 = time.perf_counter()
        eng.predict(rows)
        ts.append(time.perf_counter() - t0)
        if mirror_on:
            mirror.wait_idle()
    ts.sort()
    return ts[len(ts) // 2]


pairs = []
for _ in range(3):
    eng.detach_shadow(mirror)
    off = median_predict_s(False)
    eng.attach_shadow(mirror)
    on = median_predict_s(True)
    pairs.append((off, on))
ratios = sorted(on / off for off, on in pairs)
ratio = ratios[len(ratios) // 2]
mirror.wait_idle()

# -- phase 4: gated promotion (atomic default swap) ------------------------
t0 = time.perf_counter()
report = promoter.promote()
promote_s = time.perf_counter() - t0
assert eng.registry.default().key == rec.key
snap = promoter.online_stats.snapshot()
eng.stop(drain=False)

print(json.dumps({
    "backend": jax.default_backend(),
    "device": str(jax.devices()[0]),
    "data": "synthetic",
    "stream_batches": batches, "batch_rows": B, "features": F,
    "ingest_fit_round_s": round(fit_s, 4),
    "batches_per_sec": round(batches / fit_s, 2),
    "stage_s": round(stage_s, 4),
    "promote_s": round(promote_s, 4),
    "cycle_s": round(fit_s + stage_s + promote_s, 4),
    "drift_verdict": drift_verdict,
    "mirrored": report["mirrored"],
    "agreement": report["agreement"],
    "prior_default": report["prior_default"],
    "shadow_overhead_pct": round((ratio - 1.0) * 100.0, 2),
    "shadow_overhead_reps_pct": [round((r - 1.0) * 100.0, 2)
                                 for r in ratios],
    "overhead_bar_pct": 3.0,
    "overhead_ok": bool(ratio - 1.0 < 0.03),
    "promotions": snap["promotions"],
    "stat": "single timed pass per phase after a warm round; shadow "
            "overhead = median of 3 interleaved mirror-off/on "
            "median-predict ratios, mirror drained outside the timer",
    "note": "1-core host: phase times are host-side orchestration around "
            "tiny CPU dispatches; the on-chip offer-path overhead "
            "fraction is not measured",
}))
"""


def bench_online_loop(batches=12, predicts=24):
    """Online learning loop leg (online/): end-to-end cycle time of
    streaming ingest -> fit round -> candidate export -> shadow stage ->
    gated promotion, and the shadow-mirror overhead on the answered
    /predict path (bar < 3% — the mirror must be invisible to clients in
    time as well as bytes). Subprocess-isolated, CPU-only by design —
    the loop is host-side orchestration on every backend."""
    parsed, err = _run_subprocess_json(
        [sys.executable, "-c", _ONLINE_LOOP_SCRIPT, str(batches),
         str(predicts)], 900)
    if parsed is None:
        return {"error": err}
    return parsed


# ---------------------------------------------------------------------------
# lowprec: the low-precision plane (ISSUE 15 — ops/lowprec.py +
# etl/calibrate.py). CPU-only leg: every row is MEASURED on the XLA:CPU
# program with an honest backend label; the chip rows (real HBM halving,
# int8 MXU throughput) are not measured.
# ---------------------------------------------------------------------------

_LOWPREC_SCRIPT = r"""
import json, os, sys, time
steps, reps = int(sys.argv[1]), int(sys.argv[2])
os.environ.pop("DL4J_TPU_BF16", None)
os.environ.pop("DL4J_TPU_QUANT", None)
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np

import deeplearning4j_tpu.models.transformer as tfm
from deeplearning4j_tpu.ops import memory as mem

# ---- bf16 train step: measured CPU AOT rows + the dtype-aware analytic
# accounting. XLA:CPU float-normalizes bf16 compute back to f32 (the
# flash-leg class of CPU-vs-chip program differences), so the MEASURED
# CPU temp bytes do NOT shrink — reported honestly; the byte claim the
# chip cashes is the analytic activations estimate (ib 2 vs 4), which is
# what transformer_preflight budgets HBM with.
d, L, heads, seq, batch, vocab = 256, 4, 4, 128, 8, 4096
cfg = tfm.TransformerConfig(
    vocab_size=vocab, d_model=d, n_layers=L, n_heads=heads, d_ff=4 * d,
    max_len=seq, learning_rate=1e-4)
rng = np.random.default_rng(0)
toks = rng.integers(0, vocab, (batch, seq + 1))
x = jnp.asarray(toks[:, :-1], jnp.int32)
y = jnp.asarray(toks[:, 1:], jnp.int32)

train = {}
for tmode in ("f32", "bf16"):
    if tmode == "bf16":
        os.environ["DL4J_TPU_BF16"] = "1"
    step = tfm.make_train_step(cfg)
    # fresh lambdas: jax.eval_shape caches on (fun identity, avals), and
    # init_opt_state's tree CHANGES with the env knob
    p_sh = jax.eval_shape(lambda: tfm.init_params(cfg))
    o_sh = jax.eval_shape(lambda p: tfm.init_opt_state(p), p_sh)
    compiled = step.lower(p_sh, o_sh, x, y).compile()
    a = mem.analyze_compiled(compiled)
    _, pre = mem.transformer_preflight(cfg, batch, hbm_gb=16.0,
                                       measure_aot=False)
    params = tfm.init_params(cfg)
    opt = tfm.init_opt_state(params)
    params, opt, loss = compiled(params, opt, x, y)  # warm
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt, loss = compiled(params, opt, x, y)
    final = float(loss)  # data-dependent host readback = the fence
    train[tmode] = {
        "measured_temp_bytes": None if a is None else a["temp_bytes"],
        "measured_peak_bytes": None if a is None else a["peak_bytes"],
        "analytic_act_gb": pre["activations_gb_est"],
        "train_dtype": pre["train_dtype"],
        "step_ms": round((time.perf_counter() - t0) / steps * 1000, 1),
        "loss": round(final, 4),
    }

def ratio(num, den):
    return None if not num or not den else round(num / den, 2)

# ---- calibrated int8 serving: a dense stack big enough that the matmul
# dominates; value delta measured on the SAME batch the rps rows time
from deeplearning4j_tpu.etl.calibrate import QuantCalibrator
from deeplearning4j_tpu.nn.conf import (DenseLayer, NeuralNetConfiguration,
                                        OutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops import lowprec

F, H, C, b8 = 256, 512, 10, 256
srng = np.random.default_rng(1)
SX = srng.standard_normal((512, F)).astype(np.float32)
SY = np.eye(C, dtype=np.float32)[srng.integers(0, C, 512)]
conf = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.01)
        .updater("adam").list()
        .layer(0, DenseLayer(n_in=F, n_out=H, activation="relu"))
        .layer(1, DenseLayer(n_in=H, n_out=H, activation="relu"))
        .layer(2, OutputLayer(n_in=H, n_out=C, activation="softmax",
                              loss_function="mcxent"))
        .build())
net = MultiLayerNetwork(conf).init()
for i in range(0, 512, 128):
    net.fit(SX[i:i + 128], SY[i:i + 128])
spec = QuantCalibrator().fit(net, SX[:256]).spec(net)
qnet = lowprec.QuantizedNet(net, spec)
xb = SX[:b8]
delta = float(np.max(np.abs(np.asarray(net.output(xb))
                            - np.asarray(qnet.output(xb)))))
serving = {"delta": round(delta, 6),
           "gate_bar": lowprec.quant_max_delta(),
           "quantized_layers": qnet.quantized_layers()}
for pname, m in (("f32", net), ("int8", qnet)):
    np.asarray(m.output(xb))  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        np.asarray(m.output(xb))
    dt = time.perf_counter() - t0
    serving[pname + "_rps"] = round(reps * b8 / dt, 1)
serving["int8_speedup"] = ratio(serving["int8_rps"], serving["f32_rps"])

# ---- bf16 KV arena: pure accounting over shapes
kcfg = tfm.TransformerConfig(vocab_size=vocab, d_model=512, n_layers=8,
                             n_heads=8, d_ff=2048, max_len=1024)
kv = {
    "block_bytes_f32": mem.kv_block_bytes(kcfg, 16, dtype=jnp.float32),
    "block_bytes_bf16": mem.kv_block_bytes(kcfg, 16, dtype=jnp.bfloat16),
    "blocks_f32": mem.kv_arena_blocks(kcfg, 16, hbm_gb=2.0,
                                      dtype=jnp.float32),
    "blocks_bf16": mem.kv_arena_blocks(kcfg, 16, hbm_gb=2.0,
                                       dtype=jnp.bfloat16),
}
kv["tokens_ratio"] = ratio(kv["blocks_bf16"], kv["blocks_f32"])

out = {
    "backend": jax.default_backend(),
    "device": str(jax.devices()[0]),
    "data": "synthetic",
    "train_config": f"d{d} L{L} h{heads} b{batch} s{seq} v{vocab}",
    "timed_steps": steps,
    "train": train,
    # the accounting-plane headline: activation bytes halve under bf16
    "analytic_act_reduction_x": ratio(train["f32"]["analytic_act_gb"],
                                      train["bf16"]["analytic_act_gb"]),
    # the honest CPU fact: XLA:CPU float-normalization keeps f32 buffers
    "measured_cpu_temp_ratio_x": ratio(
        train["f32"]["measured_temp_bytes"],
        train["bf16"]["measured_temp_bytes"]),
    "bf16_step_overhead_cpu": ratio(train["bf16"]["step_ms"],
                                    train["f32"]["step_ms"]),
    "serving_int8": serving,
    "kv_arena": kv,
    "note": ("CPU rows measure the XLA:CPU program (bf16 is "
             "float-normalized to f32 and int8 dot_general has no MXU): "
             "the byte/throughput wins are chip claims, not measured; "
             "the delta/equivalence rows are backend-independent facts"),
    "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
}
print(json.dumps(out))
"""


def bench_lowprec(steps=2, reps=20):
    """Low-precision plane leg (ISSUE 15): (a) the f32-vs-bf16 train
    step — measured CPU AOT bytes (honest: XLA:CPU float-normalizes
    bf16, no byte win on this substrate) beside the dtype-aware analytic
    accounting whose activation estimate halves (the claim the chip
    budgetes HBM with); (b) calibrated int8 serving rps vs f32 with the
    MEASURED accuracy delta against the gate bar; (c) the bf16 KV-arena
    sizing (2x tokens per budget). Subprocess-isolated, CPU-only by
    design."""
    parsed, err = _run_subprocess_json(
        [sys.executable, "-c", _LOWPREC_SCRIPT, str(steps), str(reps)], 900)
    if parsed is None:
        return {"error": err}
    return parsed


# ---------------------------------------------------------------------------
# retrieval: the embedding & ANN serving plane (ISSUE 17 —
# deeplearning4j_tpu/retrieval/). CPU-only leg: recall and the
# IVF-vs-exact qps win are MEASURED on XLA:CPU at the serving batch
# size (small batches — the /search latency regime, where the probe's
# candidate traffic beats streaming the whole corpus per batch); the
# chip row (MXU-batched exact scan, DMA'd block gathers) is not measured.
# ---------------------------------------------------------------------------

_RETRIEVAL_SCRIPT = r"""
import json, sys, threading, time
rows, queries = int(sys.argv[1]), int(sys.argv[2])
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from deeplearning4j_tpu.nn.conf import (DenseLayer, NeuralNetConfiguration,
                                        OutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.retrieval import VectorStore
from deeplearning4j_tpu.serving.engine import ServingEngine

dim, B, k, nprobe = 64, 8, 10, 8
K = max(16, int(np.sqrt(rows)))
rng = np.random.default_rng(0)
# clustered synthetic corpus — the regime IVF probing exists for
# (uniform random vectors would make any recall number meaningless)
centers = rng.normal(size=(K, dim)).astype(np.float32)
assign = rng.integers(0, K, size=rows)
corpus = (centers[assign]
          + 0.05 * rng.normal(size=(rows, dim))).astype(np.float32)
q = (centers[rng.integers(0, K, queries)]
     + 0.05 * rng.normal(size=(queries, dim))).astype(np.float32)

# -- phase 1: build + publish (kmeans cost measured, not hidden) ----------
ex = VectorStore(dim, capacity=rows + 1, kind="exact", name="exact")
iv = VectorStore(dim, capacity=rows + 1, kind="ivf", clusters=K,
                 nprobe=nprobe, ivf_iters=5, name="ivf")
t0 = time.perf_counter()
ex.upsert(np.arange(rows), corpus)
ex.publish()
exact_build_s = time.perf_counter() - t0
t0 = time.perf_counter()
iv.upsert(np.arange(rows), corpus)
iv.publish()
ivf_build_s = time.perf_counter() - t0

recall = iv.probe_recall(q[:64], k=k)

# -- phase 2: qps at the serving batch size (median of reps) --------------
for s in (ex, iv):
    s.search(q[:B], k=k)  # warm the bucket's program


def qps(store, reps=5):
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        done = 0
        for i in range(0, queries, B):
            store.search(q[i:i + B], k=k)
            done += min(B, queries - i)
        ts.append(done / (time.perf_counter() - t0))
    ts.sort()
    return ts[len(ts) // 2]


exact_qps = qps(ex)
ivf_qps = qps(iv)

# -- phase 3: /embed latency through the engine (batcher path) ------------
F, H = 16, dim
conf = (NeuralNetConfiguration.builder().seed(7).list()
        .layer(0, DenseLayer(n_in=F, n_out=H, activation="relu"))
        .layer(1, OutputLayer(n_in=H, n_out=4, activation="softmax",
                              loss_function="mcxent"))
        .build())
net = MultiLayerNetwork(conf)
net.init()
eng = ServingEngine(model=net, input_shape=(F,)).start()
xs = rng.normal(size=(128, F)).astype(np.float32)
for i in range(4):
    eng.embed(xs[i:i + 1])  # warm
lat = []
for i in range(128):
    t0 = time.perf_counter()
    eng.embed(xs[i:i + 1])
    lat.append(time.perf_counter() - t0)
lat.sort()
p50_ms = lat[len(lat) // 2] * 1e3
p99_ms = lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3
eng.stop(drain=False)

# -- phase 4: generation swaps under live search load ---------------------
stop = threading.Event()
answered = [0]
failed = [0]


def searcher():
    while not stop.is_set():
        try:
            ids, _ = ex.search(q[:B], k=k)
            assert ids.shape == (B, k)
            answered[0] += 1
        except Exception:
            failed[0] += 1
            return


threads = [threading.Thread(target=searcher) for _ in range(2)]
for t in threads:
    t.start()
swaps = 12
t0 = time.perf_counter()
for i in range(swaps):
    ex.upsert(np.arange(rows - 64, rows), corpus[rows - 64:])
    ex.publish()
swap_s = (time.perf_counter() - t0) / swaps
stop.set()
for t in threads:
    t.join()

print(json.dumps({
    "backend": jax.default_backend(),
    "device": str(jax.devices()[0]),
    "data": "synthetic",
    "rows": rows, "dim": dim, "clusters": K, "nprobe": nprobe,
    "query_batch": B, "k": k,
    "recall_at_10": round(recall, 4), "recall_bar": 0.95,
    "recall_ok": bool(recall >= 0.95),
    "exact_qps": round(exact_qps, 1), "ivf_qps": round(ivf_qps, 1),
    "ivf_speedup": round(ivf_qps / exact_qps, 2), "speedup_bar": 2.0,
    "speedup_ok": bool(ivf_qps >= 2.0 * exact_qps),
    "exact_build_s": round(exact_build_s, 2),
    "ivf_build_s": round(ivf_build_s, 2),
    "embed_p50_ms": round(p50_ms, 3), "embed_p99_ms": round(p99_ms, 3),
    "swap_publish_s": round(swap_s, 3),
    "swap_searches_answered": answered[0],
    "swap_searches_failed": failed[0],
    "stat": "qps = median of 5 full query sweeps at batch %d after one "
            "warm call; recall measured vs the exact oracle on the SAME "
            "snapshot; swap phase overlaps %d publishes with 2 live "
            "search threads" % (B, swaps),
    "note": "CPU substrate: the IVF win is the serving-batch regime "
            "(per-query candidate traffic < streaming the corpus once "
            "per batch); the chip row (MXU exact scan vs DMA block "
            "gathers) is not measured",
}))
"""


def bench_retrieval(rows=65536, queries=64):
    """Retrieval plane leg (ISSUE 17): MEASURED IVF recall@10 against
    the exact oracle on the same published snapshot (bar 0.95), the
    IVF-vs-exact qps win at the serving batch size (bar 2x), /embed
    p50/p99 through the engine batcher, and zero-failed-searches across
    generation swaps under live load. Subprocess-isolated, CPU-only by
    design — the chip row is not measured."""
    parsed, err = _run_subprocess_json(
        [sys.executable, "-c", _RETRIEVAL_SCRIPT, str(rows), str(queries)],
        900)
    if parsed is None:
        return {"error": err}
    return parsed


# ---------------------------------------------------------------------------
# CPU-for-CPU baseline: OUR framework on jax-CPU vs the torch-CPU rows
# (a baseline ratio that needs no chip)
# ---------------------------------------------------------------------------

_LENET_CPU_SCRIPT = r"""
import json, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from deeplearning4j_tpu.datasets.fetchers import load_mnist_info
from deeplearning4j_tpu.models.lenet import build_lenet5

batch, steps = int(sys.argv[1]), int(sys.argv[2])
net = build_lenet5()
x, y, prov = load_mnist_info(train=True, num_examples=batch)
xb, yb = jax.device_put(x), jax.device_put(y)

out = None
for _ in range(2):
    out = net.fit(xb, yb)
np.asarray(out)
t0 = time.perf_counter()
for _ in range(steps):
    out = net.fit(xb, yb)
np.asarray(out)  # host readback with a true data dependency
per_step = batch * steps / (time.perf_counter() - t0)

# the fused loop (fit_batches) measured for the record, NOT for the
# ratio: XLA-CPU pessimizes the scanned conv program badly (~15x slower
# per step than the unfused fit on this host — measured during PR 2),
# while on TPU the same program is the headline. The honest CPU-for-CPU
# ratio is per-step vs per-step (the torch baseline is a per-step loop).
# DL4J_TPU_FUSE=force: fit_batches now auto-falls back to per-step fits
# for scanned conv on the CPU backend (dispatch.fusion_enabled — the
# guard this measurement motivated); this row deliberately measures the
# pessimized fused program itself, so it must force past the guard.
import os
os.environ["DL4J_TPU_FUSE"] = "force"
k = 4
xs = jax.device_put(np.broadcast_to(x, (k,) + x.shape).copy())
ys = jax.device_put(np.broadcast_to(y, (k,) + y.shape).copy())
losses = net.fit_batches(xs, ys)
np.asarray(losses)
t0 = time.perf_counter()
losses = net.fit_batches(xs, ys)
np.asarray(losses)
fused = batch * k / (time.perf_counter() - t0)

print(json.dumps({
    "backend": jax.default_backend(),
    "samples_per_sec": round(per_step, 1),
    "samples_per_sec_fused": round(fused, 1),
    "fused_note": "XLA-CPU scan-of-conv pessimization: the fused path is "
                  "the TPU headline, not the CPU one; ratio uses per-step",
    "batch": batch, "steps": steps, "data": prov,
    "label": "cpu_for_cpu",
}))
"""

_CHAR_RNN_CPU_SCRIPT = r"""
import json, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

batch, seq, vocab, lstm, steps = (int(a) for a in sys.argv[1:6])

from deeplearning4j_tpu.models.char_rnn import char_rnn_conf
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

net = MultiLayerNetwork(
    char_rnn_conf(vocab, lstm_size=lstm, num_layers=2, tbptt_length=50)
).init(input_shape=(1, vocab))
rng = np.random.default_rng(0)
eye = np.eye(vocab, dtype=np.float32)
ids = rng.integers(0, vocab, (batch, seq + 1))
x = jax.device_put(eye[ids[:, :seq]])
y = jax.device_put(eye[ids[:, 1:]])

out = None
for _ in range(2):
    out = net.fit(x, y)
np.asarray(out)
t0 = time.perf_counter()
for _ in range(steps):
    out = net.fit(x, y)
np.asarray(out)
ours = batch * seq * steps / (time.perf_counter() - t0)

# torch-CPU stand-in for the reference's nd4j-native LSTM path (same
# batch/seq/width; full-sequence BPTT — torch has no TBPTT, which HELPS
# torch here: one backward per step instead of two 50-step windows)
import torch
import torch.nn as tnn

torch.manual_seed(0)
lstm_mod = tnn.LSTM(vocab, lstm, num_layers=2, batch_first=True)
head = tnn.Linear(lstm, vocab)
opt = torch.optim.RMSprop(list(lstm_mod.parameters())
                          + list(head.parameters()), lr=0.1)
lossf = tnn.CrossEntropyLoss()
xt = torch.randn(batch, seq, vocab)
yt = torch.randint(0, vocab, (batch, seq))

def tstep():
    opt.zero_grad()
    h, _ = lstm_mod(xt)
    loss = lossf(head(h).reshape(-1, vocab), yt.reshape(-1))
    loss.backward()
    opt.step()
    return float(loss)

for _ in range(2):
    tstep()
t0 = time.perf_counter()
for _ in range(steps):
    tstep()
theirs = batch * seq * steps / (time.perf_counter() - t0)

print(json.dumps({
    "backend": jax.default_backend(),
    "train_tokens_per_sec": round(ours, 1),
    "torch_cpu_tokens_per_sec": round(theirs, 1),
    "vs_torch_cpu": round(ours / theirs, 3),
    "batch": batch, "seq": seq, "lstm": lstm, "steps": steps,
    "data": "synthetic",
    "label": "cpu_for_cpu",
    "note": "ours runs TBPTT(50) = 2 backward windows per step; torch "
            "runs one full-sequence backward — a generous baseline",
}))
"""


def bench_lenet_cpu(batch=512, steps=8, quick=False):
    """OUR LeNet-5 on jax-CPU, same topology/batch/step protocol as the
    committed torch-CPU row (bench_torch_lenet_cpu). Both sides are CPU
    numbers and are labelled so."""
    parsed, err = _run_subprocess_json(
        [sys.executable, "-c", _LENET_CPU_SCRIPT, str(batch),
         str(2 if quick else steps)], 1800)
    if parsed is None:
        return {"error": err}
    return parsed


def bench_char_rnn_cpu(batch=32, seq=100, vocab=80, lstm=200, steps=6,
                       quick=False):
    """OUR char-RNN (2x GravesLSTM-200, TBPTT 50) on jax-CPU vs an inline
    torch-CPU LSTM of the same width — the configs[1] CPU-for-CPU row."""
    parsed, err = _run_subprocess_json(
        [sys.executable, "-c", _CHAR_RNN_CPU_SCRIPT, str(batch), str(seq),
         str(vocab), str(lstm), str(2 if quick else steps)], 1800)
    if parsed is None:
        return {"error": err}
    return parsed


# ---------------------------------------------------------------------------
# configs[3]: Word2Vec skip-gram negative sampling
# ---------------------------------------------------------------------------


def bench_word2vec(vocab=2000, sentences=800, sent_len=40):
    from deeplearning4j_tpu.nlp.word2vec import Word2Vec

    corpus, provenance = _w2v_corpus(vocab, sentences, sent_len)
    w2v = Word2Vec(layer_size=128, window=5, negative=5, min_word_frequency=1,
                   epochs=1, iterations=1, batch_size=2048, seed=1)
    w2v.build_vocab(corpus)
    seqs = w2v._sequences_as_indices(corpus)
    centers, _ = w2v._make_pairs(seqs, np.random.default_rng(1))
    pairs = len(centers)
    t0 = time.perf_counter()
    w2v.fit_tokens(corpus)  # includes XLA compile
    cold_dt = time.perf_counter() - t0
    t0 = time.perf_counter()
    w2v.fit_tokens(corpus)  # steady state (the number that scales to real corpora)
    warm_dt = time.perf_counter() - t0
    return {
        "pairs_per_sec": round(pairs / warm_dt, 1),
        "pairs_per_sec_incl_compile": round(pairs / cold_dt, 1),
        "pairs": int(pairs), "vocab": int(len(w2v.vocab)),
        "data": provenance,
    }


def _w2v_corpus(vocab, sentences, sent_len):
    """Bench corpus: a REAL local text file when DL4J_TPU_W2V_CORPUS
    points at one (tokenized by the framework tokenizer, provenance
    'local' — this zero-egress host cannot download text8), else the
    deterministic zipf-ish synthetic corpus, labeled as such."""
    path = envknob.get_str("DL4J_TPU_W2V_CORPUS")
    if path and os.path.isfile(path):
        from deeplearning4j_tpu.nlp.text import DefaultTokenizerFactory

        tf = DefaultTokenizerFactory()
        corpus, line_count = [], 0
        with open(path, errors="ignore") as f:
            for line in f:
                toks = tf.create(line).get_tokens()
                if len(toks) >= 5:
                    corpus.append(toks[:512])
                    line_count += 1
                if line_count >= sentences * 4:
                    break
        if corpus:
            return corpus, f"local:{os.path.basename(path)}"
        _log(f"W2V corpus {path} yielded no usable lines; falling back")
    rng = np.random.default_rng(0)
    # zipf-ish corpus over a synthetic vocab
    probs = 1.0 / np.arange(1, vocab + 1)
    probs /= probs.sum()
    words = [f"w{i}" for i in range(vocab)]
    return (
        [[words[i] for i in rng.choice(vocab, size=sent_len, p=probs)]
         for _ in range(sentences)],
        "synthetic",
    )


# ---------------------------------------------------------------------------
# configs[4]: DP scaling on the virtual 8-device mesh (subprocess, CPU)
# ---------------------------------------------------------------------------

_SCALING_SCRIPT = r"""
import json, time
import numpy as np
# virtual 8-device CPU mesh
from deeplearning4j_tpu.parallel.mesh import virtual_cpu_devices
virtual_cpu_devices(8)
import jax
from deeplearning4j_tpu.models.resnet import build_resnet50
from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper

# global batch big enough that each of the 8 shards still carries real
# work (256/8 = 32/device); both configs do the SAME total work
batch, steps = 256, 3
rng = np.random.default_rng(0)
x = rng.random((batch, 32, 32, 3)).astype(np.float32)
y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)]

def setup(n_dev):
    net = build_resnet50(input_size=32, num_classes=10)
    pw = ParallelWrapper(net, num_devices=n_dev)
    float(pw.fit(x, y))  # compile + warm once; reps below are all timed
    return pw

def timed(pw):
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = pw.fit(x, y)
    float(loss)  # host readback: sound completion fence
    return batch * steps / (time.perf_counter() - t0)

# INTERLEAVED paired reps (1,8),(1,8),(1,8): on this shared 1-core host a
# single rep's ratio swings 0.61-0.83 with background load (round-4
# measurement); interleaving means a load burst must span both halves of
# a pair to bias that pair's ratio. The committed number is the MEDIAN
# pair ratio, and the row carries every rep + the spread so the reader
# sees the noise floor instead of mistaking one draw for a stable
# measurement.
pw1, pw8 = setup(1), setup(8)
t1s, t8s, ratios = [], [], []
for _ in range(3):
    a, b = timed(pw1), timed(pw8)
    t1s.append(a); t8s.append(b); ratios.append(b / a)
# the committed throughputs are the MEDIAN PAIR'S OWN halves, so the row
# is internally consistent: throughput_8dev / throughput_1dev equals
# dp_overhead_ratio exactly (mixing max-of-reps throughputs with a
# median ratio would let the quoted numbers disagree with each other)
mi = sorted(range(3), key=lambda i: ratios[i])[1]
print(json.dumps({
    "throughput_1dev": round(t1s[mi], 2),
    "throughput_8dev": round(t8s[mi], 2),
    "dp_overhead_ratio": round(ratios[mi], 4),
    "ratio_reps": [round(r, 4) for r in ratios],
    "ratio_spread": round(max(ratios) - min(ratios), 4),
    "reps": 3,
    "ratio_stat": "median of 3 interleaved pair ratios; throughputs are "
                  "the median pair's own halves",
}))
"""


def bench_native_feed(n_files=24, batch=256, feat=784, classes=10,
                      reps=3):
    """CPU-only: exported-dataset feed throughput — the native npz
    ordered prefetcher (C worker thread parsing ahead, off the GIL) vs
    the plain np.load loop it replaces. The reference's analogous edge is
    AsyncDataSetIterator vs synchronous iteration
    (deeplearning4j-core/.../AsyncDataSetIterator.java:30). Writes real
    stored-entry npz minibatches (training_master.export_datasets format)
    to a temp dir, then times streaming them back both ways."""
    import shutil
    import tempfile

    from deeplearning4j_tpu.native import NATIVE_AVAILABLE, iter_npz

    rng = np.random.default_rng(0)
    d = tempfile.mkdtemp(prefix="dl4j_feedbench_")
    try:
        paths = []
        for i in range(n_files):
            p = os.path.join(d, f"dataset_{i:05d}.npz")
            np.savez(p, features=rng.standard_normal(
                (batch, feat)).astype(np.float32),
                labels=np.eye(classes, dtype=np.float32)[
                    rng.integers(0, classes, batch)])
            paths.append(p)
        mb = sum(os.path.getsize(p) for p in paths) / 1e6

        def drain_native(work_s=0.0):
            n = 0
            for z in iter_npz(paths):
                n += z["features"].shape[0]
                if work_s:
                    time.sleep(work_s)  # device-bound consumer: the GIL
                    # is released, the C worker parses ahead
            return n

        def drain_numpy(work_s=0.0):
            n = 0
            for p in paths:
                with np.load(p) as z:
                    n += z["features"].shape[0]
                if work_s:
                    time.sleep(work_s)
            return n

        drain_native(), drain_numpy()  # warm page cache both ways
        t = {}
        # two scenarios: `drain` is the CPU-bound worst case (consumer
        # wants every batch NOW — on a 1-core host the async copy is pure
        # overhead and np.load should win); `overlap` models the real
        # fit(path) loop where the consumer waits ~10ms on the device per
        # minibatch and the prefetcher's parse-ahead hides the file IO
        # (the AsyncDataSetIterator rationale)
        for name, fn in (("native", drain_native), ("numpy", drain_numpy)):
            for label, work in ((name, 0.0), (name + "_overlap", 0.010)):
                t0 = time.perf_counter()
                for _ in range(reps):
                    assert fn(work) == n_files * batch
                t[label] = (time.perf_counter() - t0) / reps
        return {
            "native_mb_per_s": round(mb / t["native"], 1),
            "numpy_mb_per_s": round(mb / t["numpy"], 1),
            "native_over_numpy_drain": round(t["numpy"] / t["native"], 2),
            "overlap_native_s": round(t["native_overlap"], 4),
            "overlap_numpy_s": round(t["numpy_overlap"], 4),
            "native_over_numpy_overlap": round(
                t["numpy_overlap"] / t["native_overlap"], 2),
            "native_available": bool(NATIVE_AVAILABLE),
            "files": n_files, "batch": batch,
            "payload_mb": round(mb, 1),
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def bench_scaling():
    parsed, err = _run_subprocess_json(
        [sys.executable, "-c", _SCALING_SCRIPT], 1200)
    if parsed is None:
        return {"error": err}
    parsed["note"] = (
        "equal-work DP overhead on the virtual 8-device CPU mesh: ratio of "
        "8-way-sharded to single-device throughput at the SAME global batch "
        "(1.0 = zero partitioning/collective overhead). A count of "
        "partitioning overhead on XLA:CPU, not a scaling measurement."
    )
    return parsed


# ---------------------------------------------------------------------------
# child processes
# ---------------------------------------------------------------------------


def _run_subprocess_json(args, timeout_s: int, picks_backend: bool = False):
    """Run a leg's embedded script in a child: repo PYTHONPATH, stderr tail
    on failure, last-stdout-line JSON on success. Returns (parsed_or_None,
    err_or_None).

    ``picks_backend``: the child uses whatever backend jax gives it, which
    on a chip host is the chip — and a chip belongs to one process. Such a
    child is refused once THIS process has imported jax (run the leg alone:
    ``--only=<leg>``). Children whose script pins the CPU are exempt."""
    if picks_backend and "jax" in sys.modules:
        return None, ("refused: this process has already imported jax and "
                      "may own the chip the child needs; run this leg on "
                      "its own with --only")
    repo_root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + ":" + env.get("PYTHONPATH", "")
    try:
        out = subprocess.run(args, capture_output=True, text=True,
                             timeout=timeout_s, env=env, cwd=repo_root)
        if out.returncode != 0:
            tail = (out.stderr or "").strip().splitlines()[-3:]
            return None, f"exit {out.returncode}: {' | '.join(tail)}"
        return json.loads(out.stdout.strip().splitlines()[-1]), None
    except subprocess.TimeoutExpired:
        return None, f"timed out after {timeout_s}s"
    except Exception as e:  # noqa: BLE001
        return None, f"{type(e).__name__}: {e}"


def _run_chosen_backend_child(script: str, argv):
    """A leg whose child runs on the backend jax gives it and labels its
    row with that backend."""
    parsed, err = _run_subprocess_json(
        [sys.executable, "-c", script, *argv], 900, picks_backend=True)
    return parsed if parsed is not None else {"error": err}


# ---------------------------------------------------------------------------
# north star: 100-step CPU vs accelerator f32-strict curves
# ---------------------------------------------------------------------------


def bench_north_star(steps=100):
    """100-step CPU-vs-chip float32-strict loss-curve deviation, in this
    process (utils/equivalence.run_north_star runs its CPU legs under
    jax.default_device). Writes NORTHSTAR_r.json."""
    from deeplearning4j_tpu.utils.equivalence import run_north_star

    res = run_north_star(steps=steps, artifact_path="NORTHSTAR_r.json")
    return {
        k: {
            "max_abs_deviation": v["max_abs_deviation"],
            "max_rel_deviation": v["max_rel_deviation"],
            "final_loss_cpu": v["final_loss_cpu"],
            "final_loss_accel": v["final_loss_accel"],
            "backends": f"{v['backend_cpu']} vs {v['backend_accel']}",
        }
        for k, v in res.items()
    }


def bench_lstm_kernel():
    """Fused pallas LSTM fwd AND fwd+bwd vs lax.scan on the chip
    (benchmarks/pallas_lstm_bench.py, run in this process) — writes the
    PALLAS_BENCH.json win-table rows that gate the kernel per shape
    class."""
    import importlib.util

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "benchmarks", "pallas_lstm_bench.py")
    spec = importlib.util.spec_from_file_location("pallas_lstm_bench",
                                                  script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    results = mod.run()
    return {"cases": results.get("cases"), "verdict": results.get("verdict")}


# legs that measure the device: each fails unless jax's platform is "tpu"
# (_require_chip). Every other leg is a host-side count or a CPU-pinned
# script and says which backend it ran on.
_CHIP_LEGS = {"mxu_calibration", "lenet5", "lenet5_fused", "char_rnn",
              "word2vec_sgns", "transformer_lm", "resnet50",
              "resnet50_bf16", "transformer_lm_big", "flash_attention",
              "ring_attention", "lstm_kernel", "north_star"}


def _legs(quick: bool) -> dict:
    """name -> (fn, kwargs) for every leg this file knows."""
    return {
        "mxu_calibration": (bench_mxu_calibration,
                            dict(steps=3 if quick else 10)),
        "lenet5": (bench_lenet, dict(steps=10 if quick else 30)),
        "lenet5_fused": (bench_lenet_fused, dict(reps=1 if quick else 3)),
        "dispatch_overhead": (bench_dispatch_overhead,
                              dict(steps=10 if quick else 40)),
        "remat_memory": (bench_remat_memory, dict(steps=1 if quick else 2)),
        "char_rnn": (bench_char_rnn, dict(steps=3 if quick else 10)),
        "word2vec_sgns": (bench_word2vec,
                          dict(sentences=200 if quick else 800)),
        "transformer_lm": (bench_transformer, dict(steps=2 if quick else 5)),
        "resnet50": (bench_resnet50, dict(steps=3 if quick else 10)),
        "resnet50_bf16": (bench_resnet50,
                          dict(steps=3 if quick else 10,
                               dtype_policy="performance")),
        "transformer_lm_big": (bench_transformer_big,
                               dict(steps=2 if quick else 3)),
        "flash_attention": (bench_flash_attention,
                            dict(steps=3 if quick else 10)),
        "ring_attention": (bench_ring_attention,
                           dict(steps=2 if quick else 5)),
        "lstm_kernel": (bench_lstm_kernel, {}),
        "north_star": (bench_north_star, dict(steps=10 if quick else 100)),
        "serving_throughput": (bench_serving_throughput,
                               dict(per_client=4 if quick else 16)),
        "serving_decode": (bench_serving_decode,
                           dict(streams=16, n_new=12 if quick else 24)),
        "decode_amortize": (bench_decode_amortize,
                            dict(k=4, n_new=12 if quick else 24)),
        "serving_mesh": (bench_serving_mesh,
                         dict(mesh_devices=4, n_new=10 if quick else 16)),
        "serving_resilience": (bench_serving_resilience,
                               dict(per_client=4 if quick else 8)),
        "serving_fleet": (bench_serving_fleet,
                          dict(per_client=4 if quick else 12)),
        "autoscale": (bench_autoscale,
                      dict(hammers=2 if quick else 3,
                           burst_n=6 if quick else 10)),
        "checkpoint_overhead": (bench_checkpoint_overhead,
                                dict(steps=12 if quick else 30)),
        "input_pipeline": (bench_input_pipeline,
                           dict(batches=8 if quick else 20)),
        "elastic_dp": (bench_elastic_dp, dict(rounds=6 if quick else 10)),
        "online_loop": (bench_online_loop,
                        dict(batches=6 if quick else 12,
                             predicts=12 if quick else 24)),
        "lowprec": (bench_lowprec, dict(steps=1 if quick else 2,
                                        reps=8 if quick else 20)),
        "retrieval": (bench_retrieval,
                      dict(rows=32768 if quick else 65536, queries=64)),
        "reference_cpu_lenet5_torch": (bench_torch_lenet_cpu,
                                       dict(steps=3 if quick else 8)),
        "lenet5_cpu": (bench_lenet_cpu, dict(quick=quick)),
        "char_rnn_cpu": (bench_char_rnn_cpu, dict(quick=quick)),
        "native_feed": (bench_native_feed,
                        dict(n_files=8 if quick else 24,
                             reps=1 if quick else 3)),
        "scaling_virtual8": (bench_scaling, {}),
    }


def main(argv=None) -> int:
    """Run the legs named by --only IN THIS PROCESS, print one JSON line,
    return non-zero if any leg failed (raised, returned an error, or
    needed a chip that is not there)."""
    argv = sys.argv[1:] if argv is None else list(argv)
    quick = "--quick" in argv
    only = [a.split("=", 1)[1] for a in argv if a.startswith("--only=")]
    legs = _legs(quick)
    unknown = [n for n in only if n not in legs]
    if not only or unknown:
        print(f"usage: bench.py --only=<leg> [--only=<leg> ...] [--quick] "
              f"[--trace[=DIR]]; unknown legs: {unknown}; legs: "
              f"{sorted(legs)}", file=sys.stderr)
        return 2
    # --trace[=DIR]: capture an xplane trace per leg (utils/profiling.py)
    for a in argv:
        if a == "--trace":
            os.environ["DL4J_TPU_XPLANE_TRACE"] = "xplane_traces"
        elif a.startswith("--trace="):
            os.environ["DL4J_TPU_XPLANE_TRACE"] = a.split("=", 1)[1]
    trace_dir = envknob.get_str("DL4J_TPU_XPLANE_TRACE")
    extras, failed = {}, []
    for name in only:
        fn, kw = legs[name]
        _log(f"start {name}")
        t0 = time.perf_counter()
        try:
            if name in _CHIP_LEGS:
                _require_chip()
            if trace_dir:
                from deeplearning4j_tpu.utils.profiling import xplane_trace

                with xplane_trace(os.path.join(trace_dir, name)):
                    row = fn(**kw)
                row["xplane_trace"] = os.path.join(trace_dir, name)
            else:
                row = fn(**kw)
        except Exception as e:  # noqa: BLE001 — reported, and the run fails
            _log(f"FAILED {name}: {type(e).__name__}: {e}")
            row = {"error": f"{type(e).__name__}: {e}"}
        if "error" in row:
            failed.append(name)
        row.setdefault("ts", time.strftime("%Y-%m-%dT%H:%M:%S"))
        row.setdefault("quick", bool(quick))
        if "jax" in sys.modules:
            # every row names the device this process ran on (a
            # CPU-pinned child names its own backend in the row)
            import jax

            d0 = jax.devices()[0]
            row.setdefault("device", {"platform": d0.platform,
                                      "kind": d0.device_kind,
                                      "count": len(jax.devices())})
        extras[name] = row
        _log(f"done {name} in {time.perf_counter() - t0:.1f}s")
    print(json.dumps(dict(extras, failed=failed)))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
