"""Activation-rematerialization policy plane (``DL4J_TPU_REMAT``).

The flagship TransformerLM's batch is bounded by HBM: the preflight
(ops/memory.transformer_preflight) puts d2048 L8 b32 past one chip's
memory without remat. The reference never had this problem because
its training loop was an op-by-op dispatch that fused nothing
(MultiLayerNetwork.java:1017 — every activation lived exactly as long as
the JVM held a reference); whole-step XLA compilation (ARCHITECTURE.md
decision #1) buys the dispatch win at the cost of every layer's residual
buffers staying live from forward until their backward use. Activation
rematerialization (Chen et al., "Training Deep Nets with Sublinear
Memory Cost") is the standard lever every production JAX stack ships:
trade recompute for HBM by checkpointing the layer boundary and
re-running the layer body in the backward pass.

One knob, a three-rung ladder (each rung strictly less HBM, strictly
more recompute):

  ``none``   store every activation (fastest; the pre-PR behavior)
  ``dots``   ``jax.checkpoint(policy=dots_saveable)``: keep matmul
             outputs (the MXU work), recompute elementwise ops — the
             cheap middle rung (recompute is VPU-only)
  ``block``  full per-block remat: store only the residual-stream carry
             between blocks, recompute the whole block body in the
             backward pass (sublinear activation memory in depth)

Resolution order: an explicit policy string wins; ``"auto"`` (the
config default everywhere) defers to the ``DL4J_TPU_REMAT`` env knob;
an unset knob means ``none``. The policy is read at TRACE time — the
same read-at-jit-construction discipline as the donation policy
(ops/dispatch.donation_enabled): flipping the env after a step has
compiled does not retroactively change it.

Consumed by: models/transformer.forward's block scan (train_step,
fit_batches, and the accum-path microbatch scan all trace through it),
models/bert.encode's block scan, and the containers' per-layer
``remat_apply`` (nn/common.apply_layer — the pre-existing
``gradient_checkpointing`` conf flag is this ladder's ``block`` rung,
now unified under the same knob). Measured evidence lives in the
``remat_memory`` bench leg + REMAT_MEMORY.json (AOT
``memory_analysis`` temp-bytes ladder — ops/memory.py).
"""

from __future__ import annotations

from typing import Optional

from deeplearning4j_tpu.ops import env as envknob

ENV_REMAT = "DL4J_TPU_REMAT"

# ladder order: increasing HBM savings, increasing backward recompute
POLICIES = ("none", "dots", "block")


def remat_policy(configured: Optional[str] = "auto") -> str:
    """Resolve the active remat policy.

    ``configured`` is the model/config-level request: a policy name pins
    it; ``"auto"`` (or None/empty) defers to the ``DL4J_TPU_REMAT`` env
    knob, whose absence means ``none``. Unknown names raise loudly — a
    typo'd policy must not silently train without remat and OOM on the
    chip (the exact failure the ladder exists to prevent)."""
    v = (configured or "auto").strip().lower()
    if v == "auto":
        v = envknob.raw(ENV_REMAT, "").strip().lower() or "none"
    if v not in POLICIES:
        raise ValueError(
            f"unknown remat policy {v!r} (known: {', '.join(POLICIES)}, "
            "or 'auto' to defer to DL4J_TPU_REMAT)")
    return v


def checkpoint_kwargs(policy: str) -> dict:
    """kwargs for ``jax.checkpoint`` implementing one active rung
    (``none`` is not an active rung — callers skip the wrap entirely)."""
    if policy == "block":
        return {}
    if policy == "dots":
        from jax.ad_checkpoint import checkpoint_policies

        return {"policy": checkpoint_policies.dots_saveable}
    raise ValueError(f"no checkpoint kwargs for policy {policy!r}")


def remat_wrap(fn, policy: Optional[str] = "auto", *,
               prevent_cse: bool = True):
    """Wrap a function (typically a ``lax.scan`` block body) per the
    resolved policy; ``none`` returns it untouched. ``prevent_cse=False``
    is for bodies that sit inside a scan — the loop boundary already
    blocks the CSE the checkpoint barriers guard against, so the default
    barriers would only cost fusion opportunities (the same rationale as
    nn/common.remat_apply's flag)."""
    pol = remat_policy(policy)
    if pol == "none":
        return fn
    import jax

    return jax.checkpoint(fn, prevent_cse=prevent_cse,
                          **checkpoint_kwargs(pol))
