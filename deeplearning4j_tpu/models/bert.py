"""BERT-style bidirectional encoder with masked-LM pretraining.

Beyond-reference model family (the reference era, dl4j 0.4, predates
BERT), built on the same whole-step-jit machinery as the flagship LM:
the per-layer block body mirrors models/transformer.py's pre-LN design
but attends BIDIRECTIONALLY with a key-padding mask (the reference's
closest relatives are its masked time-series paths —
MultiLayerNetwork.setLayerMaskArrays :2332 — and the word2vec CBOW
context objective, SURVEY.md section 2.3; the MLM objective is CBOW's
"predict the held-out token from both sides" idea at transformer scale).

Masking follows the standard 80/10/10 recipe: of the positions selected
for prediction, 80% become [MASK], 10% a random token, 10% keep the
original. Loss is cross-entropy over the SELECTED positions only
(weights argument), with the tied embedding head.

Everything (forward + masked loss + Adam) traces into ONE XLA program
per batch shape; `fit` and `masked_accuracy` are the user surface.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.models.transformer import (
    Params,
    _adam_update,
    _donation_kwargs,
    _ln,
    _scheduled_lr,
    _validate_schedule,
    init_opt_state,
)


@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 1000
    d_model: int = 128
    n_layers: int = 2
    n_heads: int = 4
    d_ff: int = 512
    max_len: int = 64
    learning_rate: float = 1e-3
    weight_decay: float = 0.0
    clip_grad_norm: float = 0.0
    warmup_steps: int = 0
    lr_schedule: str = "none"
    total_steps: int = 0
    mlm_prob: float = 0.15
    pad_token_id: int = 0
    # [MASK] id. Default claims the TOP id: vocab_size must INCLUDE a
    # reserved slot at vocab_size-1 (as examples/bert_mlm.py reserves
    # [PAD]/[MASK] in its VocabCache) — otherwise pass the real id, or
    # the rarest vocab word silently doubles as the mask marker.
    mask_token_id: Optional[int] = None
    seed: int = 0
    # activation remat for the encoder block scan — the flagship's ladder
    # (ops/remat.py, models/transformer.TransformerConfig.remat): "auto"
    # defers to DL4J_TPU_REMAT; none/dots/block pin a rung
    remat: str = "auto"

    @property
    def mask_id(self) -> int:
        if self.mask_token_id is None:
            # audible, not silent (ADVICE r4): if the caller's vocab does
            # NOT reserve the top slot, the rarest real token doubles as
            # [MASK] and corrupts the MLM objective with no other signal.
            # warnings' default filter dedupes per call site, so the fit
            # loop isn't spammed.
            import warnings

            warnings.warn(
                "BertConfig.mask_token_id not set: defaulting [MASK] to "
                f"vocab_size-1 = {self.vocab_size - 1}. Make sure the "
                "vocab reserves that slot (examples/bert_mlm.py does), "
                "or pass the real mask id.", stacklevel=2)
            return self.vocab_size - 1
        return self.mask_token_id


def init_params(cfg: BertConfig) -> Params:
    """Same init family as the flagship (scaled-normal embeddings, zeros
    biases, ones LN gains); block leaves stacked [L, ...] for lax.scan."""
    k = jax.random.PRNGKey(cfg.seed)
    ks = jax.random.split(k, 8)
    d, f, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    s = 0.02

    def nrm(key, shape, scale=s):
        return (jax.random.normal(key, shape, jnp.float32) * scale)

    return {
        "embed": nrm(ks[0], (cfg.vocab_size, d)),
        "pos": nrm(ks[1], (cfg.max_len, d)),
        "blocks": {
            "ln1_g": jnp.ones((L, d), jnp.float32), "ln1_b": jnp.zeros((L, d), jnp.float32),
            "Wq": nrm(ks[2], (L, d, d)), "Wk": nrm(ks[3], (L, d, d)),
            "Wv": nrm(ks[4], (L, d, d)), "Wo": nrm(ks[5], (L, d, d)),
            "ln2_g": jnp.ones((L, d), jnp.float32), "ln2_b": jnp.zeros((L, d), jnp.float32),
            "W1": nrm(ks[6], (L, d, f)), "b1": jnp.zeros((L, f), jnp.float32),
            "W2": nrm(ks[7], (L, f, d)), "b2": jnp.zeros((L, d), jnp.float32),
        },
        "lnf_g": jnp.ones((d,), jnp.float32), "lnf_b": jnp.zeros((d,), jnp.float32),
    }


def _bi_attention(q, k, v, n_heads: int, key_mask) -> jax.Array:
    """Full bidirectional attention with an optional key-padding mask
    (key_mask [N, T] bool; False keys are invisible to every query) —
    the encoder twin of transformer._attention's causal path."""
    n, t, d = q.shape
    hd = d // n_heads
    qh = q.reshape(n, t, n_heads, hd)
    kh = k.reshape(n, t, n_heads, hd)
    vh = v.reshape(n, t, n_heads, hd)
    s = jnp.einsum("nqhd,nkhd->nhqk", qh, kh) / jnp.sqrt(
        jnp.asarray(hd, q.dtype))
    if key_mask is not None:
        s = jnp.where(key_mask[:, None, None, :], s,
                      jnp.asarray(-1e9, s.dtype))
    from deeplearning4j_tpu.ops.dtypes import softmax_dtype

    p = jax.nn.softmax(s.astype(softmax_dtype(s.dtype)),
                       axis=-1).astype(q.dtype)
    return jnp.einsum("nhqk,nkhd->nqhd", p, vh).reshape(n, t, d)


def encode(params: Params, tokens: jax.Array, cfg: BertConfig,
           key_mask=None) -> jax.Array:
    """tokens [N, T] -> hidden states [N, T, d] (post final-LN). key_mask
    defaults to tokens != pad_token_id."""
    n, t = tokens.shape
    if key_mask is None:
        key_mask = tokens != cfg.pad_token_id
    h = params["embed"][tokens] + params["pos"][:t][None]

    def block(h, bp):
        x = _ln(h, bp["ln1_g"], bp["ln1_b"])
        att = _bi_attention(x @ bp["Wq"], x @ bp["Wk"], x @ bp["Wv"],
                            cfg.n_heads, key_mask)
        h = h + att @ bp["Wo"]
        x = _ln(h, bp["ln2_g"], bp["ln2_b"])
        return h + jax.nn.gelu(x @ bp["W1"] + bp["b1"]) @ bp["W2"] \
            + bp["b2"], None

    from deeplearning4j_tpu.ops.remat import remat_wrap

    # same remat ladder as the flagship's block scan (cfg.remat resolved
    # at trace time; the MLM pretrain step traces through here)
    block = remat_wrap(block, cfg.remat, prevent_cse=False)
    h, _ = lax.scan(block, h, params["blocks"])
    return _ln(h, params["lnf_g"], params["lnf_b"])


def mlm_logits(params: Params, tokens: jax.Array, cfg: BertConfig,
               key_mask=None) -> jax.Array:
    return encode(params, tokens, cfg, key_mask) @ params["embed"].T


def mlm_loss(params: Params, tokens: jax.Array, targets: jax.Array,
             weights: jax.Array, cfg: BertConfig) -> jax.Array:
    """Cross-entropy over the selected (weight > 0) positions only."""
    from deeplearning4j_tpu.ops.dtypes import softmax_dtype

    logits = mlm_logits(params, tokens, cfg)
    # at-least-f32 (not a hard f32 pin): a downcast from f64 quantizes the
    # loss below the gradcheck's central-difference resolution
    dt = softmax_dtype(logits.dtype)
    logp = jax.nn.log_softmax(logits.astype(dt), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    w = weights.astype(dt)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def mask_tokens(tokens: np.ndarray, cfg: BertConfig,
                rng: np.random.Generator) -> Tuple[np.ndarray, np.ndarray,
                                                   np.ndarray]:
    """The 80/10/10 masking recipe (host-side, like the reference's
    host-side minibatch assembly). Returns (inputs, targets, weights):
    inputs has the corruptions applied, targets the original ids,
    weights 1.0 at predicted positions. Pad positions are never
    selected."""
    tokens = np.asarray(tokens)
    selectable = tokens != cfg.pad_token_id
    sel = (rng.random(tokens.shape) < cfg.mlm_prob) & selectable
    # guarantee at least one prediction per batch (tiny batches in tests)
    if not sel.any():
        i = np.argwhere(selectable)
        if len(i):
            r, c = i[rng.integers(0, len(i))]
            sel[r, c] = True
    roll = rng.random(tokens.shape)
    inputs = tokens.copy()
    inputs[sel & (roll < 0.8)] = cfg.mask_id
    rand_pos = sel & (roll >= 0.8) & (roll < 0.9)
    # random replacements drawn from the vocab MINUS the pad id: a "random"
    # pad token would become invisible as a key (key_mask is computed from
    # the corrupted inputs) and distort every position's context
    r = rng.integers(0, cfg.vocab_size - 1, int(rand_pos.sum()))
    r[r >= cfg.pad_token_id] += 1
    inputs[rand_pos] = r
    weights = sel.astype(np.float32)
    return inputs, tokens, weights


def _build_mlm_step(cfg: BertConfig):
    _validate_schedule(cfg)  # same loud rejection as the flagship's step
    from deeplearning4j_tpu.ops import lowprec

    lp = lowprec.train_policy()

    def step(params, opt, inputs, targets, weights):
        if lp:
            # bf16 master-weight mode (ops/lowprec.py, same shape as
            # transformer._build_step): scale rides the opt tree, the
            # backward runs on the scaled loss of the bf16-cast params
            ls = lowprec.opt_scale_state(opt)
            base = {"m": opt["m"], "v": opt["v"], "t": opt["t"]}
            scale = ls["scale"]
            loss, grads = jax.value_and_grad(
                lambda p: mlm_loss(lowprec.cast_tree(p), inputs, targets,
                                   weights, cfg).astype(jnp.float32)
                * scale)(params)
            loss = loss / scale
            grads = lowprec.unscale(grads, scale)
            finite = lowprec.finite_tree(grads)
            lr = _scheduled_lr(cfg, base["t"] + 1)
            new_params, new_base = _adam_update(
                params, grads, base, lr, weight_decay=cfg.weight_decay,
                clip_grad_norm=cfg.clip_grad_norm)
            params = lowprec.select_trees(finite, new_params, params)
            base = lowprec.select_trees(finite, new_base, base)
            ls = lowprec.advance_scale(ls, finite)
            return params, lowprec.opt_with_scale(base, ls), loss

        loss, grads = jax.value_and_grad(mlm_loss)(
            params, inputs, targets, weights, cfg)
        lr = _scheduled_lr(cfg, opt["t"] + 1)
        params, opt = _adam_update(params, grads, opt, lr,
                                   weight_decay=cfg.weight_decay,
                                   clip_grad_norm=cfg.clip_grad_norm)
        return params, opt, loss

    return step


def make_train_step(cfg: BertConfig):
    """One jitted optimizer step: masked loss + Adam, the whole-step-jit
    discipline shared with the flagship."""
    # donate params + Adam m/v on accelerators (the flagship's policy:
    # optimizer state is ~2/3 of training-state HBM — update in place)
    return jax.jit(_build_mlm_step(cfg), **_donation_kwargs())


def make_train_multi_step(cfg: BertConfig):
    """K optimizer steps fused into ONE XLA program (lax.scan over
    stacked pre-masked batches [K, N, T] — the flagship's fit_batches
    dispatch amortization, transformer.make_train_multi_step, applied to
    the MLM objective: K steps cost one dispatch instead of K).
    Serially equivalent to K make_train_step calls on the same
    masked batches."""
    from deeplearning4j_tpu.models.transformer import _multi_from_step

    return jax.jit(_multi_from_step(_build_mlm_step(cfg)),
                   **_donation_kwargs())


def init_classifier_head(cfg: BertConfig, n_classes: int,
                         seed: int = 0) -> Params:
    """Fresh linear classification head (the reference's fine-tune-era
    analog is replacing the output layer atop pretrained weights —
    its TransferLearning API is post-0.4; the 0.4 idiom is the
    pretrain-then-finetune DBN flow, MultiLayerNetwork.pretrain :1103
    followed by supervised fit)."""
    k = jax.random.PRNGKey(seed)
    return {"Wc": jax.random.normal(k, (cfg.d_model, n_classes),
                                    jnp.float32) * 0.02,
            "bc": jnp.zeros((n_classes,), jnp.float32)}


def classify_logits(params: Params, head: Params, tokens: jax.Array,
                    cfg: BertConfig) -> jax.Array:
    """Sequence classification [N, C]: mean-pool the encoder's hidden
    states over NON-PAD positions (no [CLS] convention needed — pooling
    over real tokens is the mask-aware equivalent; the reference's
    closest analog is the masked global pooling of its time-series
    classification path, MultiLayerNetwork masked evaluate :2316), then
    a linear head."""
    key_mask = tokens != cfg.pad_token_id
    h = encode(params, tokens, cfg, key_mask)
    w = key_mask.astype(h.dtype)[..., None]
    pooled = jnp.sum(h * w, axis=1) / jnp.maximum(jnp.sum(w, axis=1), 1.0)
    return pooled @ head["Wc"] + head["bc"]


def make_finetune_step(cfg: BertConfig, n_classes: int,
                       encoder_lr_scale: float = 1.0):
    """One jitted fine-tune step over encoder + head: cross-entropy on
    the pooled classification logits; encoder_lr_scale < 1 gives the
    pretrained encoder a smaller effective LR than the fresh head
    (discriminative fine-tuning), 0 freezes it entirely.

    The scale is applied to the encoder's UPDATE (new = old + scale *
    delta), NOT to its gradients: Adam normalizes by m/(sqrt(v)+eps), so
    scaling gradients by c scales m and sqrt(v) equally and cancels —
    gradient scaling is a silent no-op for any c in (0, 1). Update
    scaling also covers the weight-decay term, so scale=0 truly freezes
    (decay included)."""
    _validate_schedule(cfg)

    def loss_fn(both, tokens, labels):
        logits = classify_logits(both["encoder"], both["head"], tokens, cfg)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None],
                                             axis=-1))

    from deeplearning4j_tpu.ops import lowprec

    lp = lowprec.train_policy()

    def step(both, opt, tokens, labels):
        if lp:
            ls = lowprec.opt_scale_state(opt)
            base = {"m": opt["m"], "v": opt["v"], "t": opt["t"]}
            scale = ls["scale"]
            loss, grads = jax.value_and_grad(
                lambda b: loss_fn(lowprec.cast_tree(b), tokens, labels)
                * scale)(both)
            loss = loss / scale
            grads = lowprec.unscale(grads, scale)
            finite = lowprec.finite_tree(grads)
            lr = _scheduled_lr(cfg, base["t"] + 1)
            new, new_base = _adam_update(
                both, grads, base, lr, weight_decay=cfg.weight_decay,
                clip_grad_norm=cfg.clip_grad_norm)
            if encoder_lr_scale != 1.0:
                new["encoder"] = jax.tree_util.tree_map(
                    lambda old, n: old + encoder_lr_scale * (n - old),
                    both["encoder"], new["encoder"])
            new = lowprec.select_trees(finite, new, both)
            base = lowprec.select_trees(finite, new_base, base)
            ls = lowprec.advance_scale(ls, finite)
            return new, lowprec.opt_with_scale(base, ls), loss

        loss, grads = jax.value_and_grad(loss_fn)(both, tokens, labels)
        lr = _scheduled_lr(cfg, opt["t"] + 1)
        new, opt = _adam_update(both, grads, opt, lr,
                                weight_decay=cfg.weight_decay,
                                clip_grad_norm=cfg.clip_grad_norm)
        if encoder_lr_scale != 1.0:
            new["encoder"] = jax.tree_util.tree_map(
                lambda old, n: old + encoder_lr_scale * (n - old),
                both["encoder"], new["encoder"])
        return new, opt, loss

    return jax.jit(step, **_donation_kwargs())


class BertClassifier:
    """Fine-tune a (pretrained) BertMLM encoder for sequence
    classification — the pretrain -> fine-tune arc."""

    def __init__(self, mlm: "BertMLM", n_classes: int,
                 encoder_lr_scale: float = 1.0):
        self.cfg = mlm.cfg
        self.n_classes = n_classes
        self._encoder_lr_scale = encoder_lr_scale
        self.state = {"encoder": mlm.params,
                      "head": init_classifier_head(mlm.cfg, n_classes,
                                                   seed=mlm.cfg.seed + 1)}
        self.opt = init_opt_state(self.state)
        self._step = make_finetune_step(mlm.cfg, n_classes,
                                        encoder_lr_scale)
        self._logits = jax.jit(
            lambda st, t: classify_logits(st["encoder"], st["head"], t,
                                          self.cfg))

    def fit(self, tokens, labels) -> float:
        self.state, self.opt, loss = self._step(
            self.state, self.opt, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(labels, jnp.int32))
        return float(loss)

    def predict(self, tokens) -> np.ndarray:
        return np.asarray(jnp.argmax(
            self._logits(self.state, jnp.asarray(tokens, jnp.int32)), -1))

    def accuracy(self, tokens, labels) -> float:
        return float((self.predict(tokens) == np.asarray(labels)).mean())

    def save(self, path: str) -> None:
        """Checkpoint the fine-tuned encoder+head through the shared
        flagship zip layout (coefficients = the {'encoder','head'} state
        tree; n_classes/encoder_lr_scale recorded in metadata so load
        rebuilds the exact model)."""
        from deeplearning4j_tpu.utils.serialization import (
            write_flagship_zip,
        )

        write_flagship_zip(
            path, "BertClassifier", self.cfg, self.state, self.opt,
            extra_meta={"n_classes": self.n_classes,
                        "encoder_lr_scale": self._encoder_lr_scale})

    @classmethod
    def load(cls, path: str,
             load_updater: bool = True) -> "BertClassifier":
        from deeplearning4j_tpu.utils.serialization import (
            _npz_bytes_into_tree,
            read_flagship_zip,
        )

        cfg_dict, coeff, upd, meta = read_flagship_zip(
            path, "BertClassifier")
        mlm = BertMLM(BertConfig(**cfg_dict))
        clf = cls(mlm, n_classes=int(meta["n_classes"]),
                  encoder_lr_scale=float(meta.get("encoder_lr_scale",
                                                  1.0)))
        clf.state = _npz_bytes_into_tree(coeff, clf.state)
        if load_updater and upd is not None:
            clf.opt = _npz_bytes_into_tree(upd, clf.opt)
        return clf


class BertMLM:
    """User surface: masked-LM pretraining + masked-token evaluation."""

    def __init__(self, cfg: BertConfig):
        if cfg.d_model % cfg.n_heads:
            raise ValueError("n_heads must divide d_model")
        self.cfg = cfg
        self.params = init_params(cfg)
        self.opt = init_opt_state(self.params)
        self._step = make_train_step(cfg)
        self._multi = None  # built on first fit_batches
        # jitted eval surfaces too (whole-step-jit discipline: eager eval
        # would dispatch op by op)
        self._logits = jax.jit(lambda p, t: mlm_logits(p, t, cfg))
        self._encode = jax.jit(lambda p, t: encode(p, t, cfg))
        self._rng = np.random.default_rng(cfg.seed)
        from deeplearning4j_tpu.ops.memory import MemoryStats

        # AOT memory ledger (ops/memory.py), populated by measure_memory
        self.memory_stats = MemoryStats()
        from deeplearning4j_tpu.obs.registry import register_net

        # ledger-registration convention (PR 7): the ledger joins the
        # central MetricsRegistry at its attach point (weakly held)
        register_net(self)

    def measure_memory(self, inputs, targets,
                       weights) -> Optional[dict]:
        """AOT memory accounting for the MLM train step on this (already
        masked) batch — lower + compile + memory_analysis, no execution;
        recorded under 'train_step' in self.memory_stats."""
        from deeplearning4j_tpu.ops import memory as memory_mod

        return memory_mod.measure(
            self.memory_stats, "train_step", self._step, self.params,
            self.opt, jnp.asarray(inputs, jnp.int32),
            jnp.asarray(targets, jnp.int32),
            jnp.asarray(weights, jnp.float32))

    def fit(self, tokens) -> float:
        """One masked-LM step on a [N, T] int batch (masking re-drawn
        per call, as per-epoch dynamic masking)."""
        inputs, targets, weights = mask_tokens(tokens, self.cfg, self._rng)
        self.params, self.opt, loss = self._step(
            self.params, self.opt, jnp.asarray(inputs, jnp.int32),
            jnp.asarray(targets, jnp.int32), jnp.asarray(weights))
        return float(loss)

    def fit_batches(self, tokens_k) -> float:
        """K masked-LM steps in ONE XLA program: [K, N, T] stacked
        batches, masking drawn host-side per batch from the same rng
        stream fit() uses (so K fit() calls and one fit_batches on the
        same batches take identical optimizer steps). Returns the last
        step's loss."""
        tokens_k = np.asarray(tokens_k)
        if tokens_k.ndim != 3 or tokens_k.shape[0] == 0:
            raise ValueError(
                f"fit_batches expects stacked batches [K, N, T] with "
                f"K >= 1, got shape {tokens_k.shape} (a single [N, T] "
                "batch belongs in fit())")
        drawn = [mask_tokens(b, self.cfg, self._rng) for b in tokens_k]
        stack = lambda i, dt: jnp.asarray(np.stack([d[i] for d in drawn]),
                                          dt)
        if self._multi is None:
            self._multi = make_train_multi_step(self.cfg)
        self.params, self.opt, losses = self._multi(
            self.params, self.opt, stack(0, jnp.int32),
            stack(1, jnp.int32), stack(2, jnp.float32))
        return float(losses[-1])

    def masked_accuracy(self, tokens, n_draws: int = 1) -> float:
        """Fraction of masked positions predicted exactly (argmax).

        Draws masks from a DEDICATED eval RNG: consuming the training
        stream (self._rng) here would make every subsequent fit() step's
        dynamic masking depend on the eval cadence — two runs with
        identical fit sequences but different eval calls would train on
        different data (ADVICE r4). Re-seeded per call, so the estimate
        is also deterministic for a given (seed, n_draws)."""
        eval_rng = np.random.default_rng((self.cfg.seed, 0xE7A1))
        hits = total = 0
        for _ in range(n_draws):
            inputs, targets, weights = mask_tokens(tokens, self.cfg,
                                                   eval_rng)
            logits = self._logits(self.params,
                                  jnp.asarray(inputs, jnp.int32))
            pred = np.asarray(jnp.argmax(logits, axis=-1))
            m = weights > 0
            hits += int((pred[m] == np.asarray(targets)[m]).sum())
            total += int(m.sum())
        return hits / max(total, 1)

    def predict_logits(self, tokens) -> np.ndarray:
        """MLM logits [N, T, V] through the jitted eval surface (the
        fill-in-the-blank path: argmax at a masked position)."""
        return np.asarray(self._logits(self.params,
                                       jnp.asarray(tokens, jnp.int32)))

    def save(self, path: str) -> None:
        """Checkpoint in the framework's ModelSerializer zip layout
        (shared writer — utils/serialization.write_flagship_zip;
        reference ModelSerializer.java:70-110 three-part semantic:
        configuration + coefficients + updater)."""
        from deeplearning4j_tpu.utils.serialization import (
            write_flagship_zip,
        )

        write_flagship_zip(path, "BertMLM", self.cfg, self.params,
                           self.opt)

    @classmethod
    def load(cls, path: str, load_updater: bool = True) -> "BertMLM":
        from deeplearning4j_tpu.utils.serialization import (
            _npz_bytes_into_tree,
            read_flagship_zip,
        )

        cfg_dict, coeff, upd, _ = read_flagship_zip(path, "BertMLM")
        lm = cls(BertConfig(**cfg_dict))
        lm.params = _npz_bytes_into_tree(coeff, lm.params)
        if load_updater and upd is not None:
            lm.opt = _npz_bytes_into_tree(upd, lm.opt)
        return lm

    def embed_tokens(self, tokens) -> np.ndarray:
        """Contextual embeddings [N, T, d] (the feature-extraction use)."""
        return np.asarray(self._encode(self.params,
                                       jnp.asarray(tokens, jnp.int32)))
