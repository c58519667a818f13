"""BERT family — pretraining model built on DeepSpeedTransformerLayer.

Counterpart of the reference's BERT story: the vendored test models
(`tests/unit/modeling.py` ~2600 LoC) and the BingBertSquad / bert
pretraining benchmarks (`docs/_tutorials/bert-pretraining.md`) all run
BERT through the fused `DeepSpeedTransformerLayer`. Here the encoder IS a
stack of those layers (scanned, so params stack [L, ...] and the compile
is O(1) in depth), with MLM+NSP heads for pretraining parity.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.transformer import (DeepSpeedTransformerLayer,
                                           DeepSpeedTransformerConfig)


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-12
    pre_layer_norm: bool = False      # classic BERT is post-LN
    fp16: bool = False
    bf16: bool = True                 # TPU-native default
    normalize_invertible: bool = False
    gelu_checkpoint: bool = False
    attn_dropout_checkpoint: bool = False
    # d=64 head packing in the flash kernel ("auto"|"packed"|"off");
    # forwarded to DeepSpeedTransformerConfig.head_packing. bert-large
    # is d=64 (1024/16), so "auto" packs two heads per grid step into
    # K=128 contractions on real TPU.
    attention_head_packing: str = "auto"
    # Fused non-attention epilogues ("auto"|"on"|"off"), forwarded to
    # DeepSpeedTransformerConfig.fused_ops: bias+residual+LayerNorm and
    # bias+exact-erf-GeLU as single Pallas launches
    # (ops/transformer/fused_ops.py). "auto" fuses on real TPU when
    # hidden dropout is inactive; the parameter tree is unchanged.
    fused_ops: str = "auto"
    # Run the MLM head (transform + vocab decoder) matmuls in the
    # compute dtype instead of fp32. The [hidden, vocab] decoder
    # projection is ~10% of the model's flops; in fp32 it runs at a
    # fraction of the MXU's bf16 rate (its share of the seq-128
    # pretraining step: not measured on the chip; no cell of the
    # benchmark runs BERT). LayerNorm stats stay fp32 and the loss
    # upcasts logits to fp32, so only the matmul precision changes —
    # the same contract as every encoder-layer matmul. "auto" enables
    # it on real TPU only (XLA's CPU backend emulates bf16 dots, so
    # there fp32 is the faster side); True/False force. Resolved at
    # trace time off jax.default_backend() — same AOT caveat as the
    # flash kernel's interpret auto-select.
    mlm_head_in_compute_dtype: Any = "auto"


BERT_SIZES = {
    # CI/harness size: big enough to have real trajectories, small
    # enough for the virtual CPU mesh (tests/model/)
    "bert-tiny": dict(hidden_size=128, num_hidden_layers=2,
                      num_attention_heads=4, intermediate_size=512,
                      vocab_size=512),
    "bert-base": dict(hidden_size=768, num_hidden_layers=12,
                      num_attention_heads=12, intermediate_size=3072),
    "bert-large": dict(hidden_size=1024, num_hidden_layers=24,
                       num_attention_heads=16, intermediate_size=4096),
}


def bert_config(name="bert-base", **overrides) -> BertConfig:
    base = dict(BERT_SIZES[name])
    base.update(overrides)
    return BertConfig(**base)


def _ds_layer_config(cfg: BertConfig) -> DeepSpeedTransformerConfig:
    return DeepSpeedTransformerConfig(
        hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        heads=cfg.num_attention_heads,
        attn_dropout_ratio=cfg.attention_probs_dropout_prob,
        hidden_dropout_ratio=cfg.hidden_dropout_prob,
        num_hidden_layers=cfg.num_hidden_layers,
        initializer_range=cfg.initializer_range,
        pre_layer_norm=cfg.pre_layer_norm,
        fp16=cfg.fp16,
        bf16=cfg.bf16,
        normalize_invertible=cfg.normalize_invertible,
        gelu_checkpoint=cfg.gelu_checkpoint,
        attn_dropout_checkpoint=cfg.attn_dropout_checkpoint,
        layer_norm_eps=cfg.layer_norm_eps,
        head_packing=cfg.attention_head_packing,
        fused_ops=cfg.fused_ops,
        training=True)


def additive_attention_mask(attention_mask):
    """[B, T] 1/0 -> additive [B, 1, 1, T] (None passes through).
    The ONE definition of BERT's mask arithmetic — shared by the
    module path and the ZeRO-3 scheduled path so they cannot drift."""
    if attention_mask is None:
        return None
    mask = (1.0 - attention_mask.astype(jnp.float32)) * -1e9
    return mask[:, None, None, :]


def mlm_head_dtype(cfg: BertConfig):
    """Resolve mlm_head_in_compute_dtype ("auto" = real TPU only) to
    the dtype the head matmuls run in — shared by both apply paths."""
    head_compute = cfg.mlm_head_in_compute_dtype
    if head_compute == "auto":
        head_compute = jax.default_backend() == "tpu"
    if not head_compute:
        return jnp.float32
    return (jnp.float16 if cfg.fp16 else
            jnp.bfloat16 if cfg.bf16 else jnp.float32)


class BertEmbeddings(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None,
                 deterministic: bool = True):
        cfg = self.config
        b, t = input_ids.shape
        init = nn.initializers.normal(cfg.initializer_range)
        word = self.param("word_embeddings", init,
                          (cfg.vocab_size, cfg.hidden_size))
        pos = self.param("position_embeddings", init,
                         (cfg.max_position_embeddings, cfg.hidden_size))
        tok = self.param("token_type_embeddings", init,
                         (cfg.type_vocab_size, cfg.hidden_size))
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        h = word[input_ids] + pos[:t][None] + tok[token_type_ids]
        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="LayerNorm")(h)
        return nn.Dropout(cfg.hidden_dropout_prob)(
            h, deterministic=deterministic)


class BertEncoder(nn.Module):
    """num_hidden_layers DeepSpeedTransformerLayers, scanned."""
    config: BertConfig

    @nn.compact
    def __call__(self, hidden, attention_mask, deterministic: bool = True):
        cfg = self.config
        ds_cfg = _ds_layer_config(cfg)

        class Cell(nn.Module):
            @nn.compact
            def __call__(self, h, mask, det):
                out = DeepSpeedTransformerLayer(ds_cfg)(h, mask, det)
                # scan carry must be dtype-stable: the fused layer's
                # residual/LN path is fp32 while the carry may be bf16
                return out.astype(h.dtype), None

        Scanned = nn.scan(
            Cell,
            variable_axes={"params": 0},
            split_rngs={"params": True, "dropout": True},
            in_axes=(nn.broadcast, nn.broadcast),
            length=cfg.num_hidden_layers,
            metadata_params={nn.meta.PARTITION_NAME: "layers"},
        )
        hidden, _ = Scanned(name="layer")(hidden, attention_mask,
                                          deterministic)
        return hidden


class BertModel(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None,
                 deterministic: bool = True):
        cfg = self.config
        h = BertEmbeddings(cfg, name="embeddings")(
            input_ids, token_type_ids, deterministic)
        additive_mask = additive_attention_mask(attention_mask)
        h = BertEncoder(cfg, name="encoder")(h, additive_mask,
                                             deterministic)
        # pooler: tanh(dense(CLS))
        pooled = nn.tanh(nn.Dense(cfg.hidden_size, name="pooler")(
            h[:, 0].astype(jnp.float32)))
        return h, pooled


class BertForPreTraining(nn.Module):
    """MLM + NSP heads (the BingBert pretraining objective)."""
    config: BertConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None,
                 deterministic: bool = True):
        cfg = self.config
        sequence_output, pooled = BertModel(cfg, name="bert")(
            input_ids, attention_mask, token_type_ids, deterministic)
        # MLM head: transform + LN + decoder tied to nothing (separate
        # projection keeps the head simple; tying is a config choice).
        # The head matmuls run in the compute dtype (see
        # mlm_head_in_compute_dtype): the [hidden, vocab] decoder is
        # ~10% of the step's flops and in fp32 it was the top
        # per-fusion time sink. LN stats stay fp32; the loss upcasts
        # logits to fp32.
        head_dtype = mlm_head_dtype(cfg)
        x = nn.Dense(cfg.hidden_size, dtype=head_dtype, name="transform")(
            sequence_output.astype(head_dtype))
        x = nn.gelu(x, approximate=False)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         name="transform_ln")(x)
        mlm_logits = nn.Dense(cfg.vocab_size, dtype=head_dtype,
                              name="decoder")(x.astype(head_dtype))
        nsp_logits = nn.Dense(2, name="seq_relationship")(pooled)
        return mlm_logits, nsp_logits


def _cross_entropy(logits, labels, ignore_index=-100):
    logits = logits.astype(jnp.float32)
    valid = labels != ignore_index
    safe = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None],
                               axis=-1).squeeze(-1)
    nll = (logz - gold) * valid
    return nll.sum() / jnp.maximum(valid.sum(), 1)


class BertForPreTrainingLM:
    """Engine-facing wrapper: batch keys input_ids, attention_mask,
    token_type_ids, masked_lm_labels ([B,T], -100 = unmasked), and
    next_sentence_label ([B])."""

    def __init__(self, config: BertConfig):
        self.config = config
        self.module = BertForPreTraining(config)
        # ZeRO-3 gather/release scheduler (runtime/zero/stage3.py),
        # bound by the engine when the effective zero stage is 3
        self._zero3 = None

    def bind_zero3_scheduler(self, sched):
        """Engine hook: weave (or unweave, sched=None) the explicit
        stage-3 gather scheduler through the loss path. The parameter
        tree is IDENTICAL either way — checkpoints interchange."""
        self._zero3 = sched

    def init(self, rng, example_batch):
        ids = example_batch["input_ids"]
        variables = self.module.init(
            {"params": rng, "dropout": rng}, ids, deterministic=True)
        return variables["params"]

    _zero3_dropout_warned = False

    def _zero3_active(self, deterministic):
        """Scheduled-path gate: dropout-active traces stay on the
        module path — the scheduled stack folds its own per-layer rng
        stream, which would change dropout masks vs the module path
        (the fused_ops "auto = dropout-inactive" convention)."""
        if self._zero3 is None:
            return False
        cfg = self.config
        if deterministic or (cfg.hidden_dropout_prob == 0.0 and
                             cfg.attention_probs_dropout_prob == 0.0):
            return True
        if not BertForPreTrainingLM._zero3_dropout_warned:
            BertForPreTrainingLM._zero3_dropout_warned = True
            from deepspeed_tpu.utils.logging import logger
            logger.warning(
                "ZeRO-3 gather scheduler: dropout is active, so this "
                "trace uses the module path (implicit GSPMD gathers); "
                "set the dropout probs to 0.0 for the scheduled "
                "gather/release path in training")
        return False

    def loss_fn(self, params, batch, rngs=None, deterministic=False, **_):
        if self._zero3_active(deterministic):
            mlm_logits, nsp_logits = self._zero3_forward(
                params, batch, rngs, deterministic)
        else:
            mlm_logits, nsp_logits = self.module.apply(
                {"params": params}, batch["input_ids"],
                batch.get("attention_mask"), batch.get("token_type_ids"),
                deterministic, rngs=rngs or {})
        loss = _cross_entropy(mlm_logits, batch["masked_lm_labels"])
        if "next_sentence_label" in batch:
            loss = loss + _cross_entropy(nsp_logits,
                                         batch["next_sentence_label"])
        return loss

    def _zero3_forward(self, params, batch, rngs, deterministic):
        """Scheduled stage-3 forward: the encoder's stacked [L, ...]
        DeepSpeedTransformerLayer params run under the gather/prefetch/
        release schedule (attention mask threads through as a
        non-differentiable broadcast input); embeddings/pooler/heads
        gather once for the step. Same math as the module path."""
        cfg = self.config
        sched = self._zero3
        rngs = rngs or {}
        ids = batch["input_ids"]
        attention_mask = batch.get("attention_mask")
        token_type_ids = batch.get("token_type_ids")
        bert_p = params["bert"]
        # dropout-inactive by the _zero3_active gate
        h = BertEmbeddings(cfg).apply(
            {"params": sched.gather(bert_p["embeddings"],
                                    name="bert.embeddings")},
            ids, token_type_ids, deterministic, rngs=rngs)
        additive_mask = additive_attention_mask(attention_mask)

        (_, stacked), = bert_p["encoder"]["layer"].items()
        ds_cfg = _ds_layer_config(cfg)
        layer = DeepSpeedTransformerLayer(ds_cfg)

        def body(lp, x, rng_k, *extra):
            mask = extra[0] if extra else None
            out = layer.apply({"params": lp}, x, mask, deterministic)
            # dtype-stable carry, like the nn.scan cell: the fused
            # layer's residual/LN path is fp32 while the carry may not be
            return out.astype(x.dtype)

        base_rng = rngs.get("dropout", jax.random.PRNGKey(0))
        extra = () if additive_mask is None else (additive_mask,)
        h = sched.apply_layers(body, stacked, h, base_rng, extra=extra,
                               name="bert.encoder")

        pooled = nn.tanh(nn.Dense(cfg.hidden_size).apply(
            {"params": sched.gather(bert_p["pooler"],
                                    name="bert.pooler")},
            h[:, 0].astype(jnp.float32)))

        head_dtype = mlm_head_dtype(cfg)
        x = nn.Dense(cfg.hidden_size, dtype=head_dtype).apply(
            {"params": sched.gather(params["transform"],
                                    name="transform")},
            h.astype(head_dtype))
        x = nn.gelu(x, approximate=False)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps,
                         dtype=jnp.float32).apply(
            {"params": sched.gather(params["transform_ln"],
                                    name="transform_ln")}, x)
        mlm_logits = nn.Dense(cfg.vocab_size, dtype=head_dtype).apply(
            {"params": sched.gather(params["decoder"], name="decoder")},
            x.astype(head_dtype))
        nsp_logits = nn.Dense(2).apply(
            {"params": sched.gather(params["seq_relationship"],
                                    name="seq_relationship")}, pooled)
        return mlm_logits, nsp_logits


def tiny_bert_config(**overrides):
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, intermediate_size=128,
                max_position_embeddings=128, hidden_dropout_prob=0.0,
                attention_probs_dropout_prob=0.0, bf16=False)
    base.update(overrides)
    return BertConfig(**base)
