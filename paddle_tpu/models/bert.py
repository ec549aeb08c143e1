"""BERT model family (flagship config for the north-star benchmark).

Reference mapping: BERT-base pretraining is BASELINE.json config[2]
("models/PaddleNLP — matmul/layer_norm/softmax hot path"); the reference
framework builds it from ``fluid.layers`` primitives (fc/layer_norm/matmul/
softmax, ``layers/nn.py``). Here it is a Layer over the Pallas-flash
transformer stack (``nn/transformer.py``) with TP/SP sharding hints baked
into every projection, so the same model object runs 1-chip or over a
dp×fsdp×tp×sp mesh unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.layers import Dropout, Embedding, LayerNorm, Linear
from paddle_tpu.nn.module import Layer, LayerList, StackedLayers
from paddle_tpu.nn.transformer import ACT_SPEC, TransformerEncoderLayer, _constrain
from paddle_tpu.ops import activation as ops_act
from paddle_tpu.ops import attention as ops_attn
from paddle_tpu.ops import labelled_nll as ops_nll

#: the ``jax.named_scope`` names this model opens beside the blocks' own
#: (``nn.transformer.BLOCK_SCOPES``); ``mlm_head`` covers the transform,
#: LayerNorm and decoder matmul at every position in
#: :meth:`BertPretrainingHeads.forward` (inference), and in
#: :meth:`BertForPretraining.loss` the walk of ``ops.labelled_nll`` over
#: the labelled positions, forward and backward (PERF.md section 3)
MODEL_SCOPES = ("embeddings", "pooler", "mlm_head", "nsp_head")


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_size: int = 3072
    max_position: int = 512
    type_vocab_size: int = 2
    dropout: float = 0.1
    attn_dropout: float = 0.1
    pre_ln: bool = False
    attn_impl: str = "auto"
    # pipeline parallelism: run the encoder stack through the GPipe
    # schedule over the "pp" mesh axis (parallel/pipeline.py), cutting the
    # L layers into pp stages and streaming pp_microbatches through them.
    # Embeddings/heads stay outside the pipelined middle.
    pipeline: bool = False
    pp_microbatches: int = 2
    # "gpipe", or "circular" (interleaved 1F1B; pp_circuits virtual
    # stages per device — smaller bubble, see
    # parallel.pipeline.pipeline_bubble_fraction)
    pp_schedule: str = "gpipe"
    pp_circuits: int = 1
    # params already hold the circular schedule's interleaved layer order
    # (convert once with parallel.pipeline.interleave_stack on the
    # encoder stack) — skips the per-step cross-device weight reshuffle
    pp_pre_interleaved: bool = False
    # scan-over-layers param layout: encoder params stored as stacked
    # (L, ...) leaves sharded over "pp" from init — one compiled block
    # (faster compile), and pipeline stages own their rows by placement
    # (no in-graph stack/reshard). Defaults on when pipeline is on.
    # NOTE: this changes the checkpoint tree layout; convert older
    # per-layer checkpoints with stack_encoder_params / unstack_.
    stacked_layers: Optional[bool] = None

    def __post_init__(self):
        if self.stacked_layers is None:
            self.stacked_layers = self.pipeline

    @classmethod
    def base(cls, **kw):
        return cls(**kw)

    @classmethod
    def large(cls, **kw):
        return cls(hidden_size=1024, num_layers=24, num_heads=16,
                   ffn_size=4096, **kw)

    @classmethod
    def tiny(cls, **kw):
        """Test-size config."""
        kw.setdefault("vocab_size", 128)
        kw.setdefault("hidden_size", 32)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 2)
        kw.setdefault("ffn_size", 64)
        kw.setdefault("max_position", 64)
        return cls(**kw)


def stack_encoder_params(params, num_layers: int):
    """Convert a LayerList-layout BERT param tree ("encoder"/"0"/... per
    layer) to the stacked scan-over-layers layout — for loading
    checkpoints saved before ``stacked_layers`` (or by non-stacked
    configs) into a stacked model. (Generic form for other models:
    parallel.pipeline.stack_params_at.)"""
    from paddle_tpu.parallel.pipeline import stack_params_at
    return stack_params_at(params, ("bert", "encoder"), num_layers)


def unstack_encoder_params(params, num_layers: int):
    """Inverse of :func:`stack_encoder_params`."""
    from paddle_tpu.parallel.pipeline import unstack_params_at
    return unstack_params_at(params, ("bert", "encoder"), num_layers)


class BertEmbeddings(Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.word = Embedding(cfg.vocab_size, cfg.hidden_size,
                              weight_init=I.normal(0.0, 0.02))
        self.position = Embedding(cfg.max_position, cfg.hidden_size,
                                  weight_init=I.normal(0.0, 0.02),
                                  sharding=None)
        self.token_type = Embedding(cfg.type_vocab_size, cfg.hidden_size,
                                    weight_init=I.normal(0.0, 0.02),
                                    sharding=None)
        self.ln = LayerNorm(cfg.hidden_size)
        self.drop = Dropout(cfg.dropout)

    def forward(self, params, input_ids, token_type_ids=None, *,
                key=None, training=False):
        with jax.named_scope("embeddings"):
            s = input_ids.shape[1]
            pos = jnp.arange(s, dtype=jnp.int32)[None, :]
            x = self.word(params["word"], input_ids)
            x = x + self.position(params["position"], pos)
            if token_type_ids is None:
                token_type_ids = jnp.zeros_like(input_ids)
            x = x + self.token_type(params["token_type"], token_type_ids)
            x = self.ln(params["ln"], x)
            return self.drop(None, x, key=key, training=training)


class BertModel(Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = BertEmbeddings(cfg)

        def make_layer():
            return TransformerEncoderLayer(
                cfg.hidden_size, cfg.num_heads, cfg.ffn_size,
                dropout=cfg.dropout, attn_dropout=cfg.attn_dropout,
                pre_ln=cfg.pre_ln, attn_impl=cfg.attn_impl)

        if cfg.stacked_layers:
            self.encoder = StackedLayers(make_layer(), cfg.num_layers)
        else:
            self.encoder = LayerList(
                [make_layer() for _ in range(cfg.num_layers)])
        self.pooler = Linear(cfg.hidden_size, cfg.hidden_size,
                             sharding=None)

    def forward(self, params, input_ids, token_type_ids=None,
                attention_mask=None, *, key=None, training=False):
        """Returns (sequence_output (B,S,D), pooled_output (B,D))."""
        keys = [None] * (self.cfg.num_layers + 1)
        if key is not None:
            keys = list(jax.random.split(key, self.cfg.num_layers + 1))
        bias = None
        if attention_mask is not None:
            bias = ops_attn.make_padding_bias(attention_mask)
        x = self.embeddings(params["embeddings"], input_ids, token_type_ids,
                            key=keys[0], training=training)
        x = _constrain(x, ACT_SPEC)
        if self.cfg.pipeline:
            x = self._encoder_pipelined(params, x, bias, keys[1:], training)
        elif self.cfg.stacked_layers:
            lkeys = (jnp.stack(keys[1:]) if keys[1] is not None else None)
            x = self.encoder(params["encoder"], x, layer_keys=lkeys,
                             bias=bias, training=training)
        else:
            for i, layer in enumerate(self.encoder):
                x = layer(params["encoder"][str(i)], x, bias=bias,
                          key=keys[i + 1], training=training)
        with jax.named_scope("pooler"):
            pooled = jnp.tanh(self.pooler(params["pooler"], x[:, 0]))
        return x, pooled

    def _encoder_pipelined(self, params, x, bias, layer_keys, training):
        """GPipe the encoder stack over "pp" (PipelineOptimizer analog,
        optimizer.py:2931): per-layer params are stacked to (L, ...) leaves
        sharded over the stage axis; the attention bias rides the ring as
        a per-microbatch extra."""
        from paddle_tpu.parallel import pipeline as pp_lib

        cfg = self.cfg
        M = cfg.pp_microbatches
        extras = extras_spec = None
        if bias is not None:
            extras, extras_spec = pp_lib.microbatch_extras(bias, M)

        if cfg.stacked_layers:
            block_layer = self.encoder.template
            enc_params = params["encoder"]       # pre-stacked (L, ...)
        else:
            block_layer = self.encoder[0]
            enc_params = [params["encoder"][str(i)]
                          for i in range(cfg.num_layers)]
        return pp_lib.gpipe_layer_stack(
            lambda lp, h, extra, k: block_layer(
                lp, h, bias=extra, key=k, training=training),
            enc_params,
            x, num_microbatches=M, layer_keys=layer_keys,
            extras=extras, extras_spec=extras_spec,
            schedule=cfg.pp_schedule, num_circuits=cfg.pp_circuits,
            pre_interleaved=cfg.pp_pre_interleaved)


class BertPretrainingHeads(Layer):
    """MLM head (transform + tied-embedding decoder) + NSP head.

    :meth:`forward` gives the MLM logits at EVERY position: the inference
    surface. Training does not go through it: :meth:`mlm_nll` hands the
    same transform and decoder to ``ops.labelled_nll``, which computes
    them at the labelled positions only."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.transform = Linear(cfg.hidden_size, cfg.hidden_size,
                                sharding=None)
        self.ln = LayerNorm(cfg.hidden_size)
        self.decoder_bias = self.create_parameter(
            "decoder_bias", (cfg.vocab_size,), initializer=I.zeros,
            sharding=P("tp"))
        self.nsp = Linear(cfg.hidden_size, 2, sharding=None)

    def mlm_transform(self, params, rows):
        """Hidden rows ``(..., D)`` -> the decoder's input rows."""
        h = ops_act.gelu(self.transform(params["transform"], rows))
        return self.ln(params["ln"], h)

    def nsp_logits(self, params, pooled_output):
        with jax.named_scope("nsp_head"):
            return self.nsp(params["nsp"], pooled_output)

    def forward(self, params, sequence_output, pooled_output, word_table):
        with jax.named_scope("mlm_head"):
            h = self.mlm_transform(params, sequence_output)
            mlm_logits = jnp.einsum("bsd,vd->bsv", h, word_table) \
                + params["decoder_bias"]
        return mlm_logits, self.nsp_logits(params, pooled_output)

    def mlm_nll(self, params, sequence_output, word_table, mlm_labels,
                mlm_mask):
        """-> (masked sum of the MLM negative log-likelihoods, the mask's
        count, the share of the ``B x S`` rows the head computed): what
        :meth:`forward`'s logits would give, from the labelled rows alone
        (``ops.labelled_nll``)."""
        with jax.named_scope("mlm_head"):
            return ops_nll.labelled_nll(
                sequence_output, word_table, params["decoder_bias"],
                mlm_labels, mlm_mask, head=self.mlm_transform,
                head_params={k: params[k] for k in ("transform", "ln")})


class BertForPretraining(Layer):
    """BERT with MLM + NSP losses (PaddleNLP pretraining recipe parity)."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.bert = BertModel(cfg)
        self.heads = BertPretrainingHeads(cfg)

    def forward(self, params, input_ids, token_type_ids=None,
                attention_mask=None, *, key=None, training=False):
        seq, pooled = self.bert(params["bert"], input_ids, token_type_ids,
                                attention_mask, key=key, training=training)
        word_table = params["bert"]["embeddings"]["word"]["weight"]
        return self.heads(params["heads"], seq, pooled, word_table)

    def loss(self, params, input_ids, token_type_ids, attention_mask,
             mlm_labels, mlm_mask, nsp_labels, *, key=None, training=True):
        """mlm_labels: (B,S) target ids; mlm_mask: (B,S) 1.0 where masked;
        nsp_labels: (B,). Returns (loss, metrics).

        The MLM loss is the mean over the labelled positions of the
        float32 negative log-likelihood, as :meth:`forward`'s logits at
        every position would give it masked afterwards; it is computed
        by :meth:`BertPretrainingHeads.mlm_nll` from the labelled rows
        alone, for any mask, and the ``(B, S, vocab)`` logits are never
        formed. ``metrics["mlm_head_rows_share"]`` is the share of the
        ``B x S`` rows the head computed (a device scalar)."""
        seq, pooled = self.bert(params["bert"], input_ids, token_type_ids,
                                attention_mask, key=key, training=training)
        word_table = params["bert"]["embeddings"]["word"]["weight"]
        nll_sum, count, rows_share = self.heads.mlm_nll(
            params["heads"], seq, word_table, mlm_labels, mlm_mask)
        with jax.named_scope("mlm_head"):
            mlm_loss = nll_sum / jnp.maximum(count, 1.0)
        nsp_logits = self.heads.nsp_logits(params["heads"], pooled)
        with jax.named_scope("nsp_head"):
            nsp_lp = jax.nn.log_softmax(nsp_logits.astype(jnp.float32),
                                        axis=-1)
            nsp_loss = -jnp.take_along_axis(
                nsp_lp, nsp_labels[:, None], axis=-1).mean()
        loss = mlm_loss + nsp_loss
        return loss, {"mlm_loss": mlm_loss, "nsp_loss": nsp_loss,
                      "mlm_head_rows_share": rows_share}
