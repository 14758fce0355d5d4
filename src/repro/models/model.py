"""Model facade: family dispatch, abstract init, input specs, loss/prefill/decode.

``Model`` is the single public entry point consumed by the trainer, the serving
engine, and the dry-run launcher.  All heavy code lives in transformer.py /
encdec.py; this module wires families together and owns the ShardCtx used to
place sharding constraints on activations.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import sharding
from repro.configs.base import ModelConfig, ShapeConfig

from . import encdec, transformer


@dataclasses.dataclass
class ShardCtx:
    mesh: object = None
    rules: dict | None = None

    def constrain(self, x, axes):
        if self.mesh is None:
            return x
        return sharding.constrain(x, self.mesh, axes, self.rules)


NULL_CTX = ShardCtx()

AUX_LOSS_WEIGHT = 0.01


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self._axes = None

    # -- params ----------------------------------------------------------------

    def _init(self, key):
        if self.cfg.is_encoder_decoder:
            return encdec.encdec_init(key, self.cfg)
        return transformer.decoder_init(key, self.cfg)

    def init(self, key):
        params, axes = self._init(key)
        self._axes = axes
        return params

    def abstract_params(self, key=None):
        """Shapes-only params (no allocation) + axes tree."""
        key = key if key is not None else jax.random.PRNGKey(0)
        box = {}

        def f(k):
            p, a = self._init(k)
            box["axes"] = a
            return p

        shapes = jax.eval_shape(f, key)
        self._axes = box["axes"]
        return shapes, box["axes"]

    def param_axes(self):
        if self._axes is None:
            self.abstract_params()
        return self._axes

    # -- training --------------------------------------------------------------

    def loss(self, params, batch, ctx: ShardCtx = NULL_CTX):
        """batch: dict with tokens/labels (+frontend embeds). Returns (loss, metrics)."""
        cfg = self.cfg
        if cfg.is_encoder_decoder:
            enc_out = encdec.encode(params, batch["frame_embeds"], cfg, ctx)
            x = encdec.decode_train(params, batch["tokens"], enc_out, cfg, ctx)
            aux = jnp.zeros((), jnp.float32)
        else:
            fe = batch.get("vision_embeds")
            x, aux = transformer.decoder_forward(params, batch["tokens"], cfg, ctx,
                                                 frontend_embeds=fe)
        ce = transformer.decoder_loss(params, x, batch["labels"], cfg, ctx)
        loss = ce + AUX_LOSS_WEIGHT * aux
        return loss, {"ce": ce, "aux": aux}

    # -- serving ----------------------------------------------------------------

    def prefill(self, params, batch, cache_len: int, ctx: ShardCtx = NULL_CTX,
                last_pos=None):
        """Returns (per-row last-prompt-position logits (B, Vp), caches).

        ``last_pos``: (B,) index of each row's final prompt token (ragged
        right-padded prompts, continuous batching); None → S-1 for all rows.
        """
        cfg = self.cfg
        if cfg.is_encoder_decoder:
            enc_out = encdec.encode(params, batch["frame_embeds"], cfg, ctx)
            x, caches = encdec.decode_train(params, batch["tokens"], enc_out, cfg,
                                            ctx, return_caches=True,
                                            cache_len=cache_len)
        else:
            fe = batch.get("vision_embeds")
            x, _, caches = transformer.decoder_forward(
                params, batch["tokens"], cfg, ctx, frontend_embeds=fe,
                return_caches=True, cache_len=cache_len)
        B, S, _ = x.shape
        if last_pos is None:
            x_last = x[:, -1:, :]
        else:
            x_last = x[jnp.arange(B), last_pos][:, None, :]
        logits = transformer.decoder_logits(params, x_last, cfg, ctx)[:, 0]
        return logits, caches

    def decode_step(self, params, caches, token, pos, ctx: ShardCtx = NULL_CTX):
        if self.cfg.is_encoder_decoder:
            return encdec.encdec_decode_step(params, caches, token, pos, self.cfg, ctx)
        return transformer.decoder_decode_step(params, caches, token, pos, self.cfg, ctx)

    def empty_caches(self, batch: int, cache_len: int):
        if self.cfg.is_encoder_decoder:
            return encdec.encdec_empty_caches(self.cfg, batch, cache_len)
        return transformer.decoder_empty_caches(self.cfg, batch, cache_len)

    def cache_axes(self):
        if self.cfg.is_encoder_decoder:
            return encdec.encdec_cache_axes(self.cfg)
        return transformer.cache_axes(self.cfg)

    # -- abstract inputs ---------------------------------------------------------

    def input_specs(self, shape: ShapeConfig) -> dict:
        """ShapeDtypeStruct stand-ins for every input of the step function.

        train/prefill: token batch (+ stub frontend embeddings).
        decode: one new token + per-request positions + the full KV cache.
        """
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        sds = jax.ShapeDtypeStruct
        if shape.kind == "train":
            out = {"tokens": sds((B, S), jnp.int32),
                   "labels": sds((B, S), jnp.int32)}
            if cfg.frontend == "vision_stub":
                out["vision_embeds"] = sds((B, cfg.n_frontend_tokens, cfg.d_model),
                                           jnp.bfloat16)
            if cfg.frontend == "audio_stub":
                out["frame_embeds"] = sds((B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
            return out
        if shape.kind == "prefill":
            out = {"tokens": sds((B, S), jnp.int32)}
            if cfg.frontend == "vision_stub":
                out["vision_embeds"] = sds((B, cfg.n_frontend_tokens, cfg.d_model),
                                           jnp.bfloat16)
            if cfg.frontend == "audio_stub":
                out["frame_embeds"] = sds((B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
            return out
        if shape.kind == "decode":
            caches = jax.eval_shape(lambda: self.empty_caches(B, S))
            return {"caches": caches,
                    "token": sds((B, 1), jnp.int32),
                    "pos": sds((B,), jnp.int32)}
        raise ValueError(shape.kind)

    def input_axes(self, shape: ShapeConfig) -> dict:
        """Logical axes for input_specs (same tree structure)."""
        cfg = self.cfg
        if shape.kind in ("train", "prefill"):
            out = {"tokens": ("batch", "seq")}
            if shape.kind == "train":
                out["labels"] = ("batch", "seq")
            if cfg.frontend == "vision_stub":
                out["vision_embeds"] = ("batch", None, None)
            if cfg.frontend == "audio_stub":
                out["frame_embeds"] = ("batch", None, None)
            return out
        return {"caches": self.cache_axes(),
                "token": ("batch", None),
                "pos": ("batch",)}


def sharded_greedy(logits, ctx: ShardCtx):
    """argmax over vocab-TP logits without all-gathering them.

    Each model shard reduces its local vocab slice to (max, argmax); only the
    16 scalar pairs cross the ICI (§Perf iteration 2).  Falls back to a plain
    argmax without a mesh.
    """
    if ctx is None or ctx.mesh is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    mesh = ctx.mesh
    from jax.sharding import PartitionSpec as P
    V = logits.shape[-1]
    msize = mesh.shape["model"]
    if V % msize:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def local(l):  # l: (B, V/m) local slice
        vloc = l.shape[-1]
        m = l.max(axis=-1)
        a = l.argmax(axis=-1).astype(jnp.int32)
        a = a + jax.lax.axis_index("model").astype(jnp.int32) * vloc
        gm = jax.lax.pmax(m, "model")
        cand = jnp.where(m >= gm, a, jnp.int32(2**30))
        return jax.lax.pmin(cand, "model")  # lowest index among ties

    fn = jax.shard_map(local, mesh=mesh,
                           in_specs=P(None, "model"), out_specs=P(),
                           check_vma=False)
    return fn(logits)


def build_model(name_or_cfg, smoke: bool = False) -> Model:
    if isinstance(name_or_cfg, ModelConfig):
        return Model(name_or_cfg)
    from repro.configs import get_config
    return Model(get_config(name_or_cfg, smoke=smoke))
