"""Uniform model API: build(config) -> Model with train/prefill/decode
step functions and ShapeDtypeStruct input specs for every assigned input
shape (the dry-run contract).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

from .config import ModelConfig
from . import encdec as ED
from . import lm as LM

# assigned input shapes: name -> (seq_len, global_batch, kind)
SHAPES = {
    "train_4k": (4_096, 256, "train"),
    "prefill_32k": (32_768, 32, "prefill"),
    "decode_32k": (32_768, 128, "decode"),
    "long_500k": (524_288, 1, "decode"),
}


def _explicit_mesh(tree):
    """The explicit-axes mesh the leaves of ``tree`` are typed on, if
    any (None for single-device or auto-axes inputs)."""
    for leaf in jax.tree.leaves(tree):
        mesh = jax.typeof(leaf).sharding.mesh
        if not mesh.empty and AxisType.Explicit in mesh.axis_types:
            return mesh
    return None


def shape_applicable(cfg: ModelConfig, shape: str) -> Tuple[bool, str]:
    if shape == "long_500k" and not cfg.is_subquadratic():
        return False, ("pure full-attention architecture: long_500k needs "
                       "sub-quadratic attention (skip noted in DESIGN.md)")
    return True, ""


@dataclasses.dataclass
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------------ init
    def init(self, key) -> Dict:
        if self.cfg.family == "encdec":
            return ED.init_encdec(self.cfg, key)
        return LM.init_lm(self.cfg, key)

    def init_shapes(self, key) -> Dict:
        return jax.eval_shape(lambda k: self.init(k), key)

    # ---------------------------------------------------------------- fwd/loss
    def loss_fn(self, params, batch):
        """Returns ``(loss + 0.01 * aux, (loss, aux))``.  The model is
        written for compiler-chosen (auto-axes) sharding; inputs typed
        on an explicit-axes mesh (``jax.make_mesh``'s default) run under
        auto axes of that mesh, with replicated scalar outputs."""
        mesh = _explicit_mesh((params, batch))
        if mesh is None:
            return self._loss(params, batch)
        with jax.sharding.use_abstract_mesh(mesh):
            return jax.sharding.auto_axes(
                self._loss, out_sharding=(P(), (P(), P())))(params, batch)

    def _loss(self, params, batch):
        cfg = self.cfg
        if cfg.family == "encdec":
            logits, aux = ED.encdec_forward(
                cfg, params, batch["enc_embeds"], batch["tokens"],
                batch["enc_positions"], batch["positions"])
        else:
            logits, aux = LM.lm_forward(
                cfg, params, batch.get("embeds", batch.get("tokens")),
                batch["positions"])
        logp = jax.nn.log_softmax(logits, -1)
        ll = jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
        loss = -ll.mean()
        return loss + 0.01 * aux, (loss, aux)

    # ---------------------------------------------------------------- serving
    def init_cache(self, batch: int, max_len: int, enc_len: int = 0):
        if self.cfg.family == "encdec":
            return ED.init_dec_cache(self.cfg, batch, max_len,
                                     enc_len or max_len)
        return LM.init_cache(self.cfg, batch, max_len)

    def prefill(self, params, batch, cache, start=None):
        cfg = self.cfg
        if cfg.family == "encdec":
            return ED.encdec_prefill(cfg, params, batch["enc_embeds"],
                                     batch["enc_positions"],
                                     batch["tokens"], batch["positions"],
                                     cache)
        return LM.lm_prefill(cfg, params,
                             batch.get("embeds", batch.get("tokens")),
                             batch["positions"], cache, start)

    def decode_step(self, params, batch, cache, index):
        cfg = self.cfg
        if cfg.family == "encdec":
            return ED.encdec_decode(cfg, params, batch["tokens"],
                                    batch["positions"], cache, index)
        return LM.lm_decode(cfg, params,
                            batch.get("embeds", batch.get("tokens")),
                            batch["positions"], cache, index)

    # ---------------------------------------------------------------- specs
    def input_specs(self, shape_name: str) -> Dict:
        """ShapeDtypeStruct stand-ins for every model input (no device
        allocation) — the dry-run contract."""
        cfg = self.cfg
        seq, gbs, kind = SHAPES[shape_name]
        i32 = jnp.int32
        dt = jnp.dtype(cfg.dtype)
        S = jax.ShapeDtypeStruct

        def positions(b, s):
            if cfg.m_rope:
                return S((3, b, s), i32)
            return S((s,), i32)

        if kind == "train":
            batch = {"positions": positions(gbs, seq),
                     "labels": S((gbs, seq), i32)}
            if cfg.family == "encdec":
                batch["enc_embeds"] = S((gbs, seq, cfg.d_model), dt)
                batch["enc_positions"] = S((seq,), i32)
                batch["tokens"] = S((gbs, seq), i32)
            elif cfg.frontend == "embeds":
                batch["embeds"] = S((gbs, seq, cfg.d_model), dt)
            else:
                batch["tokens"] = S((gbs, seq), i32)
            return batch

        if kind == "prefill":
            batch = {"positions": positions(gbs, seq)}
            if cfg.family == "encdec":
                batch["enc_embeds"] = S((gbs, seq, cfg.d_model), dt)
                batch["enc_positions"] = S((seq,), i32)
                batch["tokens"] = S((gbs, seq), i32)
            elif cfg.frontend == "embeds":
                batch["embeds"] = S((gbs, seq, cfg.d_model), dt)
            else:
                batch["tokens"] = S((gbs, seq), i32)
            cache = jax.eval_shape(
                lambda: self.init_cache(gbs, seq, enc_len=seq))
            return {"batch": batch, "cache": cache}

        # decode: one new token against a cache of length seq
        batch = {"positions": positions(gbs, 1)}
        if cfg.family == "encdec":
            batch["tokens"] = S((gbs, 1), i32)
        elif cfg.frontend == "embeds":
            batch["embeds"] = S((gbs, 1, cfg.d_model), dt)
        else:
            batch["tokens"] = S((gbs, 1), i32)
        cache = jax.eval_shape(lambda: self.init_cache(gbs, seq,
                                                       enc_len=min(seq, 32768)))
        return {"batch": batch, "cache": cache,
                "index": S((), i32)}

    # ---------------------------------------------------------------- demo data
    def demo_batch(self, key, seq: int, gbs: int, kind: str = "train"):
        """Small concrete batch for smoke tests."""
        cfg = self.cfg
        ks = jax.random.split(key, 3)
        pos = (jnp.tile(jnp.arange(seq, dtype=jnp.int32)[None, None],
                        (3, gbs, 1))
               if cfg.m_rope else jnp.arange(seq, dtype=jnp.int32))
        batch = {"positions": pos,
                 "labels": jax.random.randint(ks[0], (gbs, seq), 0,
                                              cfg.vocab_size)}
        if cfg.family == "encdec":
            batch["enc_embeds"] = jax.random.normal(
                ks[1], (gbs, seq, cfg.d_model), jnp.dtype(cfg.dtype))
            batch["enc_positions"] = jnp.arange(seq, dtype=jnp.int32)
            batch["tokens"] = jax.random.randint(ks[2], (gbs, seq), 0,
                                                 cfg.vocab_size)
        elif cfg.frontend == "embeds":
            batch["embeds"] = jax.random.normal(
                ks[1], (gbs, seq, cfg.d_model), jnp.dtype(cfg.dtype))
        else:
            batch["tokens"] = jax.random.randint(ks[2], (gbs, seq), 0,
                                                 cfg.vocab_size)
        return batch


def build(cfg: ModelConfig) -> Model:
    return Model(cfg)
