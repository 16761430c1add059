"""ResNet-50, pure JAX, built for the MXU.

Second flagship model (the reference's headline serving benchmark is a
batched ResNet-50 replica — BASELINE.md:63 "batched ResNet-50 serving
replica (p50)"; the reference itself has no model zoo, its Serve wraps
user torch models). TPU-first choices:

- NHWC layout end-to-end (TPU conv layout; channels land on the
  128-wide lane dimension),
- all convs in bfloat16 with f32 accumulation (MXU-native),
- batchnorm folds to scale+shift at inference (one fused multiply-add);
  training mode returns updated running stats functionally,
- static shapes only: serving pads batches to bucket sizes upstream
  (``ray_tpu.serve.batching``), so every bucket compiles once.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Params = Dict[str, Any]

# Bottleneck block counts per stage (reference torchvision resnet50/101).
DEPTHS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 18: (2, 2, 2, 2)}


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    depth: int = 50
    num_classes: int = 1000
    width: int = 64
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    bn_momentum: float = 0.9
    bn_eps: float = 1e-5

    @property
    def stages(self) -> Tuple[int, ...]:
        return DEPTHS[self.depth]

    @property
    def bottleneck(self) -> bool:
        return self.depth >= 50

    def num_params(self) -> int:
        leaves = jax.tree.leaves(
            jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                               self)))
        return sum(int(math.prod(x.shape)) for x in leaves)


def _conv_init(key, kh, kw, cin, cout, dtype):
    fan_in = kh * kw * cin
    scale = math.sqrt(2.0 / fan_in)
    return (jax.random.normal(key, (kh, kw, cin, cout)) * scale
            ).astype(dtype)


def _bn_init(c, dtype):
    return {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype),
            "mean": jnp.zeros((c,), dtype), "var": jnp.ones((c,), dtype)}


def init_params(rng: jax.Array, cfg: ResNetConfig) -> Params:
    pd = cfg.param_dtype
    keys = iter(jax.random.split(rng, 256))
    params: Params = {
        "stem": {"conv": _conv_init(next(keys), 7, 7, 3, cfg.width, pd),
                 "bn": _bn_init(cfg.width, pd)},
    }
    cin = cfg.width
    expansion = 4 if cfg.bottleneck else 1
    for stage, blocks in enumerate(cfg.stages):
        cmid = cfg.width * (2 ** stage)
        cout = cmid * expansion
        stage_params = []
        for b in range(blocks):
            stride = 2 if (stage > 0 and b == 0) else 1
            blk: Params = {}
            if cfg.bottleneck:
                blk["conv1"] = _conv_init(next(keys), 1, 1, cin, cmid, pd)
                blk["bn1"] = _bn_init(cmid, pd)
                blk["conv2"] = _conv_init(next(keys), 3, 3, cmid, cmid, pd)
                blk["bn2"] = _bn_init(cmid, pd)
                blk["conv3"] = _conv_init(next(keys), 1, 1, cmid, cout, pd)
                blk["bn3"] = _bn_init(cout, pd)
            else:
                blk["conv1"] = _conv_init(next(keys), 3, 3, cin, cmid, pd)
                blk["bn1"] = _bn_init(cmid, pd)
                blk["conv2"] = _conv_init(next(keys), 3, 3, cmid, cout, pd)
                blk["bn2"] = _bn_init(cout, pd)
            if cin != cout or stride != 1:
                blk["proj"] = _conv_init(next(keys), 1, 1, cin, cout, pd)
                blk["proj_bn"] = _bn_init(cout, pd)
            stage_params.append(blk)
            cin = cout
        params[f"stage{stage}"] = stage_params
    params["head"] = {
        "kernel": (jax.random.normal(next(keys), (cin, cfg.num_classes))
                   * 0.01).astype(pd),
        "bias": jnp.zeros((cfg.num_classes,), pd),
    }
    return params


def _conv(x, w, stride, cfg, padding="SAME"):
    # No preferred_element_type: the MXU accumulates bf16 convs in f32
    # regardless, and a f32-out annotation breaks the transpose-conv
    # gradient rule (cotangent f32 vs bf16 operand dtype mismatch).
    return lax.conv_general_dilated(
        x.astype(cfg.dtype), w.astype(cfg.dtype),
        window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn_inference(x, bn, cfg):
    # Folded: y = x * (scale/sqrt(var+eps)) + (bias - mean*scale/sqrt..)
    inv = (bn["scale"].astype(jnp.float32)
           * lax.rsqrt(bn["var"].astype(jnp.float32) + cfg.bn_eps))
    shift = bn["bias"].astype(jnp.float32) - \
        bn["mean"].astype(jnp.float32) * inv
    return (x.astype(jnp.float32) * inv + shift).astype(cfg.dtype)


def _bn_train(x, bn, cfg):
    """Returns (y, updated_bn) — functional batch statistics."""
    xf = x.astype(jnp.float32)
    mean = xf.mean(axis=(0, 1, 2))
    var = xf.var(axis=(0, 1, 2))
    inv = bn["scale"].astype(jnp.float32) * lax.rsqrt(var + cfg.bn_eps)
    y = ((xf - mean) * inv + bn["bias"].astype(jnp.float32)).astype(
        cfg.dtype)
    m = cfg.bn_momentum
    new_bn = dict(bn)
    new_bn["mean"] = (m * bn["mean"].astype(jnp.float32)
                      + (1 - m) * mean).astype(bn["mean"].dtype)
    new_bn["var"] = (m * bn["var"].astype(jnp.float32)
                     + (1 - m) * var).astype(bn["var"].dtype)
    return y, new_bn


def forward(params: Params, x: jax.Array, cfg: ResNetConfig,
            train: bool = False):
    """images [B, H, W, 3] float → logits [B, num_classes] f32.

    ``train=True`` returns ``(logits, new_params)`` with updated BN
    running stats (functional — no mutation)."""
    new_params = jax.tree.map(lambda a: a, params) if train else None

    def bn(x, p, path):
        if not train:
            return _bn_inference(x, p, cfg)
        y, nb = _bn_train(x, p, cfg)
        node = new_params
        for k in path[:-1]:
            node = node[k]
        node[path[-1]] = nb
        return y

    x = _conv(x, params["stem"]["conv"], 2, cfg)
    x = jax.nn.relu(bn(x, params["stem"]["bn"], ("stem", "bn")))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                          (1, 2, 2, 1), "SAME")
    for stage in range(len(cfg.stages)):
        for i, blk in enumerate(params[f"stage{stage}"]):
            stride = 2 if (stage > 0 and i == 0) else 1
            path = (f"stage{stage}", i)
            shortcut = x
            if "proj" in blk:
                shortcut = _conv(x, blk["proj"], stride, cfg)
                shortcut = bn(shortcut, blk["proj_bn"],
                              path + ("proj_bn",))
            if cfg.bottleneck:
                h = jax.nn.relu(bn(_conv(x, blk["conv1"], 1, cfg),
                                   blk["bn1"], path + ("bn1",)))
                h = jax.nn.relu(bn(_conv(h, blk["conv2"], stride, cfg),
                                   blk["bn2"], path + ("bn2",)))
                h = bn(_conv(h, blk["conv3"], 1, cfg),
                       blk["bn3"], path + ("bn3",))
            else:
                h = jax.nn.relu(bn(_conv(x, blk["conv1"], stride, cfg),
                                   blk["bn1"], path + ("bn1",)))
                h = bn(_conv(h, blk["conv2"], 1, cfg),
                       blk["bn2"], path + ("bn2",))
            x = jax.nn.relu(h + shortcut)
    x = x.astype(jnp.float32).mean(axis=(1, 2))  # global average pool
    logits = x @ params["head"]["kernel"].astype(jnp.float32) + \
        params["head"]["bias"].astype(jnp.float32)
    if train:
        return logits, new_params
    return logits


def make_predictor(cfg: ResNetConfig, params: Params,
                   uint8_input: bool = False):
    """Jitted inference fn for serving: one compile per batch bucket.

    ``uint8_input=True`` takes raw [0,255] uint8 images and normalizes
    on-device — 4x less host→device traffic per batch."""

    @jax.jit
    def predict(images):
        if uint8_input:
            images = images.astype(cfg.dtype) * jnp.asarray(
                1.0 / 255.0, cfg.dtype)
        return forward(params, images, cfg, train=False)

    return predict
