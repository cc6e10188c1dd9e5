"""Low-precision serving: quantizable layers + quantize-at-restore.

The serving engine runs inference in the training master dtype (f32) even
though inference traffic tolerates much less precision. This module is the
mechanics of the `inference_dtype` engine mode (f32 | bf16 | int8):

* **Quantizable layers.** `QuantDense` / `QuantConv` are drop-in
  `nn.Dense` / `nn.Conv` subclasses that override ONLY parameter
  retrieval: when the `kernel` leaf arrives as int8 (a quantized serving
  tree) they dequantize it through the per-output-channel scale stored in
  the sidecar ``quant`` collection — ``(w_int8 * scale) @ x``, the
  weight-only form whose dequant XLA fuses into the consuming matmul/conv.
  With an f32/bf16 tree the override returns the kernel untouched, so
  training, checkpoints, and every f32 code path are bit-identical to the
  stock flax layers (same param names, same init, same compute).
* **Quantize-at-restore.** `quantize_tree` turns an f32 master
  checkpoint tree into the serving tree: per-output-channel scales are
  computed on the host (``scale = max|w| / 127`` over the non-output
  axes), kernels round-clip to int8, and the scales land in a ``quant``
  collection mirroring the module paths (``.../attn/query/kernel`` →
  ``quant/.../attn/query/kernel_scale``). WHICH leaves quantize is not
  decided here: `rt1_tpu/parallel/plan.py` declares the quantization
  group per param path with the same path-regex machinery as the sharding
  rules, so "what gets int8" reads next to "how it shards" — norms,
  embeddings, the action head and BatchNorm statistics stay at the
  master dtype by explicit rule.
* **bf16 mode.** `cast_tree` casts every float leaf once at restore;
  paired with a bf16-compute model this is bit-identical to flax's own
  compute-dtype cast at use sites (pinned in tests/test_quant.py), while
  halving resident param bytes.

A quantization bug can never ship silently: `rt1_tpu/serve/parity.py`
gates the quantized engine on canned-episode action-token agreement vs the
f32 engine, enforced in tier-1.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

# The sidecar variable collection carrying per-output-channel dequant
# scales, mirroring the quantized leaves' module paths with a `_scale`
# suffix on the leaf name.
QUANT_COLLECTION = "quant"

INFERENCE_DTYPES = ("f32", "bf16", "int8")

INT8_MAX = 127


def check_inference_dtype(mode: str) -> str:
    if mode not in INFERENCE_DTYPES:
        raise ValueError(
            f"inference_dtype must be one of {INFERENCE_DTYPES}, got {mode!r}"
        )
    return mode


# ------------------------------------------------------------------ layers


def maybe_dequantize(module: nn.Module, value: Any, scale_name: str) -> Any:
    """Inside a bound module: dequantize an int8 param leaf via its sidecar
    scale, or return the leaf untouched when it is not quantized.

    An int8 leaf WITHOUT a scale is a hard error: silently feeding raw
    int8 integers to a matmul would serve garbage with 200 OK — quantized
    trees must come from `quantize_tree`, which always writes the scale.
    """
    if value.dtype != jnp.int8:
        return value
    if not module.has_variable(QUANT_COLLECTION, scale_name):
        raise ValueError(
            f"{type(module).__name__}: param is int8 but no "
            f"'{QUANT_COLLECTION}' collection carries {scale_name!r}; "
            "quantized serving trees must be built by "
            "rt1_tpu.models.quant.quantize_tree (quantize-at-restore)"
        )
    scale = module.get_variable(QUANT_COLLECTION, scale_name)
    # (w_int8 * scale) @ x: the dequant is element-wise on the weight and
    # adjacent to its consuming contraction, where XLA fuses it.
    return value.astype(scale.dtype) * scale


class QuantDense(nn.Dense):
    """`nn.Dense` that transparently dequantizes an int8 kernel.

    Only parameter retrieval is overridden; init, param names, and the
    f32/bf16 compute path are inherited — a model threaded with this layer
    is bit-identical to one built on `nn.Dense` until a quantized tree is
    served through it.
    """

    def param(self, name, *args, **kwargs):
        value = super().param(name, *args, **kwargs)
        if name == "kernel":
            value = maybe_dequantize(self, value, "kernel_scale")
        return value


class QuantConv(nn.Conv):
    """`nn.Conv` that transparently dequantizes an int8 kernel (see
    `QuantDense`; conv kernels are (kh, kw, cin, cout) — the scale is
    per-cout, broadcast over the receptive field)."""

    def param(self, name, *args, **kwargs):
        value = super().param(name, *args, **kwargs)
        if name == "kernel":
            value = maybe_dequantize(self, value, "kernel_scale")
        return value


# ------------------------------------------------------------ quantization


def quantize_per_channel(w: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric per-output-channel int8 quantization of `w` (..., cout).

    Returns (w_int8, scale_f32 (cout,)) with ``w ≈ w_int8 * scale``,
    ``scale = max|w| / 127`` over all non-output axes. An all-zero channel
    (e.g. FiLM's zero-initialized projections) gets scale 1.0, so its
    round-trip is exact instead of 0/0.
    """
    w = np.asarray(w, np.float32)
    if w.ndim < 2:
        raise ValueError(
            f"per-channel quantization needs rank >= 2, got shape {w.shape}"
        )
    axes = tuple(range(w.ndim - 1))
    amax = np.max(np.abs(w), axis=axes)
    scale = np.where(amax > 0, amax / INT8_MAX, 1.0).astype(np.float32)
    q = np.clip(np.rint(w / scale), -INT8_MAX, INT8_MAX).astype(np.int8)
    return q, scale


def dequantize(q: np.ndarray, scale: np.ndarray) -> np.ndarray:
    """Host-side inverse of `quantize_per_channel` (tests, error bounds)."""
    return q.astype(np.float32) * scale


def _is_mapping(x: Any) -> bool:
    return hasattr(x, "items") and not hasattr(x, "shape")


def _quantize_mapping(
    tree: Any, prefix: str, rules: List[Tuple[str, str]]
) -> Tuple[Dict[str, Any], Dict[str, Any], int]:
    """Recurse one params mapping: (quantized params, mirrored scales,
    n_quantized). Scale leaves are named `<leaf>_scale` at the leaf's own
    module path, which is exactly where Quant layers look them up."""
    from rt1_tpu.parallel.plan import QUANT_INT8, quant_group_for_path

    out: Dict[str, Any] = {}
    scales: Dict[str, Any] = {}
    n = 0
    for key, value in tree.items():
        path = f"{prefix}/{key}"
        if _is_mapping(value):
            sub, sub_scales, sub_n = _quantize_mapping(value, path, rules)
            out[key] = sub
            n += sub_n
            if sub_scales:
                scales[key] = sub_scales
        else:
            leaf = np.asarray(value)
            if (
                getattr(leaf, "ndim", 0) >= 2
                and quant_group_for_path(path, rules) == QUANT_INT8
            ):
                q, scale = quantize_per_channel(leaf)
                out[key] = q
                scales[f"{key}_scale"] = scale
                n += 1
            else:
                out[key] = leaf
    return out, scales, n


def quantize_tree(
    variables: Any, rules: Optional[List[Tuple[str, str]]] = None
) -> Dict[str, Any]:
    """f32 master variables → int8 serving tree + ``quant`` scale collection.

    Only the ``params`` collection is eligible (BatchNorm statistics in
    ``batch_stats`` are never quantized); WHICH params leaves quantize is
    declared by the plan's quant rules (`parallel/plan.py
    rt1_quant_rules`). Deterministic: the same master tree always produces
    the same serving tree, which is what lets `swap_variables` requantize
    a standby checkpoint and land on the exact compiled dtypes.
    """
    from rt1_tpu.parallel.plan import rt1_quant_rules

    if rules is None:
        rules = rt1_quant_rules()
    if not _is_mapping(variables) or "params" not in variables:
        raise ValueError(
            "quantize_tree expects a variables mapping with a 'params' "
            f"collection, got {type(variables).__name__}"
        )
    out: Dict[str, Any] = {}
    qparams, scales, n = _quantize_mapping(
        variables["params"], "params", rules
    )
    out["params"] = qparams
    for key, value in variables.items():
        if key == "params":
            continue
        out[key] = jax.tree.map(lambda x: np.asarray(x), value)
    if n == 0:
        raise ValueError(
            "quantize_tree: no leaf matched an int8 quant rule — an int8 "
            "engine serving a byte-identical f32 tree would report a "
            "fabricated memory win; check rt1_quant_rules against this "
            "model's param paths"
        )
    out[QUANT_COLLECTION] = scales
    return out


def cast_tree(variables: Any, dtype=jnp.bfloat16) -> Any:
    """Every float leaf cast to `dtype` once, on the host (bf16 restore).
    Integer leaves (none in RT-1 variables today) pass through."""

    def cast(x):
        x = np.asarray(x)
        if np.issubdtype(x.dtype, np.floating):
            return x.astype(dtype)
        return x

    return jax.tree.map(cast, variables)


def serving_preparer(
    inference_dtype: str, rules: Optional[List[Tuple[str, str]]] = None
) -> Optional[Callable[[Any], Any]]:
    """The host-side master-tree → serving-tree transform for an engine
    mode, or None for f32 (identity). Used once at restore and again by
    `PolicyEngine.swap_variables` for every standby checkpoint, so
    `/reload` keeps working — and keeps compile_count = 1 — in quantized
    modes."""
    check_inference_dtype(inference_dtype)
    if inference_dtype == "f32":
        return None
    if inference_dtype == "bf16":
        return cast_tree
    return lambda variables: quantize_tree(variables, rules)


# ---------------------------------------------------------- byte accounting


def tree_bytes(tree: Any) -> int:
    """Total leaf bytes of a pytree (arrays or ShapeDtypeStructs)."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is None:
            nbytes = int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        total += int(nbytes)
    return total


def abstract_serving_variables(config) -> Any:
    """The serving variables tree as shapes/dtypes only (`jax.eval_shape`
    over the model init — no FLOPs, so even the flagship B3 resolves in
    seconds on a laptop)."""
    from rt1_tpu.specs import language_table_action_space, sample_space
    from rt1_tpu.train.train import build_model

    model = build_model(config.model)
    t = config.model.time_sequence_length
    h, w = config.data.height, config.data.width
    obs = {
        "image": jax.ShapeDtypeStruct((1, t, h, w, 3), np.float32),
        "natural_language_embedding": jax.ShapeDtypeStruct(
            (1, t, 512), np.float32
        ),
    }
    actions = sample_space(
        language_table_action_space(), jax.random.PRNGKey(1), (1, t)
    )
    return jax.eval_shape(
        lambda r, o, a: model.init(
            {"params": r, "dropout": r, "crop": r}, o, a, train=False
        ),
        jax.random.PRNGKey(0),
        obs,
        actions,
    )


def quant_byte_report(
    config, rules: Optional[List[Tuple[str, str]]] = None
) -> Dict[str, Any]:
    """Per-dtype serving param-byte accounting for a config, from abstract
    shapes (no init cost). The bench's honesty companion on hosts where
    XLA:CPU has no native int8 matmul: bytes moved is the measurable win
    there, latency is the TPU projection."""
    from rt1_tpu.parallel.plan import QUANT_INT8, quant_group_for_path
    from rt1_tpu.parallel.sharding import _path_str

    if rules is None:
        from rt1_tpu.parallel.plan import rt1_quant_rules

        rules = rt1_quant_rules()
    shapes = abstract_serving_variables(config)
    f32_bytes = 0
    bf16_bytes = 0
    int8_bytes = 0
    quantized_leaves = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        n = int(np.prod(leaf.shape))
        f32_bytes += n * 4
        bf16_bytes += n * 2
        s = _path_str(path)
        if (
            leaf.ndim >= 2
            and quant_group_for_path(s, rules) == QUANT_INT8
        ):
            # int8 payload + one f32 scale per output channel.
            int8_bytes += n + int(leaf.shape[-1]) * 4
            quantized_leaves += 1
        else:
            int8_bytes += n * 4
    return {
        "config": str(getattr(config.model, "image_tokenizer", "rt1")),
        "quantized_leaves": quantized_leaves,
        "f32_bytes": f32_bytes,
        "bf16_bytes": bf16_bytes,
        "int8_bytes": int8_bytes,
        "bf16_reduction": round(f32_bytes / bf16_bytes, 3),
        "int8_reduction": (
            round(f32_bytes / int8_bytes, 3) if int8_bytes else 0.0
        ),
    }


# ----------------------------------------------------------- path utilities


def quantized_paths(
    variables: Any, rules: Optional[List[Tuple[str, str]]] = None
) -> List[str]:
    """Param paths an int8 restore would quantize (tests, reporting)."""
    from rt1_tpu.parallel.plan import QUANT_INT8, quant_group_for_path
    from rt1_tpu.parallel.sharding import _path_str

    if rules is None:
        from rt1_tpu.parallel.plan import rt1_quant_rules

        rules = rt1_quant_rules()
    out = []
    tree = variables.get("params", variables) if _is_mapping(variables) else variables
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        s = "params/" + _path_str(path)
        if (
            getattr(leaf, "ndim", 0) >= 2
            and quant_group_for_path(s, rules) == QUANT_INT8
        ):
            out.append(s)
    return out
