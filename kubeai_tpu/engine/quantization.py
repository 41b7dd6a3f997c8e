"""Weight-only int8 quantization for serving.

Decode throughput is bounded by streaming the weights from HBM each step;
int8 storage halves that traffic. Symmetric per-output-channel scales:

    w ≈ w8 * scale,   w8 = round(w / scale) ∈ [-127, 127]

Dequantization happens inside the matmul's operand read (XLA fuses
`convert(int8→bf16) * scale` into the dot input), so no bf16 copy of the
weights ever materializes.

The engine applies this at load time (EngineConfig.quantization="int8");
quantized leaves are dicts {"w8": int8, "scale": f32} and the model's
matmul helper dispatches on leaf type, so the same forward code serves
both precisions. KV cache and activations stay bf16.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# Stacked-weight leaves eligible for quantization, per family tree path.
# Last axis = output channels (per-channel scales).
QUANTIZABLE = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def quantize_tensor(w) -> dict:
    """[..., in, out] -> {"w8": int8, "scale": f32[..., 1, out]}, computed
    and left on the HOST: the engine shards the result straight onto its
    mesh, so no unsharded copy of a weight ever sits on one device. A
    stacked [NL, in, out] tensor goes one layer at a time, which bounds
    the f32 working copy to one layer."""
    w = np.asarray(w)
    if w.ndim > 2:
        parts = [quantize_tensor(w[i]) for i in range(w.shape[0])]
        return {
            "w8": np.stack([p["w8"] for p in parts]),
            "scale": np.stack([p["scale"] for p in parts]),
        }
    w32 = w.astype(np.float32)
    amax = np.max(np.abs(w32), axis=-2, keepdims=True)  # per output channel
    scale = np.maximum(amax / 127.0, 1e-8).astype(np.float32)
    w8 = np.clip(np.round(w32 / scale), -127, 127).astype(np.int8)
    return {"w8": w8, "scale": scale}


def dequantize(leaf) -> jnp.ndarray:
    if is_quantized(leaf):
        return (
            leaf["w8"].astype(jnp.bfloat16)
            * leaf["scale"].astype(jnp.bfloat16)
        )
    return leaf


def is_quantized(leaf) -> bool:
    return isinstance(leaf, dict) and "w8" in leaf and "scale" in leaf


def quantize_params(params: dict, targets=QUANTIZABLE) -> dict:
    """Quantize the named layer weights of a stacked-layer param tree."""
    out = dict(params)
    layers = dict(params["layers"])
    for name in targets:
        if name in layers:
            layers[name] = quantize_tensor(layers[name])
    out["layers"] = layers
    return out


def quantized_specs(specs: dict, layers_params: dict) -> dict:
    """Mirror the sharding-spec tree onto the quantized structure: the w8
    leaf keeps the weight's axes; scales shard like the output axis."""
    out = dict(specs)
    lspecs = dict(specs["layers"])
    for name, leaf in layers_params.items():
        if is_quantized(leaf) and name in lspecs:
            axes = lspecs[name]
            # scale shape [..., 1, out]: the singleton input axis must be
            # replicated; the output axis shards like the weight's.
            scale_axes = tuple(axes[:-2]) + (None,) + (axes[-1],)
            lspecs[name] = {"w8": axes, "scale": scale_axes}
    out["layers"] = lspecs
    return out
