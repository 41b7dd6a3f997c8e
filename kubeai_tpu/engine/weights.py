"""Checkpoint loading: HuggingFace-format directories → native param trees.

The reference delegates weight loading to engine images + a loader
container (reference: components/model-loader/load.sh, engine_vllm.go
runai-streamer args). Here loading is native AND streamed:

  - Tensors are read LAZILY: safetensors headers are parsed once, each
    tensor is seek-read from its shard file only when its target slot is
    being filled, and stacked-layer leaves are assembled directly into
    preallocated TARGET-dtype (bf16) buffers. Peak host memory is the
    bf16 param tree plus ONE tensor — never an fp32 full-model staging
    copy (SURVEY.md §7 "sharded load fast enough for elastic scaling";
    70B in fp32 staging would need ~280 GB host RAM).
  - Remote artifacts (s3:// gs:// oss://) stream shard-at-a-time to
    local disk through kubeai_tpu.objstore (chunked object→file copies,
    one object in flight), then lazy-load from there.

Supported sources:
  - local directory (pvc:// mounts, cache dirs): config.json + *.safetensors
  - hf://repo: resolved through HF_HOME cache / huggingface_hub when
    network is available (zero-egress test environments use local dirs)
  - s3://, gs://, oss:// bucket prefixes (engine-direct; cache Jobs use
    kubeai_tpu.loader for the shared-PVC flow)
"""

from __future__ import annotations

import json
import os
from typing import Any

import jax.numpy as jnp
import numpy as np


class WeightLoadError(RuntimeError):
    pass


def load_hf_config(model_dir: str) -> dict:
    path = os.path.join(model_dir, "config.json")
    if not os.path.exists(path):
        raise WeightLoadError(f"no config.json under {model_dir}")
    with open(path) as f:
        return json.load(f)


_ST_DTYPES = {
    "F32": np.float32,
    "F16": np.float16,
    "BF16": jnp.bfloat16,  # the ml_dtypes scalar type numpy understands
    "I64": np.int64,
    "I32": np.int32,
    "U8": np.uint8,
}


def _decode_raw(raw: bytes, dtype_s: str, shape, name: str) -> np.ndarray:
    """The tensor in its STORED dtype (BF16 as ml_dtypes bfloat16)."""
    np_dtype = _ST_DTYPES.get(dtype_s)
    if np_dtype is None:
        raise WeightLoadError(f"unsupported dtype {dtype_s} for {name}")
    return np.frombuffer(raw, np_dtype).reshape(shape)


class LazyTensors:
    """Lazy tensor mapping over a checkpoint directory.

    safetensors: headers parsed up front (cheap), tensor data seek-read
    on demand — nothing resident until requested, nothing cached after.
    pytorch_model*.bin: eager fallback (torch pickles don't support
    random access without loading)."""

    def __init__(self, model_dir: str):
        self._index: dict[str, tuple[str, str, list, int, int]] = {}
        self._eager: dict[str, np.ndarray] | None = None
        st_files = sorted(
            f for f in os.listdir(model_dir) if f.endswith(".safetensors")
        )
        if st_files:
            for fname in st_files:
                fpath = os.path.join(model_dir, fname)
                with open(fpath, "rb") as f:
                    header_len = int.from_bytes(f.read(8), "little")
                    header = json.loads(f.read(header_len))
                    base = 8 + header_len
                for name, meta in header.items():
                    if name == "__metadata__":
                        continue
                    start, end = meta["data_offsets"]
                    self._index[name] = (
                        fpath, meta["dtype"], meta["shape"],
                        base + start, end - start,
                    )
            return
        bin_files = sorted(
            f for f in os.listdir(model_dir)
            if f.endswith(".bin") and f.startswith("pytorch_model")
        )
        if not bin_files:
            raise WeightLoadError(
                f"no safetensors or pytorch_model*.bin in {model_dir}"
            )
        import torch

        self._eager = {}
        for fname in bin_files:
            sd = torch.load(
                os.path.join(model_dir, fname), map_location="cpu",
                weights_only=True,
            )
            for k, v in sd.items():
                self._eager[k] = v.to(torch.float32).numpy()

    def __contains__(self, name: str) -> bool:
        if self._eager is not None:
            return name in self._eager
        return name in self._index

    def keys(self):
        return (self._eager or self._index).keys()

    def read(self, name: str) -> np.ndarray:
        """One tensor, freshly read, in its stored dtype — a bf16
        checkpoint loading into a bf16 tree never takes the fp32 detour
        (caller must not expect the buffer to persist cheaply — copy into
        the target and drop)."""
        if self._eager is not None:
            return self._eager[name]
        if name not in self._index:
            raise KeyError(name)
        fpath, dtype_s, shape, offset, nbytes = self._index[name]
        with open(fpath, "rb") as f:
            f.seek(offset)
            raw = f.read(nbytes)
        return _decode_raw(raw, dtype_s, shape, name)

    def __getitem__(self, name: str) -> np.ndarray:
        """fp32 view of one tensor (see read)."""
        return np.asarray(self.read(name), np.float32)


def _stream_helpers(model_dir: str, NL: int, dtype):
    """(tensors, get, stack, leaf): the shared streamed-assembly kit.

    `stack` fills a preallocated [NL, ...] TARGET-dtype buffer one layer
    tensor at a time (numpy casts on assignment), so each tensor lives
    only for its own copy — peak host memory is the target tree + one
    tensor, not an fp32 full model.

    Leaves stay HOST numpy arrays: `shard_params` moves each one straight
    to its shards on the engine's mesh, so a tensor is never whole on one
    device (a 14.5 GB model would not fit on the first of four 16 GB
    chips)."""
    t = LazyTensors(model_dir)
    target = np.dtype(dtype)

    def get(name: str) -> np.ndarray:
        if name not in t:
            raise WeightLoadError(f"missing tensor {name}")
        return t.read(name)

    def leaf(name: str) -> np.ndarray:
        return get(name).astype(target)

    def stack(fmt: str, transpose: bool = True) -> np.ndarray:
        buf = None
        for i in range(NL):
            a = get(fmt.format(i=i))
            if transpose:
                a = a.T
            if buf is None:
                buf = np.empty((NL, *a.shape), target)
            buf[i] = a  # casts stored dtype -> target in place
        return buf

    return t, get, stack, leaf


def load_llama_params(model_dir: str, cfg, dtype=jnp.bfloat16) -> dict:
    """Map a HF LlamaForCausalLM checkpoint onto the stacked-layer tree
    (kubeai_tpu.models.llama.param_specs layout).

    HF stores per-layer `model.layers.{i}.self_attn.q_proj.weight` with
    shape [out, in]; our layout stacks layers and keeps [in, out] so the
    forward einsums contract without transposes on the MXU.
    """
    t, get, stack, leaf = _stream_helpers(model_dir, cfg.num_layers, dtype)

    extra_layers = {}
    if getattr(cfg, "attention_bias", False):
        extra_layers = {
            "bq": stack("model.layers.{i}.self_attn.q_proj.bias", transpose=False),
            "bk": stack("model.layers.{i}.self_attn.k_proj.bias", transpose=False),
            "bv": stack("model.layers.{i}.self_attn.v_proj.bias", transpose=False),
        }
    params = {
        "embed": leaf("model.embed_tokens.weight"),
        "layers": {
            "input_norm": stack(
                "model.layers.{i}.input_layernorm.weight", transpose=False
            ),
            "wq": stack("model.layers.{i}.self_attn.q_proj.weight"),
            "wk": stack("model.layers.{i}.self_attn.k_proj.weight"),
            "wv": stack("model.layers.{i}.self_attn.v_proj.weight"),
            "wo": stack("model.layers.{i}.self_attn.o_proj.weight"),
            "post_attn_norm": stack(
                "model.layers.{i}.post_attention_layernorm.weight",
                transpose=False,
            ),
            "w_gate": stack("model.layers.{i}.mlp.gate_proj.weight"),
            "w_up": stack("model.layers.{i}.mlp.up_proj.weight"),
            "w_down": stack("model.layers.{i}.mlp.down_proj.weight"),
            **extra_layers,
        },
        "final_norm": leaf("model.norm.weight"),
    }
    if "lm_head.weight" in t:
        params["lm_head"] = leaf("lm_head.weight")
    else:  # tied embeddings
        params["lm_head"] = params["embed"]
    return params


def resolve_model_dir(model_url: str, model_dir: str = "") -> str:
    """Resolve a Model URL to a local directory.

    pvc://name/path → /model/path (the operator mounts the PVC at /model);
    hf://repo → huggingface_hub snapshot (network) or $HF_HOME cache;
    plain paths pass through. `model_dir` (the cache dir) wins when set.
    """
    if model_dir:
        return model_dir
    if model_url.startswith("pvc://"):
        ref = model_url[len("pvc://"):]
        sub = ref.split("/", 1)[1] if "/" in ref else ""
        return os.path.join("/model", sub) if sub else "/model"
    if model_url.startswith("hf://"):
        repo = model_url[len("hf://"):].split("?")[0]
        try:
            from huggingface_hub import snapshot_download

            return snapshot_download(repo)
        except Exception as e:
            raise WeightLoadError(
                f"cannot download {repo} (offline?): {e}"
            ) from e
    if model_url.split("://")[0] in ("s3", "gs", "oss"):
        # Engine-direct object-store load: stream shard files one at a
        # time to a local cache dir (disk, chunked — never whole-model in
        # RAM), then lazy-read from there. Cache Jobs pre-populate a PVC
        # via kubeai_tpu.loader for the shared-filesystem flow.
        import hashlib as _hashlib

        from kubeai_tpu import objstore

        cache_root = os.environ.get(
            "KUBEAI_WEIGHTS_CACHE", "/tmp/kubeai-weights"
        )
        digest = _hashlib.sha256(model_url.encode()).hexdigest()[:16]
        dest = os.path.join(cache_root, digest)
        done_marker = os.path.join(dest, ".kubeai-complete")
        if not os.path.exists(done_marker):
            # Download into a process-private staging dir, then atomically
            # rename: concurrent replicas sharing the cache never read a
            # half-written shard, and the loser of the rename race just
            # uses the winner's copy.
            import shutil as _shutil
            import tempfile as _tempfile

            os.makedirs(cache_root, exist_ok=True)
            staging = _tempfile.mkdtemp(dir=cache_root, prefix=f".{digest}-")
            try:
                objstore.download_prefix(model_url.split("?")[0], staging)
                with open(os.path.join(staging, ".kubeai-complete"), "w") as f:
                    f.write(model_url)
                try:
                    os.rename(staging, dest)
                except OSError:
                    if not os.path.exists(done_marker):
                        raise
            finally:
                if os.path.exists(staging):
                    _shutil.rmtree(staging, ignore_errors=True)
        return dest
    if os.path.isdir(model_url):
        return model_url
    raise WeightLoadError(f"unsupported model url {model_url!r}")


def load_gemma_params(model_dir: str, cfg, dtype=jnp.bfloat16) -> dict:
    """HF Gemma/Gemma2 checkpoint → kubeai_tpu.models.gemma layout."""
    t, get, stack, leaf = _stream_helpers(model_dir, cfg.num_layers, dtype)

    layers = {
        "input_norm": stack("model.layers.{i}.input_layernorm.weight", False),
        "wq": stack("model.layers.{i}.self_attn.q_proj.weight"),
        "wk": stack("model.layers.{i}.self_attn.k_proj.weight"),
        "wv": stack("model.layers.{i}.self_attn.v_proj.weight"),
        "wo": stack("model.layers.{i}.self_attn.o_proj.weight"),
        "w_gate": stack("model.layers.{i}.mlp.gate_proj.weight"),
        "w_up": stack("model.layers.{i}.mlp.up_proj.weight"),
        "w_down": stack("model.layers.{i}.mlp.down_proj.weight"),
    }
    if cfg.sandwich_norms:  # gemma2 naming
        layers["post_attn_norm"] = stack(
            "model.layers.{i}.post_attention_layernorm.weight", False
        )
        layers["pre_mlp_norm"] = stack(
            "model.layers.{i}.pre_feedforward_layernorm.weight", False
        )
        layers["post_mlp_norm"] = stack(
            "model.layers.{i}.post_feedforward_layernorm.weight", False
        )
    else:  # gemma1: post_attention_layernorm IS the pre-MLP norm
        layers["pre_mlp_norm"] = stack(
            "model.layers.{i}.post_attention_layernorm.weight", False
        )
    return {
        "embed": leaf("model.embed_tokens.weight"),
        "layers": layers,
        "final_norm": leaf("model.norm.weight"),
    }


def load_mixtral_params(model_dir: str, cfg, dtype=jnp.bfloat16) -> dict:
    """HF Mixtral checkpoint → kubeai_tpu.models.mixtral layout
    (experts stacked: w1=gate, w3=up, w2=down)."""
    NL, X = cfg.num_layers, cfg.num_experts
    t, get, stack, leaf = _stream_helpers(model_dir, NL, dtype)
    target = np.dtype(dtype)

    def stack_experts(w_name):
        buf = None
        for i in range(NL):
            for e in range(X):
                a = get(
                    f"model.layers.{i}.block_sparse_moe.experts.{e}.{w_name}.weight"
                ).T
                if buf is None:
                    buf = np.empty((NL, X, *a.shape), target)
                buf[i, e] = a
        return buf  # [NL, X, in, out]

    return {
        "embed": leaf("model.embed_tokens.weight"),
        "layers": {
            "input_norm": stack("model.layers.{i}.input_layernorm.weight", False),
            "wq": stack("model.layers.{i}.self_attn.q_proj.weight"),
            "wk": stack("model.layers.{i}.self_attn.k_proj.weight"),
            "wv": stack("model.layers.{i}.self_attn.v_proj.weight"),
            "wo": stack("model.layers.{i}.self_attn.o_proj.weight"),
            "post_attn_norm": stack(
                "model.layers.{i}.post_attention_layernorm.weight", False
            ),
            "router": stack("model.layers.{i}.block_sparse_moe.gate.weight"),
            "w_gate": stack_experts("w1"),
            "w_up": stack_experts("w3"),
            "w_down": stack_experts("w2"),
        },
        "final_norm": leaf("model.norm.weight"),
        "lm_head": leaf("lm_head.weight"),
    }


_LOADERS = {
    "llama": load_llama_params,
    "qwen": load_llama_params,  # same layout + biases (attention_bias)
    "gemma": load_gemma_params,
    "mixtral": load_mixtral_params,
}


def load_params(family_name: str, model_dir: str, cfg, dtype=jnp.bfloat16):
    """Family-dispatching checkpoint loader."""
    if family_name not in _LOADERS:
        raise WeightLoadError(f"no weight loader for family {family_name!r}")
    return _LOADERS[family_name](model_dir, cfg, dtype)


def load_whisper_params(model_dir: str, cfg, dtype=jnp.float32) -> dict:
    """HF WhisperForConditionalGeneration → kubeai_tpu.models.whisper layout."""
    t = LazyTensors(model_dir)

    def get(name):
        if name not in t:
            raise WeightLoadError(f"missing tensor {name}")
        return t[name]

    def j(a):
        return jnp.asarray(a, dtype)

    def attn(prefix):
        return {
            "wq": j(get(f"{prefix}.q_proj.weight").T),
            "bq": j(get(f"{prefix}.q_proj.bias")),
            "wk": j(get(f"{prefix}.k_proj.weight").T),
            "wv": j(get(f"{prefix}.v_proj.weight").T),
            "bv": j(get(f"{prefix}.v_proj.bias")),
            "wo": j(get(f"{prefix}.out_proj.weight").T),
            "bo": j(get(f"{prefix}.out_proj.bias")),
        }

    def ln(name):
        return {"w": j(get(f"{name}.weight")), "b": j(get(f"{name}.bias"))}

    def ffn(prefix):
        return {
            "w1": j(get(f"{prefix}.fc1.weight").T),
            "b1": j(get(f"{prefix}.fc1.bias")),
            "w2": j(get(f"{prefix}.fc2.weight").T),
            "b2": j(get(f"{prefix}.fc2.bias")),
        }

    enc_layers = [
        {
            "ln1": ln(f"model.encoder.layers.{i}.self_attn_layer_norm"),
            "attn": attn(f"model.encoder.layers.{i}.self_attn"),
            "ln2": ln(f"model.encoder.layers.{i}.final_layer_norm"),
            "ffn": ffn(f"model.encoder.layers.{i}"),
        }
        for i in range(cfg.encoder_layers)
    ]
    dec_layers = [
        {
            "ln1": ln(f"model.decoder.layers.{i}.self_attn_layer_norm"),
            "self_attn": attn(f"model.decoder.layers.{i}.self_attn"),
            "ln2": ln(f"model.decoder.layers.{i}.encoder_attn_layer_norm"),
            "cross_attn": attn(f"model.decoder.layers.{i}.encoder_attn"),
            "ln3": ln(f"model.decoder.layers.{i}.final_layer_norm"),
            "ffn": ffn(f"model.decoder.layers.{i}"),
        }
        for i in range(cfg.decoder_layers)
    ]
    return {
        # torch conv1d weight [out, in, k] -> [k, in, out]
        "conv1_w": j(get("model.encoder.conv1.weight").transpose(2, 1, 0)),
        "conv1_b": j(get("model.encoder.conv1.bias")),
        "conv2_w": j(get("model.encoder.conv2.weight").transpose(2, 1, 0)),
        "conv2_b": j(get("model.encoder.conv2.bias")),
        "enc_pos": j(get("model.encoder.embed_positions.weight")),
        "enc_layers": enc_layers,
        "enc_ln": ln("model.encoder.layer_norm"),
        "dec_embed": j(get("model.decoder.embed_tokens.weight")),
        "dec_pos": j(get("model.decoder.embed_positions.weight")),
        "dec_layers": dec_layers,
        "dec_ln": ln("model.decoder.layer_norm"),
    }


_LOADERS["whisper"] = load_whisper_params


# ---- native checkpoint format (orbax) ---------------------------------------
#
# Engine-side save/resume (SURVEY.md §5.4: the reference has no engine-side
# checkpointing — weight loading is delegated to vLLM images; here the
# engine can snapshot its post-conversion param tree so replica restarts
# skip the HF->native mapping and load sharded directly from disk/GCS-fuse).


def save_native_checkpoint(path: str, params) -> None:
    import orbax.checkpoint as ocp

    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(os.path.abspath(path), params, force=True)
        ckptr.wait_until_finished()


def load_native_checkpoint(path: str, like=None):
    import orbax.checkpoint as ocp

    with ocp.StandardCheckpointer() as ckptr:
        if like is not None:
            return ckptr.restore(os.path.abspath(path), like)
        return ckptr.restore(os.path.abspath(path))
