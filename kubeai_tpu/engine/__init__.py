"""The TPU serving engine — the component the reference outsources to vLLM.

JetStream-style design: a fixed pool of decode *slots*, per-request prefill
that scatters KV into the slot's pages of a shared pool, and a single
batched decode step over all active slots (continuous batching). Everything
jitted with static shapes; prompt lengths are bucketed to bound
recompilation.

Reference seams this replaces:
  - the vLLM serving container (reference: internal/modelcontroller/engine_vllm.go)
  - the vLLM admin client for LoRA (reference: internal/vllmclient/client.go)
"""

from kubeai_tpu.engine.engine import Engine, EngineConfig
