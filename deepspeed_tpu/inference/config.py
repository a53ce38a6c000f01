"""Inference configuration.

Reference parity: ``DeepSpeedInferenceConfig`` (``inference/config.py``) and the
v2 ``RaggedInferenceEngineConfig`` (``inference/v2/config_v2.py``). Kernel-
injection / CUDA-graph knobs become their TPU meanings: kernel selection is the
op-registry backend choice (Pallas vs XLA), and graph capture is jit caching —
always on, so ``enable_cuda_graph`` is accepted and ignored.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional

from ..telemetry.compile import CompileMonitorConfig
from ..telemetry.trace import TraceConfig


@dataclass
class TPConfig:
    """Tensor-parallel sub-config (reference ``DeepSpeedTPConfig``)."""

    tp_size: int = 1


@dataclass
class RaggedConfig:
    """v2 state-manager sub-config (reference ``DSStateManagerConfig``)."""

    max_tracked_sequences: int = 64      # concurrent sequence slots
    max_ragged_batch_size: int = 64      # decode batch per step
    memory_config_blocks: int = 512      # KV blocks in the pool
    block_size: int = 128                # tokens per KV block


@dataclass
class PrefixCacheConfig:
    """Prefix-aware KV-cache reuse for the v2 paged engine (docs/serving.md).

    Default OFF: with ``enabled=False`` the serving path is bit-identical to
    the cache-less engine. When ON, admissions resolve shared prompt prefixes
    (system prompts, few-shot templates, multi-turn histories) to existing KV
    blocks via a chain-hash index and start prefill at the first uncached
    token; retired sequences' full blocks park in a retained LRU pool and are
    evicted only under allocation pressure."""

    enabled: bool = False
    # retained-pool cap: -1 = bounded only by the block pool itself,
    # 0 = share blocks between live sequences but retain nothing after
    # retire, >0 = keep at most this many unreferenced blocks
    max_retained_blocks: int = -1
    # host-spill tier (docs/memory.md): evicted unreferenced blocks copy to
    # a host pool keyed by their chain hash instead of being dropped, and
    # admissions restore spilled blocks on a prefix hit — the retained pool
    # multiplies past HBM. OFF → the pre-spill eviction path, byte-identical.
    host_spill: bool = False
    # host-pool cap in blocks: -1 = unbounded (host RAM is the budget)
    max_spilled_blocks: int = -1


@dataclass
class SpeculativeConfig:
    """Speculative decoding for the v2 paged engine (docs/serving.md).

    Default OFF: with ``enabled=False`` the decode path is bit-identical to
    the plain engine. When ON, each ``step()`` drafts up to
    ``max_draft_tokens`` per live sequence with a model-free prompt-lookup
    (n-gram) drafter — the trailing ``ngram_max``-gram of the request's own
    prompt+output history is matched against an earlier occurrence and the
    tokens that followed it are proposed — then ONE batched forward pass over
    the paged cache verifies every draft position, the longest agreeing
    prefix is accepted (exact rejection sampling for non-greedy requests),
    and rejected KV positions are rolled back (``StateManager.truncate``)."""

    enabled: bool = False
    max_draft_tokens: int = 4    # draft positions verified per step (k)
    ngram_max: int = 3           # longest trailing n-gram tried first
    min_match: int = 1           # shortest n-gram that may draft


@dataclass
class QuantConfig:
    """Weight quantization for inference (reference
    ``inference/quantization`` INT4/INT8 + ``GroupQuantizer``)."""

    enabled: bool = False
    bits: int = 8          # 8 (int8) or 4 (packed nibbles)
    dtype: str = "int"     # "int" | "fp8" (float8_e4m3 weights + row scales)


@dataclass
class KVQuantConfig:
    """Quantized KV cache for the v2 paged engine (docs/serving.md
    "Quantized KV cache").

    Default OFF: with ``enabled=False`` the block pools, every compiled
    paged program, and the token streams are byte-identical to the bf16
    engine (pinned by parity tests). When ON, the paged allocator's K/V
    block pools store int8 codes with fp32 per-block-per-group scales
    living beside them in the cache pytree — halving (bf16→int8) KV bytes
    per block, so ~2× sequences fit at the same pool size — and dequant is
    FUSED into the attention kernels (in-register in the Pallas paged
    decode kernel, into the gather consumer on the prefill path) rather
    than run as a standalone XLA convert pass: QUANT_TPU_LIVE.json shows
    naive int8→bf16 casts before the MXU are 1.02–1.21× SLOWER than bf16,
    so the win must come from storage, not compute. Scales ride the cache
    pytree, so copy-on-write, fork, spec-decode truncate, prefix-cache
    matching, and host-spill all carry codes AND scales automatically."""

    enabled: bool = False
    dtype: str = "int8"    # the only wired code dtype (fp8 is future work)
    # tokens' head-dim group per fp32 scale; clamped to head_size (the
    # default therefore gives ONE scale per (token, kv-head) at hd <= 128)
    group_size: int = 128


@dataclass
class InferenceConfig:
    dtype: str = "bfloat16"
    tensor_parallel: TPConfig = field(default_factory=TPConfig)
    max_out_tokens: int = 1024           # dense KV-cache length budget
    min_out_tokens: int = 1
    replace_with_kernel_inject: bool = False  # prefer Pallas kernels when True
    enable_cuda_graph: bool = False      # accepted for parity; jit caches anyway
    max_batch_size: int = 8
    prefill_bucket: int = 64             # pad prompts to a multiple of this
    # Dynamic-SplitFuse analog (reference blogs/deepspeed-fastgen: long
    # prompts decompose into fixed-size chunks scheduled alongside decode):
    # >0 = tokens per prefill chunk for split-admitted sequences (rounded up
    # to prefill_bucket); one chunk advances per step()/step_many() call, so
    # ongoing decodes are never blocked for more than one chunk's compute
    split_prefill_chunk: int = 0
    ragged: RaggedConfig = field(default_factory=RaggedConfig)
    quant: QuantConfig = field(default_factory=QuantConfig)
    # int8 KV-cache blocks with fused dequant (docs/serving.md). Default
    # OFF → serving byte-identical, pinned.
    kv_quant: KVQuantConfig = field(default_factory=KVQuantConfig)
    prefix_cache: PrefixCacheConfig = field(default_factory=PrefixCacheConfig)
    speculative: SpeculativeConfig = field(default_factory=SpeculativeConfig)
    # request-lifecycle tracing + latency SLO stats (telemetry/trace.py;
    # docs/serving.md). Default OFF → the serving path records nothing.
    trace: TraceConfig = field(default_factory=TraceConfig)
    # recompilation sentinel + per-program MFU attribution
    # (telemetry/compile.py; docs/observability.md). Default OFF → every
    # paged program is the plain jax.jit object, byte-identical.
    compile_monitor: CompileMonitorConfig = field(
        default_factory=CompileMonitorConfig)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "InferenceConfig":
        d = dict(d or {})
        tp = d.pop("tensor_parallel", {})
        if isinstance(tp, int):
            tp = {"tp_size": tp}
        ragged = d.pop("ragged", {})
        quant = d.pop("quant", {})
        kvq = d.pop("kv_quant", {})
        prefix = d.pop("prefix_cache", {})
        spec = d.pop("speculative", {})
        trace = d.pop("trace", {})
        cmon = d.pop("compile_monitor", {})
        known = {k: v for k, v in d.items() if k in cls.__dataclass_fields__}
        return cls(tensor_parallel=TPConfig(**tp), ragged=RaggedConfig(**ragged),
                   quant=QuantConfig(**quant),
                   kv_quant=KVQuantConfig(**kvq),
                   prefix_cache=PrefixCacheConfig(**prefix),
                   speculative=SpeculativeConfig(**spec),
                   trace=TraceConfig(**trace),
                   compile_monitor=CompileMonitorConfig(**cmon), **known)
