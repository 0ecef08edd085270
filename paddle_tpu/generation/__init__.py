"""paddle_tpu.generation — paged-KV continuous-batching decode engine.

The autoregressive layer above `paddle_tpu.serving`: where serving
batches fixed-shape one-shot forward passes, generation runs the LLM
inference loop — a paged KV cache (page pool + per-sequence page
tables), paged decode attention (Pallas TPU kernel with a pure-jnp
reference), a continuous-batching scheduler with a prefill/decode split
over fixed slots, and a sampling engine with per-request streaming.
See docs/GENERATION.md for layouts, the step diagram, and the oracle
strategy.

Quick start::

    from paddle_tpu import generation

    model = generation.TinyCausalLM(vocab_size=64)   # or any protocol model
    engine = generation.GenerationEngine(
        model, generation.GenerationConfig(max_decode_slots=8,
                                           num_pages=256, page_size=16))
    handle = engine.submit([1, 2, 3], max_new_tokens=32,
                           sampling=generation.SamplingParams(temperature=0.8,
                                                              top_p=0.95,
                                                              seed=7))
    for token in handle.tokens():        # streams as sampled
        print(token)
    result = handle.result()             # GenerationResult
    engine.shutdown()
"""
from .decode_attention import (chunk_prefill_attention,
                               chunk_prefill_attention_reference,
                               dense_causal_reference,
                               paged_decode_attention,
                               paged_decode_attention_reference,
                               ragged_paged_attention,
                               ragged_paged_attention_reference)
from .engine import (DEFAULT_PREFILL_CHUNK_TOKENS, GenerationConfig,
                     GenerationEngine, GenerationHandle, GenerationResult,
                     UnsupportedModelPathError)
from .fused import (ChunkedPrefillStep, FusedDecodeStep,
                    LoopedRaggedStep, RaggedStep, decode_batch_menu)
from .gqa_window_moe_model import GQAWindowMoELM
from .hybrid_ssm_moe_model import HybridSSMMoELM
from .kv_cache import (DeviceKVPool, HeadRows, KVQuantMismatchError,
                       LatentRows, OutOfPagesError, PagedKVCache,
                       SparseLatentRows, UnknownSequenceError,
                       UnsupportedCachePathError, WindowPageGroup)
from .latent_moe_model import LatentMoELM
from .metrics import GenerationMetrics
from .model import TinyCausalLM
from .sparse_latent_moe_model import SparseLatentMoELM
from .sampling import (SampleStream, SamplingParams, sample_token,
                       sample_tokens_batch, sample_tokens_device)
from .scheduler import (ContinuousBatchingScheduler, GenerationRequest,
                        SequenceState)
from .speculation import NgramIndex, NgramProposer, verify_accept

__all__ = [
    "GenerationEngine", "GenerationConfig", "GenerationHandle",
    "GenerationResult", "PagedKVCache", "DeviceKVPool",
    "OutOfPagesError", "UnknownSequenceError", "KVQuantMismatchError",
    "paged_decode_attention", "paged_decode_attention_reference",
    "dense_causal_reference", "ContinuousBatchingScheduler",
    "GenerationRequest", "SequenceState", "SamplingParams", "sample_token",
    "sample_tokens_batch", "sample_tokens_device", "SampleStream",
    "GenerationMetrics", "TinyCausalLM", "LatentMoELM", "LatentRows",
    "GQAWindowMoELM", "HeadRows", "WindowPageGroup", "HybridSSMMoELM",
    "SlotState", "SparseLatentMoELM", "SparseLatentRows",
    "UnsupportedModelPathError", "UnsupportedCachePathError",
    "FusedDecodeStep", "ChunkedPrefillStep", "RaggedStep",
    "LoopedRaggedStep", "decode_batch_menu",
    "chunk_prefill_attention", "chunk_prefill_attention_reference",
    "ragged_paged_attention", "ragged_paged_attention_reference",
    "DEFAULT_PREFILL_CHUNK_TOKENS", "NgramProposer", "NgramIndex",
    "verify_accept",
]
