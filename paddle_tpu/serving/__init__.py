"""Paged KV-cache serving engine: continuous batching, batched chunked
prefill, prefix sharing, and SLO-aware scheduling.

The serving-throughput subsystem (ISSUE 4 + the ISSUE 6 prefill/SLO
rebuild). Four parts:

1. **Paged KV cache** (`paged_cache.py`): K/V in fixed-size pages with
   per-slot block tables and a host-side allocator — HBM scales with
   live tokens, not ``batch × max_len`` — plus **refcounted prefix
   sharing**: published prompt-prefix pages are mapped copy-free into
   new requests' block tables (a shared system prompt is prefilled once
   for thousands of requests), with copy-on-write for shared tail pages.
2. **Ragged paged attention kernels** (`decode_attention.py`): one
   fixed-shape call attends every slot's query token (decode) or query
   CHUNK (batched prefill) over only its own live pages (Pallas with
   block-table scalar prefetch; lax fallback and an ``interpret=True``
   path so CPU tier-1 tests run the real kernels).
3. **Schedulers** (`scheduler.py`): fixed decode slots with immediate
   EOS eviction — plain FIFO (`ContinuousBatchingScheduler`) or
   SLO-aware (`SLOScheduler`: priority lanes, TTFT deadlines, bounded-
   skip anti-starvation, structured `LoadShedError` load shedding) —
   pure host logic.
4. **ServingEngine** (`engine.py`): ``submit``/``step``/
   ``generate_many`` driving one jit-compiled fixed-shape decode step
   AND one batched chunked-prefill step with donated cache pages (zero
   steady-state recompiles, proven by a ``RecompileDetector``), prefill/
   decode interleaving under a token budget, wired into the
   observability registry with split TTFT accounting — plus slot-level
   live-migration snapshot/restore (sha256-verified per-page shards).
5. **Fleet** (`fleet/`): N engines behind one ``FleetRouter`` —
   prefix-affinity routing over the published prefix index,
   power-of-two-choices fallback, burn-rate elastic autoscaling, and
   live request migration on drain.
"""

from paddle_tpu.serving.paged_cache import (PagedCacheConfig, PagedKVCache,
                                            PageOverflowError,
                                            prompt_prefix_digests,
                                            quantize_kv)
from paddle_tpu.serving.decode_attention import (
    ragged_paged_decode_attention, ragged_paged_decode_int8_attention,
    ragged_paged_prefill_attention, ragged_paged_prefill_int8_attention)
from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                          LoadShedError, Reject, Request,
                                          SLOScheduler, SlotState)
from paddle_tpu.serving.engine import ServingEngine, SlotMigrationError
from paddle_tpu.serving import fleet

__all__ = [
    "PagedCacheConfig", "PagedKVCache", "PageOverflowError",
    "ragged_paged_decode_attention",
    "ragged_paged_decode_int8_attention",
    "ragged_paged_prefill_attention",
    "ragged_paged_prefill_int8_attention",
    "prompt_prefix_digests", "quantize_kv",
    "ContinuousBatchingScheduler", "SLOScheduler", "LoadShedError",
    "Reject", "Request", "SlotState",
    "ServingEngine", "SlotMigrationError", "fleet",
]
