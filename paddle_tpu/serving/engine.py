"""ServingEngine: continuous-batching inference over a paged KV cache.

The serving loop is TWO jit-compiled fixed-shape steps, written against
what a model supplies (:mod:`~paddle_tpu.serving.program`) and what its
layers cache (:mod:`~paddle_tpu.serving.layer_kinds`: one kind a layer,
built once from the program's spec and the cache's geometry; the loops are
straight-line code over ``cache.config.kinds[i]``, a layer whose kind
caches no rows (``layer_kinds.State``) running the program's ``mixer`` alone
over its slot state and touching no page):

- a **batched chunked-prefill step**: one call advances the admitted
  requests' next prompt chunks at once — tokens (lanes, C), ragged
  per-lane valid counts, causal paged attention. A lane is a (slot,
  chunk) pair: where the program's layer kinds take it
  (``layer_kinds.Kind.prefill_run``) consecutive lanes carry a **run** of
  consecutive chunks of ONE prompt, so that a step's prompt tokens read
  the weights in one call however few prompts are in prefill;
- a **decode step**: every slot advances a BLOCK of ``decode_block``
  tokens per call (an on-device ``fori_loop``, amortizing the host
  round-trip), attending over its own pages.

All shapes are static: ``num_slots``, the prefill chunk, pow2-bucketed
block-table gather widths that track the LIVE high-water mark (work
follows live tokens, not slot capacity), and bucketed lane counts (1, 2,
4, 8, then every multiple of 8 the budget reaches). The cache pages are
**donated** into both steps, and :meth:`ServingEngine.warmup` precompiles
every bucket, so steady-state serving triggers zero recompiles and zero
cache copies (a :class:`~paddle_tpu.observability.RecompileDetector`
wired to the step proves it).

Prefill and decode **interleave** under a per-step token budget
(``prefill_budget``): each ``step()`` spends at most
``max(prefill_budget, prefill_chunk)`` prompt tokens on prefill before
running the decode block — the chunk floor is a single liveness lane
for budgets below one chunk — so a burst of long prompts cannot starve
in-flight decodes and vice versa. The budget is a ceiling, not a quota:
where calls carry runs and a slot decodes, a slot gives a step one run,
and what it could still give leads the next step's call; a further call
of the step carries only slots the first had no lane for, and only at or
above the break-even in lanes under which it is mostly a second read of
the weights (:meth:`ServingEngine._prefill_round`).

The host waits for the device **once a step**: every call of a step is
ordered on the device by the donated page pool it threads, so a prefill
call is dispatched and not read. A finished prompt's first token stays on
the device and is merged into the decode block's input tokens there
(``first_token_step``, one tiny program per lane count), and the block's
read-back brings it over together with the block's tokens and the
programs' pending counts; TTFT is stamped then, when the host learns the
token. Only a finishing request the step must judge at once reads back in
its prefill call: one with an ``eos_id`` or a budget of one token (the
admission cascade evicts on it), a speculative engine (its round reads the
token on the host), a prefill tier (the slot parks for handoff).
``serving_device_readbacks_total{phase}`` counts the waits.

And the block a step reads is the one the step BEFORE dispatched:
``step()`` dispatches block k, then settles block k-1, so at most one
decode block is in flight across calls and the host's per-step work runs
beside the device, not between two of its blocks. What is decided at
dispatch, what is learned at settle and which engines and calls settle at
once: :meth:`ServingEngine.step`.

Prefix sharing: admission maps published prompt-prefix pages straight
into the new slot's block table (refcount bump, prefill skipped for the
shared tokens — see ``paged_cache``) and the engine performs the single
copy-on-write page copy a borrowed *tail* page requires before the
slot's first write. ``cache_dtype=jnp.int8`` selects the int8 kind of
layer: roughly half the HBM per live token of bf16, and migration shards
carry page + scales under one hash.

Speculative decoding: pass ``draft_model``/``draft_params`` (+
``spec_k``) and the decode phase becomes draft-then-verify: the draft
proposes ``spec_k`` greedy tokens per slot on its OWN paged cache (same
slot/page geometry, allocations in lockstep), and the target verifies the
whole chunk in ONE fixed-shape batched-prefill-shaped step
(`_verify_step_impl`). Each round accepts the longest draft prefix the
target agrees with plus the target's next token, so **greedy outputs are
bit-exact vs non-speculative decoding**; rollback is a host-side cursor
rewind (rejected tokens' K/V stay masked behind the slot length and are
overwritten — pages were reserved up front, nothing leaks). Accept quality
lands in ``serving_spec_*`` and per-request ``request_stats``. Speculation
disables prefix sharing (the draft must prefill every prompt token) and
slot migration (the draft cache is not carried in snapshots).

Tensor parallel: ``mesh=`` (or the shorthand ``tp=N``) shards the whole
paged stack over the mesh's ``tp`` axis — the page pools hold per-shard
head slices (``H/tp``), both fixed-shape steps run under ``shard_map`` on
the parameter tree the model's serving program lays out and shards for it
(``tp_params``, ``tp_plan``; GPT's: head-major Megatron slices and ONE
``psum`` per layer at the attention output). Greedy tokens are identical
to the tp=1 engine, slot migration moves one sha256 shard per (page, tp
shard), ``health()`` reports the mesh shape, and ``warmup()`` covers the
same bucket plan.

Scheduling is SLO-aware by default (``scheduler_policy="slo"``): priority
lanes, TTFT deadlines with earliest-deadline-first boosting, bounded-skip
anti-starvation, and load shedding via structured
:class:`~paddle_tpu.serving.LoadShedError` rejects instead of unbounded
queueing. ``scheduler_policy="fifo"`` is the plain head-blocking FIFO.

Observability: every series the engine feeds is bound, with its meaning,
in :meth:`ServingEngine._bind_step_metrics` (a layer kind's own in its
``bind``); the spans of a step are listed in :meth:`ServingEngine.step`
and PERF.md section 3. Pass ``tracer=`` for request-lifecycle tracing —
one root span per request with scheduler-decision / prefix-share / CoW
events, child spans per prefill chunk and decode block (all host-side);
``ttft_budget_s=`` arms an SLO burn-rate monitor over the TTFT histogram;
``health()`` / ``start_exposition()`` serve live ``/metrics``
``/healthz`` ``/traces``.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from paddle_tpu.analysis.concurrency import guarded_by
from paddle_tpu.observability import recompile as _recompile
from paddle_tpu.observability.anatomy import GAP_PART, OTHER_PART
from paddle_tpu.serving import layer_kinds
from paddle_tpu.serving.paged_cache import (_ROOT_KEY, _chain,
                                            PagedCacheConfig, PagedKVCache,
                                            payload_digest)
from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                          Reject, Request, SLOScheduler,
                                          SlotState)

# TTFT/queue-wait histograms need sub-second resolution around
# interactive SLO budgets; the default span (100us..100s) is too coarse
# for p99 interpolation there. SLO budgets should sit ON an edge: the
# burn-rate monitor counts violations conservatively (count_over), so a
# mid-bucket budget can never see violations inside its own bucket —
# 4.0 is here for the CPU bench's stated budget.
_LATENCY_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.2, 0.35,
                    0.5, 0.75, 1.0, 1.5, 2.0, 3.0, 4.0, 5.0, 7.5, 10.0,
                    15.0, 30.0, 60.0)

# decode-block / prefill-call wall times: geometric, 1 ms to 2 s in
# steps of sqrt(2), fine enough that an operator can take a median
_STEP_BUCKETS = tuple(round(1e-3 * 2 ** (k / 2), 6) for k in range(23))

# serving_step_part_seconds_total{phase,part}: the leaf spans of one
# engine step, by the work they time (PERF.md section 3)
#: what the step programs count on the device and hand back with the
#: tokens (``ServingSpec.stats`` plus those of the layers' kinds)
_STEP_STAT_HELP = {
    "moe_assignments": "token-expert pairs computed",
    "moe_experts_touched": "experts with at least one token, summed "
                           "over layers and token steps",
    "moe_expert_slots": "experts a layer has, summed over layers and "
                        "token steps (the denominator of touched)",
    "moe_max_expert_tokens": "the fullest expert's load, summed over "
                             "layers and token steps",
    "moe_routed_pairs": "token-expert pairs the router made, those of "
                        "experts held on other chips included (the "
                        "denominator of assignments where a chip holds a "
                        "share of a layer's experts)",
    "moe_tile_rows": "rows of the row tiles the grouped expert kernel "
                     "walked (tiles in use x rows a tile; the pairs "
                     "over it is the tiles' fill)",
    "attn_context_tokens": "cached tokens a query could attend to (the "
                           "indexer scores them once past topk), summed "
                           "over queries and layers",
    "attn_selected_tokens": "tokens attended after selection, summed "
                            "over queries and layers",
}

_STEP_PARTS = (("prefill", "assemble"), ("prefill", "cow_copy"),
               ("prefill", "dispatch"), ("prefill", "sync"),
               ("prefill", "book"), ("decode", "assemble"),
               ("decode", "dispatch"), ("decode", "sync"),
               ("decode", "book"), ("sched", "book"), ("observe", "book"))
#: what a step's record holds beside them: the caller's time between two
#: steps, and what is left of the step's wall (``anatomy.GAP_PART`` /
#: ``OTHER_PART``). A slow step is named after one of all thirteen
_SLOW_PARTS = _STEP_PARTS + (tuple(GAP_PART.split(".")),
                             tuple(OTHER_PART.split(".")))
#: lanes a prefill call carried; calls a step that made any
_LANE_BUCKETS = (1, 2, 4, 8, 16, 32, 64)
_CALL_BUCKETS = (1, 2, 4, 8, 12, 16, 24, 32, 64)
#: the one step of a prefill call's lanes: lane buckets are powers of two
#: up to here, then every multiple of it (:meth:`ServingEngine._pow2_count`),
#: and a window layer's ring gets this many pages of room (or the lanes
#: the budget buys, if fewer: ``layer_kinds.build(prefill_room=)``), so
#: one slot's longest run is one step and whole runs fill a bucket. A
#: pad lane runs the dense projections and the dense MLP on zeros (only
#: the attention kernels and the expert layer skip a dead lane), which a
#: call of a few lanes hides under its weights' read and a wide one does
#: not: a power-of-two bucket pads 24 live lanes with 8, a multiple of 8
#: with none, and at most 7 of any count (2.1 ms a pad lane in the
#: long-prompt cell: PERF.md section 6, PR 54). A page of room is
#: ``num_slots`` x window layers x a page of K and V: 8 are 1.47 GB over
#: the one page a ring had in the long-prompt cell (PERF.md section 3)
_LANE_STEP = 8
#: what the matrix unit retires while one byte arrives from HBM (TPU
#: v5e: 197 TFLOP/s in bf16 over 819 GB/s)
_FLOPS_PER_HBM_BYTE = 240


def _break_even_lanes(itemsize: int, chunk: int) -> int:
    """The lanes of ``chunk`` rows at which a prefill call's arithmetic
    takes as long as its weights' read, from the configuration's bytes
    and flops: a weight of ``itemsize`` bytes is read once a call and
    does 2 flops a row, so the two meet at ``itemsize *
    _FLOPS_PER_HBM_BYTE / 2`` rows (240 in bf16). A call under it is
    mostly a read of the stage's weights. Reckoned as if every held
    weight met every row: for a stage that holds more experts than a row
    meets it is the least the break-even can be (2 lanes in bf16 at a
    chunk of 128, where the long-prompt cell's held experts make it
    about 5), so no call worth its read is held back by it."""
    return -(-itemsize * _FLOPS_PER_HBM_BYTE // (2 * chunk))


#: the ``jax.named_scope`` names the two loops open, one around each
#: program hook (``_decode_loop`` / ``_prefill_loop``). They reach the
#: compiled programs' ``op_name`` metadata, where
#: ``observability.scopes`` books device time by them: a contract like
#: the ``serving.*`` span names (PERF.md section 3). No layer index: the
#: layers of a program are one scope.
STEP_SCOPES = ("embed", "attn_in", "write_rows", "attend", "attn_out",
               "mixer", "ffn", "head", "stats")

MIGRATION_FORMAT = "paddle_tpu.serving.slot-migration-v1"

# fleet-global prefix reuse (ISSUE 20): committed prefix pages travel
# between replicas in the SAME per-(page, tp-shard) sha256 shard layout
# as slot migration, wrapped per published page with its chain key and
# token content so the importer can re-verify the whole hash chain
PREFIX_BUNDLE_FORMAT = "paddle_tpu.serving.prefix-pages-v1"


class _StepPart:
    """One part of ``serving_step_part_seconds_total`` as a phase's
    counter: the bound child, and beside it the seconds credited inside
    the step in progress (``step()`` zeroes them on entry), so that a
    step knows its own parts from the clock reads its phases took."""

    __slots__ = ("_child", "in_step")

    def __init__(self, child):
        self._child = child
        self.in_step = 0.0

    def inc(self, n: float):
        self._child.inc(n)
        self.in_step += n


@dataclasses.dataclass
class _Block:
    """A decode block between its dispatch and its settle: what the
    device holds, and what the host promised on its strength."""
    out: object             # (S, decode_block) tokens, on the device
    started_from: object    # its input tokens on the device where a row
                            # owes its first token, else None
    counts: list            # program counts that come over with it:
                            # ``(the call's phase, handle)``
    rows: Dict[int, tuple]  # slot -> (rid, tokens to keep, owes its first)
    width: int
    rnd: object             # the round's phase (its span names the block)
    t0: float               # assemble.start
    t1: float               # dispatch.end

    def promised(self, slot: int, rid: int) -> int:
        """Tokens the settle will hand ``rid`` in ``slot``."""
        row = self.rows.get(slot)
        return row[1] + row[2] if row is not None and row[0] == rid else 0


class SlotMigrationError(RuntimeError):
    """A slot snapshot cannot be restored: corrupt shard (sha256
    mismatch), incompatible cache geometry, or no free slot/pages on
    the target engine."""


@guarded_by("_health_lock", "_health_snap")
class ServingEngine:
    """Continuous-batching front end over a ``models.gpt.GPT``.

    ``submit()`` enqueues a request (optionally tagging an SLO lane and
    a TTFT deadline), ``step()`` advances the engine one iteration
    (admit + budgeted batched prefill + dispatch one decode block +
    settle the one before + evict), and ``generate_many()`` drives the
    loop to completion. Decoding is
    greedy — the deterministic serving mode the paged-vs-dense parity
    tests pin down.
    """

    def __init__(self, model, params, *, num_slots: int = 8,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 max_tokens_per_slot: Optional[int] = None,
                 prefill_chunk: int = 32, decode_block: int = 8,
                 prefill_budget: Optional[int] = None,
                 attn_impl: str = "auto", cache_dtype=None,
                 prefix_sharing: Optional[bool] = None,
                 scheduler_policy: str = "slo",
                 lanes: Sequence[str] = ("interactive", "default", "batch"),
                 max_queue_depth: Optional[int] = None,
                 starvation_skips: int = 64,
                 registry=None, tracer=None,
                 ttft_budget_s: Optional[float] = None,
                 draft_model=None, draft_params=None, spec_k: int = 4,
                 snapshot_every_blocks: Optional[int] = None,
                 mesh=None, tp: Optional[int] = None,
                 tier: str = "colocated",
                 host_spill_pages: int = 0):
        # the model's block as the engine runs it (serving/program.py);
        # what that program cannot do yet is refused here, by name
        base = model.serving()
        cfg = spec = base.spec
        self.draft_program = draft_model.serving() \
            if draft_model is not None else None
        # -- disaggregation tier (ISSUE 19): a "prefill" engine runs
        # only the batched chunked prefill step and PARKS prefill-done
        # slots for handoff (poll_handoffs snapshots + releases them); a
        # "decode" engine accepts only restored slots and runs only the
        # decode block. "colocated" (default) is the classic engine —
        # every existing shape, bucket, and test is untouched.
        if tier not in ("colocated", "prefill", "decode"):
            raise ValueError(
                f"tier must be 'colocated', 'prefill' or 'decode', "
                f"got {tier!r}")
        if tier != "colocated" and draft_model is not None:
            raise ValueError(
                "speculative decoding does not compose with a "
                "disaggregated tier (draft caches do not migrate)")
        self.tier = tier
        # handoff-fallback slots allowed to decode on a prefill-tier
        # engine (restore_slot honors snap["decode_in_place"])
        self._decode_in_place: set = set()
        self.model = model
        self.params = params
        self.attn_impl = attn_impl
        self.prefill_chunk = int(prefill_chunk)
        self.decode_block = max(int(decode_block), 1)
        # -- tensor parallel (ISSUE 15): heads sharded H/tp over the
        # mesh's "tp" axis — per-shard page pools, both jitted steps
        # under shard_map with ONE psum at each layer's attention
        # output (the MLP/embeddings stay replicated: decode is
        # KV-bandwidth-bound, and that is what holds the sharded step
        # to a single collective kind).
        from paddle_tpu.core import mesh as mesh_lib
        mesh_tp = int(dict(mesh.shape).get("tp", 1)) if mesh is not None \
            else None
        if mesh is not None and tp is not None and int(tp) != mesh_tp:
            raise ValueError(f"tp={tp} disagrees with the mesh's tp "
                             f"axis ({mesh_tp})")
        if mesh is not None:
            tp = mesh_tp
        tp = int(tp or 1)
        wants = {
            "tp": tp > 1,
            "int8_pages": cache_dtype is not None
            and jnp.dtype(cache_dtype) == jnp.dtype(jnp.int8),
            "draft": draft_model is not None,
            "host_spill": host_spill_pages > 0,
            "migration": snapshot_every_blocks is not None,
            "tiers": tier != "colocated",
            "prefix_sharing": bool(prefix_sharing),
        }
        for feature, wanted in wants.items():
            if wanted:
                self._require(feature, "ServingEngine()", spec)
        # left unsaid, prompts share their prefixes where the program can
        if prefix_sharing is None:
            prefix_sharing = "prefix_sharing" in spec.supports
        if draft_model is not None \
                and "draft" not in self.draft_program.spec.supports:
            raise ValueError(f"{type(draft_model).__name__} cannot be a "
                             "draft model yet ('draft')")
        if tp > 1 and mesh is None:
            devs = jax.devices()
            if len(devs) < tp:
                raise ValueError(
                    f"tp={tp} needs {tp} devices, have {len(devs)}")
            mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(tp=tp),
                                      devices=devs[:tp])
        if tp > 1:
            if cfg.num_heads % tp:
                raise ValueError(
                    f"tp={tp} must divide num_heads={cfg.num_heads}")
            if draft_model is not None:
                raise ValueError(
                    "speculative decoding does not compose with tensor "
                    "parallelism (the draft cache is single-device)")
        # a mesh whose tp axis is 1 adds nothing here — drop it so
        # health()'s chip accounting cannot read replication-only axes
        # (dp etc.) as serving capacity
        self.mesh = mesh if tp > 1 else None
        self.tp = tp
        self._tp_heads = cfg.num_heads // tp
        # prefill tier + tp: shard the MLP too (Megatron ffn_up
        # column / down row split) — prefill is flops-bound, so the MLP
        # matmuls are worth the second psum per layer. Gated to the
        # prefill tier so the colocated/decode step HLO (and every
        # pre-existing cost surface) stays byte-identical.
        self._mlp_sharded = self.tier == "prefill" and tp > 1
        # -- speculative decoding (ISSUE 13): a draft model proposes
        # spec_k tokens per slot per round; the target verifies them all
        # in ONE fixed-shape batched-prefill-shaped step
        self.draft_model = draft_model
        self.draft_params = draft_params
        self.speculative = draft_model is not None
        self.spec_k = int(spec_k)
        if self.speculative:
            if draft_params is None:
                raise ValueError("draft_model needs draft_params")
            dspec = self.draft_program.spec
            if dspec.vocab_size != cfg.vocab_size:
                raise ValueError(
                    "draft and target models must share a vocabulary "
                    f"({dspec.vocab_size} != {cfg.vocab_size})")
            if self.spec_k < 2:
                raise ValueError("spec_k must be >= 2 (spec_k=1 is "
                                 "plain decoding — drop the draft)")
            # the draft cache must hold EVERY prompt token (the draft
            # prefills alongside the target), so target-side prefix
            # sharing — which skips prefilling shared tokens — would
            # desynchronize the two caches; speculation disables it
            prefix_sharing = False
        # prefill/decode interleaving budget: prompt tokens per step()
        # (default = one full batched call across every slot)
        self.prefill_budget = int(prefill_budget or
                                  num_slots * self.prefill_chunk)
        if max_tokens_per_slot is None:
            max_tokens_per_slot = cfg.max_position
        max_pages_per_slot = -(-max_tokens_per_slot // page_size)
        if num_pages is None:
            # enough for every slot full, +1 null page — callers can size
            # DOWN to bet on early EOS (that is the paging win)
            num_pages = num_slots * max_pages_per_slot + 1
        # like generate(cache_dtype=...): a bf16 page pool halves KV
        # gather traffic (softmax still runs fp32 inside the kernel), an
        # int8 one (its own kind of layer) roughly halves it AGAIN
        dtype = cache_dtype or base.param_dtype(params)
        # a tp engine's pool is globally shaped but placed sharded H/tp
        geometry = dict(num_slots=num_slots, page_size=page_size,
                        num_pages=num_pages)
        #: the most lanes one prefill call carries: what the budget buys
        #: (the liveness lane where it buys none), a lane a slot at least
        #: (a lane's index codes its first token's place for the decode
        #: block, under ``num_slots``)
        self._lane_cap = min(
            max(self.prefill_budget // self.prefill_chunk, 1), num_slots)
        self.cache = self._paged_cache(
            spec, dtype, prefix_sharing, geometry, max_pages_per_slot,
            tp=tp, mesh=self.mesh, host_spill_pages=host_spill_pages)
        #: one object a kind of layer, for the host's counts
        self._kinds = tuple(dict.fromkeys(self.cache.config.kinds))
        #: the pages carry scale rows (the page wire format's two shapes)
        self.quantized = any(kind.quantized for kind in self._kinds)
        #: the lanes of a prefill call's table carry their slot beside the
        #: pages (pool row slot + 1): whose state row, whose ring
        self._lane_slot_column = bool(spec.slot_state) or any(
            kind.by_slot for kind in self._kinds)
        #: the step programs take a slot's whole table and no narrower
        #: one: where a kind says so, and where the program has state
        #: layers (they never see the table, and the dense paged kernels of
        #: the attention layers beside them skip the pages past a lane's
        #: tokens: a narrow table saves a few dead grid steps, not worth a
        #: compile of both step programs a width)
        self._whole_table = bool(spec.state_layers) or any(
            kind.whole_table for kind in self._kinds)
        #: consecutive chunks of ONE slot a prefill call may carry: the
        #: least over what the program is built from. Its layers' kinds
        #: say theirs; a program that carries state from chunk to chunk
        #: outside the pages (two lanes of a slot would write one state
        #: row) and an engine option not shown to take runs yet (a draft
        #: cache beside the target's, a tier, a sharded pool) answer 1,
        #: which is the call of one chunk a slot as it always was
        self._run_limit = 1 if (
            spec.slot_state or self.speculative or tier != "colocated"
            or tp > 1) else max(min(
                [self._lane_cap] + [kind.prefill_run for kind in self._kinds
                                    if kind.prefill_run is not None]), 1)
        #: the fewest live lanes a further call of a step is made for
        #: where calls carry runs (:meth:`_prefill_round`)
        self._second_call_lanes = _break_even_lanes(
            jnp.dtype(base.param_dtype(params)).itemsize, self.prefill_chunk)
        self.draft_cache = None
        if self.speculative:
            # same slot/page geometry as the target cache: allocations
            # run in lockstep (reserve/free the same slots for the same
            # token counts), so target admission implies draft admission
            self.draft_cache = self._paged_cache(
                self.draft_program.spec,
                cache_dtype or self.draft_program.param_dtype(draft_params),
                False, geometry, max_pages_per_slot)
        if scheduler_policy == "slo":
            self.scheduler = SLOScheduler(
                num_slots, can_admit=self._can_admit, lanes=lanes,
                max_queue_depth=max_queue_depth,
                starvation_skips=starvation_skips)
        elif scheduler_policy == "fifo":
            self.scheduler = ContinuousBatchingScheduler(
                num_slots, can_admit=self._can_admit)
        else:
            raise ValueError(
                f"scheduler_policy must be 'slo' or 'fifo', "
                f"got {scheduler_policy!r}")

        from paddle_tpu import observability as obs
        self._reg = registry or obs.default()
        self.recompile_detector = obs.RecompileDetector(
            "serving_decode", warmup=1, registry=self._reg)
        # the step programs stay catalogued after this engine is dropped,
        # so that a trace of its steps can be booked by scope afterwards
        obs.recompile.hold_step_programs()
        # request-lifecycle tracing: one root span per request, children
        # per prefill chunk / decode block, scheduler verdicts as events.
        # All host-side — nothing below touches jitted code, so tracing
        # on/off cannot change compiled shapes (zero-recompile invariant
        # is RecompileDetector-asserted with tracing enabled in tests).
        self.tracer = tracer or obs.tracing.default()
        self._req_spans: Dict[int, object] = {}
        self._phase_acc: Dict[int, Dict[str, float]] = {}
        self.scheduler.event_cb = self._sched_event
        # SLO burn-rate monitor over the TTFT histogram: deadline
        # pressure becomes visible (gauge + alert counter + trace
        # events) BEFORE requests start getting shed
        self.ttft_budget_s = ttft_budget_s
        self.slo_monitor = None
        if ttft_budget_s is not None:
            self.slo_monitor = obs.BurnRateMonitor(
                "serving_ttft_seconds", ttft_budget_s,
                windows=(60.0, 300.0), registry=self._reg,
                tracer=self.tracer)
        # step-time anatomy (ISSUE 16): host gap / phase-split device
        # busy / host assembly per step; the flight recorder rides along
        # as the replica's crash black box (the router dumps it on eject)
        self.anatomy = obs.StepAnatomy(registry=self._reg)
        self.flight = obs.FlightRecorder(
            "engine", anatomy=self.anatomy, registry=self._reg,
            tracer=self.tracer)
        # the program the jitted steps run (one head shard's under tp)
        self.program = base if self.tp == 1 else model.serving(
            tp=self.tp, mlp_sharded=self._mlp_sharded)
        #: counts the steps hand back beside the tokens, in order
        self._step_stats = self._stat_names(spec, self.cache.config.kinds)
        self._bind_step_metrics()

        # step-side params: under tp the program re-lays its tree out
        # so that a "tp" shard boundary is a head boundary and says how
        # to shard it (serving/program.py); tp=1 uses the model's own
        # tree untouched
        if self.tp > 1:
            from jax.sharding import PartitionSpec as PSpec

            from paddle_tpu.core.compat import shard_map
            from paddle_tpu.parallel import plan as plan_lib
            tp_params = self.program.tp_params(params)
            self._param_specs = self.program.tp_plan().params_specs(
                tp_params)
            self._step_params = jax.device_put(
                tp_params,
                plan_lib.named_shardings(mesh, self._param_specs))
            # don't pin the caller's unsharded attention projections
            # for the engine's lifetime next to their sharded copies:
            # under tp, self.params IS the step-side (re-laid-out,
            # sharded) tree
            self.params = self._step_params
            rep = PSpec()
            self._page_specs = self.cache.page_specs()
            step_specs = (self._param_specs, self._page_specs,
                          rep, rep, rep, rep)
            self.decode_step = jax.jit(shard_map(
                self._decode_step_impl, mesh=mesh, in_specs=step_specs,
                out_specs=(rep, self._page_specs), check_vma=False),
                donate_argnums=(1,))
            self.prefill_step = jax.jit(shard_map(
                self._prefill_step_impl, mesh=mesh, in_specs=step_specs,
                out_specs=(rep, self._page_specs), check_vma=False),
                donate_argnums=(1,))
        else:
            self._step_params = params
            self.decode_step = jax.jit(self._decode_step_impl,
                                       donate_argnums=(1,))
            self.prefill_step = jax.jit(self._prefill_step_impl,
                                        donate_argnums=(1,))
        if self.speculative:
            # draft pages donate into their own steps; the verify step
            # donates the TARGET pages exactly like prefill does
            self.draft_prefill_step = jax.jit(
                self._draft_prefill_step_impl, donate_argnums=(1,))
            self.draft_propose_step = jax.jit(
                self._draft_propose_step_impl, donate_argnums=(1,))
            self.verify_step = jax.jit(self._verify_step_impl,
                                       donate_argnums=(1,))
        self.copy_page_step = jax.jit(self._copy_page_impl,
                                      donate_argnums=(0,))
        # a finished prompt's first token travels from its prefill call
        # to the decode block of the same step on the device. A compiled
        # program is keyed by where its operands are committed too, so
        # the token vectors the host uploads are placed like the ones the
        # steps hand back: under tp committed to the whole mesh
        self._upload = jnp.asarray
        placed = {}
        if self.tp > 1:
            whole = jax.sharding.NamedSharding(self.mesh, PSpec())
            self._upload = lambda a: jax.device_put(a, whole)
            placed = dict(in_shardings=whole, out_shardings=whole)
        self.first_token_step = jax.jit(self._first_token_impl, **placed)
        #: first tokens the host has not read yet: ``(the finishing
        #: call's tokens on the device, [(lane, slot), ...])``; the
        #: step's decode block takes them over, so empty whenever
        #: ``step()`` returns
        self._owed: List[tuple] = []
        #: program counts of calls no block has taken over yet: ``(the
        #: call's phase, handle)``
        self._unread_counts: List[tuple] = []
        #: the decode block in flight: dispatched, not read (``step()``)
        self._pending: Optional[_Block] = None
        self._last_settle_end = 0.0
        self._left_step_at = 0.0
        # whether the engine held work when step() last returned: only
        # then is the caller's time until the next step a part of it
        self._left_with_work = False
        #: the step in progress: its prefill calls ``(lanes_live, lanes,
        #: width, tokens, seconds)`` and what a slow step's record says
        self._step_calls: Optional[List[tuple]] = None
        self._step_width = 0
        #: the slots this step's prefill calls have carried
        #: (:meth:`_prefill_round`'s rule on further calls)
        self._step_carried: set = set()
        # migration page IO (fleet drain): src/dst are traced scalars,
        # so ONE compile each covers every page ever moved
        self.read_page_step = jax.jit(self._read_page_impl)
        self.write_page_step = jax.jit(self._write_page_impl,
                                       donate_argnums=(0,))
        # HBM->host spill tier (ISSUE 20): the cache calls back through
        # the SAME warmed ("page_read",) signature when it pages a cold
        # published page out, so spill traffic compiles nothing
        self.cache.attach_spill_io(self._spill_read)
        # finished-request store for result(); pop-on-read + bounded, so
        # a server that only consumes step()'s return dict still cannot
        # grow host memory with the total requests ever served
        self._results: "OrderedDict[int, np.ndarray]" = OrderedDict()
        self._rejects: "OrderedDict[int, Reject]" = OrderedDict()
        self._stats: "OrderedDict[int, Dict[str, float]]" = OrderedDict()
        self._results_cap = max(64, 16 * num_slots)
        # filled by warmup(): compiled bucket signatures + their static
        # cost reports (the bucket-coverage proof reads warmup_plan()
        # when warmup has not run yet)
        self.warmed_signatures: set = set()
        self.bucket_costs: Dict[tuple, object] = {}
        # micro-checkpoints (fleet fault tolerance): every K decode
        # blocks an in-flight slot's snapshot_slot lands in a host-side
        # outbox the replica handle drains to the router — a crashed
        # replica's requests then warm-restore on a peer instead of
        # re-decoding from the prompt. Host-side page reads only
        # (("page_read",) is a warmed signature), so the zero-recompile
        # invariant holds with checkpointing on.
        if snapshot_every_blocks is not None:
            if self.speculative:
                raise ValueError(
                    "micro-checkpoints need slot migration, which "
                    "speculative engines do not support")
            if snapshot_every_blocks < 1:
                raise ValueError("snapshot_every_blocks must be >= 1")
        self.snapshot_every_blocks = snapshot_every_blocks
        # where something reads a slot's tokens or pages between two
        # blocks, a block is settled in the step that dispatched it: a
        # speculative round reads ``generated`` on the host, a tier
        # hands slots over after every step, micro-checkpoints read
        # pages after every block. Facts of how the engine was built
        self._settles_at_once = (self.speculative or tier != "colocated"
                                 or snapshot_every_blocks is not None)
        self._micro_snaps: Dict[int, Dict] = {}
        self._last_snap_blocks: Dict[int, int] = {}
        # externally-minted trace ids (router propagation) so
        # request_stats carries them even with tracing disabled
        self._ext_trace: Dict[int, int] = {}
        self.migrated_in_total = 0
        self.migrated_out_total = 0
        # resource-headroom plane (ISSUE 16): static per-bucket flops x
        # observed step counts vs elapsed busy time, with the best
        # per-call rate as the utilization ceiling (the high-water mark
        # this hardware + bucket set actually demonstrated)
        self._busy_s = 0.0
        # calls dispatched and not waited for yet (_note_busy)
        self._unwaited_sigs: tuple = ()
        self._unwaited_s = 0.0
        self._flops_done = 0.0
        self._flops_rate_peak = 0.0
        self._anat_steps = 0
        # health(): a fleet router polls from ITS thread while step()
        # mutates the scheduler/cache books — the engine publishes a
        # consistent snapshot at safe points and health() only ever
        # reads that, under a lock (never the live books)
        self._health_lock = threading.Lock()
        self._health_snap: Dict[str, object] = {}
        self._refresh_health()

    def _bind_step_metrics(self):
        """Every series ``step()`` touches, bound once: a step then pays
        one lock acquisition per update and no look-up by name."""
        r = self._reg
        part = r.counter(
            "serving_step_part_seconds_total",
            "seconds inside step() by phase (prefill/decode/sched/observe) "
            "and part (assemble/cow_copy/dispatch/sync/book): the same "
            "clock reads as the serving.* phase spans")
        self._c_part = {(ph, pt): _StepPart(part.child(phase=ph, part=pt))
                        for ph, pt in _STEP_PARTS}
        self._step_parts = [(f"{ph}.{pt}", child)
                            for (ph, pt), child in self._c_part.items()]
        # the slow steps (observability/anatomy.SlowStepRule): every child
        # bound here, so a run that met none reads 0.0 and not nothing
        slow = r.counter(
            "serving_slow_steps_total",
            "working steps the slow-step rule flagged, by the part with "
            "the largest excess (caller/gap: the caller's time between "
            "two steps; step/other: inside step(), in no named part)")
        excess = r.counter(
            "serving_slow_step_excess_seconds_total",
            "seconds the flagged steps' parts took above three times "
            "their own medians, by the part that named the step")
        self._c_slow = {f"{ph}.{pt}": (slow.child(phase=ph, part=pt),
                                       excess.child(phase=ph, part=pt))
                        for ph, pt in _SLOW_PARTS}
        self._c_step_traces = r.counter(
            "serving_step_traces_total",
            "functions traced to a jaxpr while a working step ran (the "
            "compile listener's trace events; warm-up is outside step()): "
            "a step program traced again costs its tracing even where "
            "nothing compiles").child()
        self._c_step_gc = r.counter(
            "serving_step_gc_seconds_total",
            "seconds the garbage collector ran inside working steps"
        ).child()
        self._h_call_lanes = r.histogram(
            "serving_prefill_call_lanes",
            "live lanes a batched prefill call carried (its lanes less "
            "the padding up to the bucket): a lane is one chunk of one "
            "slot, and a slot may give a call several",
            buckets=_LANE_BUCKETS).child()
        self._h_run_chunks = r.histogram(
            "serving_prefill_run_chunks",
            "consecutive chunks of one slot in a batched prefill call, "
            "observed once a slot and call (1: the call carried one chunk "
            "of the slot)", buckets=_LANE_BUCKETS).child()
        lanes = r.counter(
            "serving_prefill_lanes_total",
            "lanes of the batched prefill calls: kind=live those that "
            "carried a chunk, kind=bucket those the call's program ran "
            "(live and the pad up to the bucket)")
        self._c_lanes_live = lanes.child(kind="live")
        self._c_lanes_bucket = lanes.child(kind="bucket")
        self._h_step_calls = r.histogram(
            "serving_step_prefill_calls",
            "batched prefill calls in a step that made any",
            buckets=_CALL_BUCKETS).child()
        self._c_step_seconds = r.counter(
            "serving_step_seconds_total",
            "wall seconds inside step(), steps that did work only").child()
        self._c_prefill_calls = r.counter(
            "serving_prefill_calls_total",
            "batched prefill calls (one per serving.prefill_call)").child()
        self._c_decode_rounds = r.counter(
            "serving_decode_rounds_total",
            "decode blocks dispatched (one per serving.decode_round that "
            "had a slot to advance)").child()
        self._c_overlapped = r.counter(
            "serving_decode_blocks_overlapped_total",
            "decode blocks dispatched while the block before was still "
            "unread: the host's work of a step ran beside the device"
        ).child()
        self._c_discarded = r.counter(
            "serving_decode_discarded_tokens_total",
            "tokens a decode block computed for a request that had "
            "ended by then (an eos_id inside the block, or inside the "
            "block before while this one was in flight)").child()
        back = r.counter(
            "serving_device_readbacks_total",
            "times the host waited for a device value inside step(), by "
            "the phase that waited: the tokens of a batched call (prefill, "
            "decode) or a page on its way to the host (page_read: spill, "
            "micro-checkpoints)")
        self._c_readbacks = {ph: back.child(phase=ph)
                             for ph in ("prefill", "decode", "page_read")}
        kv = r.counter(
            "serving_decode_kv_bytes_total",
            "K/V bytes per decode round: kind=live is what the live "
            "tokens hold (token steps x live lengths x 2 x H x Dh x "
            "itemsize x layers), kind=gathered what the kernel's (slots, "
            "pages) grid visits (token steps x slots x gather width x "
            "page bytes x layers)")
        self._c_kv_live = kv.child(kind="live")
        self._c_kv_gathered = kv.child(kind="gathered")
        self._h_decode_step = r.histogram(
            "serving_decode_step_seconds",
            "wall time per decode block: assemble.start to the end of "
            "its read-back, or from the end of the read-back before "
            "where the block was dispatched ahead of it, less what the "
            "caller spent between the two step() calls",
            buckets=_STEP_BUCKETS).child()
        self._h_prefill_step = r.histogram(
            "serving_prefill_step_seconds",
            "wall time per batched prefill call (sync included)",
            buckets=_STEP_BUCKETS).child()
        self._h_ttft = r.histogram(
            "serving_ttft_seconds", "submit -> first token latency",
            buckets=_LATENCY_BUCKETS).child()
        self._h_admit_to_first = r.histogram(
            "serving_admit_to_first_token_seconds",
            "admit -> first token (prefill cost, net of queue wait)",
            buckets=_LATENCY_BUCKETS).child()
        self._c_tokens = r.counter("serving_tokens_total",
                                   "decode tokens produced").child()
        self._c_steps = r.counter("serving_steps_total").child()
        self._c_prefill_tokens = r.counter(
            "serving_prefill_tokens_total",
            "prompt tokens actually computed by prefill (shared "
            "prefix tokens are skipped)").child()
        self._c_prefix_shared = r.counter(
            "serving_prefix_shared_tokens_total",
            "prompt tokens skipped via shared prefix pages").child()
        self._c_cow = r.counter(
            "serving_prefix_cow_total",
            "copy-on-write page copies for shared tails").child()
        self._g_occupancy = r.gauge(
            "serving_slot_occupancy",
            "fraction of decode slots live").child()
        self._g_page_util = r.gauge(
            "serving_page_utilization",
            "live tokens / page-pool capacity").child()
        head = r.gauge(
            "serving_headroom",
            "spare capacity per resource (1 = idle, 0 = saturated)")
        self._g_headroom = {
            res: head.child(resource=res)
            for res in ("flops", "pages", "slots", "hbm", "spill")}
        self._g_spill_pages = r.gauge(
            "serving_spill_pages",
            "published KV pages resident in the host spill pool").child()
        self._g_spill_bytes = r.gauge(
            "serving_spill_bytes",
            "bytes of KV (incl. int8 scale rows) in the host spill pool"
        ).child()
        self._bind_state_metrics(r)
        for kind in self._kinds:
            kind.bind(r)
        self._c_step_stats = [
            r.counter(f"serving_{name}_total", _STEP_STAT_HELP.get(
                name, "a count the step program hands back")).child()
            for name in self._step_stats]
        self._g_prefix_saved = r.gauge(
            "serving_prefix_saved_per_token",
            "prefill tokens skipped via prefix sharing per served token"
        ).child()
        warm = r.counter(
            "serving_warmup_seconds_total",
            "seconds of warmup() by part: cost_gauges (the static cost "
            "model's second trace + lowering of each bucket) and "
            "first_call (trace, lower, compile or cache load, run)")
        self._c_warm_cost = warm.child(part="cost_gauges")
        self._c_warm_first = warm.child(part="first_call")

    def _bind_state_metrics(self, r):
        """The series of a program that keeps state a slot
        (``spec.slot_state``), fed from counts the host already holds;
        a program without keeps none of them."""
        self._state_slot_bytes = self.cache.state_bytes_per_slot()
        if not self._state_slot_bytes:
            return
        self._c_ssm_decode = r.counter(
            "serving_ssm_decode_slot_steps_total",
            "live slots x token steps x layers whose slot state a "
            "decode token step advanced by one token (a recurrence's "
            "one-token update, or the attention projections' conv "
            "tails)").child()
        self._c_ssm_prefill = r.counter(
            "serving_ssm_prefill_tokens_total",
            "valid prompt tokens x layers whose slot state a prefill "
            "call advanced (the chunked scan, or the tails)").child()
        moved = r.counter(
            "serving_ssm_state_bytes_total",
            "slot-state bytes (every entry of the program's slot_state, "
            "every layer: a mixer's conv window + head states, or the "
            "attention projections' tails) the steps read and wrote: a "
            "decode token step reads and writes each live slot's; a "
            "prefill call writes each lane's and reads it unless the "
            "lane's prompt starts there")
        self._c_ssm_read = moved.child(kind="read")
        self._c_ssm_written = moved.child(kind="written")
        self._c_ssm_resets = r.counter(
            "serving_ssm_state_resets_total",
            "prompts started from a zero state (one an admission)").child()
        r.gauge("serving_ssm_state_pool_bytes",
                "bytes of the slot-state pool, null row included").set(
                    self._state_slot_bytes * (self.scheduler.num_slots + 1))

    def _count_state(self, span, decoding: int = 0, token_steps: int = 0,
                     lanes: int = 0, fresh: int = 0, tokens: int = 0):
        """One decode round's or prefill call's slot-state work, and the
        span's ``state_slots``."""
        if not self._state_slot_bytes:
            return
        layers = self.cache.state_layers()
        self._c_ssm_decode.inc(decoding * token_steps * layers)
        self._c_ssm_prefill.inc(tokens * layers)
        self._c_ssm_resets.inc(fresh)
        b = self._state_slot_bytes
        self._c_ssm_read.inc(b * (decoding * token_steps + lanes - fresh))
        self._c_ssm_written.inc(b * (decoding * token_steps + lanes))
        if span is not None:
            span.set_attrs(state_slots=decoding + lanes)

    def _paged_cache(self, spec, dtype, share_prefix, geometry,
                     max_pages_per_slot, tp=1, **placed) -> PagedKVCache:
        """The page cache of the program ``spec`` describes, its layers'
        kinds built by the one function that decides them."""
        kinds = layer_kinds.build(
            spec, dtype=dtype, share_prefix=share_prefix, tp=tp,
            impl=self.attn_impl, prefill_chunk=self.prefill_chunk,
            prefill_room=min(self._lane_cap, _LANE_STEP),
            **geometry)
        return PagedKVCache(PagedCacheConfig(
            num_layers=spec.num_layers, num_heads=spec.kv_heads,
            head_dim=spec.head_dim, max_pages_per_slot=max_pages_per_slot,
            dtype=dtype, share_prefix=share_prefix,
            slot_state=spec.slot_state,
            slot_state_dtype=jnp.dtype(spec.slot_state_dtype),
            kinds=kinds, **geometry), **placed)

    def _shared_groups(self, dslots) -> tuple:
        """The decode step's seventh argument as ``(groups,)``, where a
        layer's kind folds the pages that several of ``dslots``' tables
        open with into one walk; ``()`` where none does."""
        groups = ()
        for kind in self._kinds:
            groups = groups or kind.decode_groups(
                self.cache.block_tables, self.cache.lengths, dslots)
        return groups

    def _require(self, feature: str, what: str, spec=None):
        """The one refusal of an option or call the model's serving
        program does not carry yet (its pool entries hold rows or state
        this path would lose): the model's class and the feature, by
        name. ``spec``: the program's, while the engine is being built."""
        spec = spec or self.program.spec
        if feature not in spec.supports:
            raise ValueError(
                f"{what}: {type(self.model).__name__} does not serve "
                f"with {feature!r} yet (its serving program supports "
                f"{sorted(spec.supports) or 'none of the options'})")

    # -- request surface --------------------------------------------------

    def _can_admit(self, req) -> bool:
        return self.cache.can_reserve(req.total_tokens, prompt=req.prompt)

    def submit(self, prompt, max_new_tokens: int = 32,
               eos_id: Optional[int] = None, *, lane: str = "default",
               ttft_deadline_s: Optional[float] = None,
               trace_id: Optional[int] = None) -> int:
        """Enqueue a request; returns its rid. ``lane`` and
        ``ttft_deadline_s`` feed the SLO scheduler (ignored under
        ``scheduler_policy="fifo"``). ``trace_id`` adopts an externally
        minted trace id (the fleet router's) for the request's root
        span instead of starting a fresh trace, and is carried through
        ``request_stats`` even with tracing off — one Perfetto timeline
        then shows the request crossing router and replica. Raises
        :class:`~paddle_tpu.serving.LoadShedError` (with a structured
        :class:`~paddle_tpu.serving.Reject`) when the scheduler sheds
        the request instead of queueing it."""
        from paddle_tpu.serving.scheduler import LoadShedError
        if self.tier == "decode":
            # fresh prompts would run prefill buckets this tier never
            # warms; the two-tier router routes prompts to the prefill
            # tier and this engine only ever sees restore_slot
            raise ValueError(
                "decode-tier engines accept only restored slots "
                "(restore_slot), not fresh prompts")
        total = len(np.asarray(prompt).reshape(-1)) + max_new_tokens
        limit = min(self.cache.config.max_tokens_per_slot,
                    self.program.spec.max_position)
        if total > limit:
            raise ValueError(f"request needs {total} tokens > per-slot "
                             f"limit {limit}")
        if self.cache.config.pages_for(total) > self.cache.config.num_pages - 1:
            raise ValueError("request exceeds the whole page pool")
        try:
            rid = self.scheduler.submit(prompt, max_new_tokens, eos_id,
                                        lane=lane,
                                        ttft_deadline_s=ttft_deadline_s)
        except LoadShedError as e:
            self._reg.counter("serving_rejected_total",
                              "requests load-shed instead of queued").inc(
                                  reason=e.reject.reason)
            if self.tracer.enabled:
                # shed-at-submit: a zero-length request span whose
                # attributes carry the structured verdict
                self.tracer.record_span(
                    "serving.request", duration_s=0.0, status="shed",
                    lane=lane, shed_reason=e.reject.reason,
                    queue_depth=e.reject.queue_depth,
                    est_ttft_s=round(e.reject.est_ttft_s, 6))
            raise
        self._reg.counter("serving_requests_total",
                          "requests submitted to the engine").inc()
        self._reg.counter("serving_prompt_tokens_total",
                          "prompt tokens submitted").inc(total -
                                                         max_new_tokens)
        self._phase_acc[rid] = {"prefill_s": 0.0, "decode_s": 0.0,
                                "prefill_chunks": 0.0,
                                "decode_blocks": 0.0,
                                "shared_tokens": 0.0,
                                "spec_proposed": 0.0,
                                "spec_accepted": 0.0}
        if trace_id is not None:
            self._ext_trace[rid] = int(trace_id)
        if self.tracer.enabled:
            root = self.tracer.start_span(
                "serving.request", trace_id=trace_id, rid=rid, lane=lane,
                prompt_tokens=total - max_new_tokens,
                max_new_tokens=max_new_tokens)
            root.add_event("submitted",
                           queue_depth=self.scheduler.queue_depth())
            self._req_spans[rid] = root
        self._refresh_health()
        return rid

    def _sched_event(self, rid: int, name: str, **attrs):
        """Scheduler decision → event on the request's trace span."""
        root = self._req_spans.get(rid)
        if root is not None:
            root.add_event(name, **attrs)

    def result(self, rid: int) -> Optional[np.ndarray]:
        """Generated tokens for a finished request (None while running
        or already consumed). Pop-on-read, and the store keeps only the
        most recent finishers (``step()``'s return dict is the primary
        delivery path) — consume results promptly."""
        return self._results.pop(rid, None)

    def reject_reason(self, rid: int) -> Optional[Reject]:
        """Structured reject for a request shed AFTER queueing (its
        TTFT deadline expired before admission); pop-on-read."""
        return self._rejects.pop(rid, None)

    def request_stats(self, rid: int) -> Optional[Dict[str, float]]:
        """Per-request latency record for a finished request — the wall
        split (``ttft_s``, ``queue_wait_s``, ``prefill_s``) plus the
        per-phase breakdown sourced from the request's trace spans:
        ``prefill_compute_s`` / ``decode_s`` (time inside the batched
        fixed-shape calls), ``prefill_chunks`` / ``decode_blocks``,
        ``shared_tokens`` (prefix-share savings), ``tokens``, and
        ``trace_id`` (0 when tracing was off) — the exact per-request
        numbers behind the histogram aggregates (SLO audits read these;
        pop-on-read, bounded like ``result``)."""
        return self._stats.pop(rid, None)

    def _refresh_health(self):
        """Recompute the health snapshot from the live scheduler/cache
        books. Called only from the engine's own thread at consistent
        points (construction, submit, end of step, migration), so the
        reads here never race the step loop; cross-thread readers get
        the last published snapshot via :meth:`health`."""
        h: Dict[str, object] = {
            "slot_occupancy": self.scheduler.occupancy(),
            "queue_depth": self.scheduler.queue_depth(),
            "page_utilization": self.cache.utilization(),
            "free_slots": len(self.scheduler.free_slots()),
            "recompiles": self.recompile_detector.recompiles,
            "requests_in_flight": len(self.scheduler.active_slots()),
            "steps": int(self._c_steps.value()),
            # mesh shape (ISSUE 15): the autoscaler and /healthz must
            # distinguish a 4-chip tp replica from a 1-chip one. The
            # chip count is the TP degree, not the raw mesh size — a
            # dp axis only replicates this engine's work
            "tp": self.tp,
            "mesh_devices": self.tp,
            # disaggregation tier: the two-tier router and the
            # autoscaler key placement/scaling decisions off this
            "tier": self.tier,
            # hierarchical KV (ISSUE 20): bumps on ANY publication
            # change in EITHER tier (device index or host spill pool),
            # so fleet affinity snapshots can detect a replica that
            # dropped a prefix it used to advertise
            "prefix_gen": int(self.cache.prefix_gen),
        }
        if self.slo_monitor is not None:
            h["slo"] = self.slo_monitor.status()
        h["headroom"] = self._headroom()
        with self._health_lock:
            self._health_snap = h

    def _headroom(self) -> Dict[str, float]:
        """The resource-headroom plane (ISSUE 16): per-resource spare
        capacity in [0, 1] — the routing signal the two-tier dispatcher
        reads (prefill placement wants flops headroom, decode placement
        wants page/slot headroom), published as ``serving_headroom``
        gauges and aggregated fleet-wide by ``FleetMonitor``."""
        util = self.cache.utilization()
        free = len(self.scheduler.free_slots())
        s_tot = self.scheduler.num_slots
        cap_b = self.cache.capacity_bytes()
        live_b = self.cache.live_bytes()
        # flops utilization: static bucket flops actually retired per
        # busy second, against the best per-call rate ever observed —
        # 0.0 (full headroom) until warmup(cost_gauges=True) priced the
        # buckets and a step ran
        flops_util = 0.0
        if self._busy_s > 0 and self._flops_rate_peak > 0:
            flops_util = min(
                (self._flops_done / self._busy_s)
                / self._flops_rate_peak, 1.0)
        tokens = self._c_tokens.value()
        saved = self._c_prefix_shared.value()
        head = {
            "flops_utilization": round(flops_util, 6),
            "flops": round(1.0 - flops_util, 6),
            "pages": round(max(1.0 - util, 0.0), 6),
            "slots": round(free / s_tot, 6),
            "hbm": round(max(1.0 - (live_b / cap_b if cap_b else 0.0),
                             0.0), 6),
            "hbm_live_bytes": int(live_b),
            "hbm_capacity_bytes": int(cap_b),
            "flops_per_busy_s": (self._flops_done / self._busy_s
                                 if self._busy_s > 0 else 0.0),
            "prefix_saved_per_token": round(
                saved / tokens if tokens else 0.0, 6),
        }
        # host spill tier: headroom 1.0 when the tier is off (it can
        # never veto anything), else spare host-pool capacity — the
        # autoscaler's scale-in veto reads this so a fleet does not
        # shrink away the replica holding everyone's cold prefixes
        pool = self.cache.spill_pool
        if pool is None:
            head["spill"] = 1.0
            head["spill_pages"] = 0
            head["spill_bytes"] = 0
        else:
            head["spill"] = round(
                max(1.0 - len(pool) / pool.capacity, 0.0), 6)
            head["spill_pages"] = len(pool)
            head["spill_bytes"] = int(pool.spilled_bytes())
        for res, g in self._g_headroom.items():
            g.set(head[res])
        self._g_spill_pages.set(head["spill_pages"])
        self._g_spill_bytes.set(head["spill_bytes"])
        self._g_prefix_saved.set(head["prefix_saved_per_token"])
        return head

    def _note_busy(self, sigs, dur: float, waited: bool = True):
        """Headroom accounting for one jitted call: busy seconds plus
        the static flops of the bucket(s) it retired (when warmup
        priced them). A call the host did not wait for is held until the
        read-back that covers it, so a rate is flops over the wall time
        of the calls that retired them, never over a bare dispatch."""
        self._unwaited_sigs += tuple(sigs)
        self._unwaited_s += dur
        if not waited:
            return
        sigs, dur = self._unwaited_sigs, self._unwaited_s
        self._unwaited_sigs, self._unwaited_s = (), 0.0
        self._busy_s += dur
        flops = 0.0
        for sig in sigs:
            cost = self.bucket_costs.get(sig)
            if cost is not None:
                flops += cost.total_flops
        if flops > 0:
            self._flops_done += flops
            if dur > 0:
                self._flops_rate_peak = max(self._flops_rate_peak,
                                            flops / dur)

    def health(self) -> Dict[str, object]:
        """Structured live health (the ``/healthz`` payload and the
        fleet router's load signal): slot occupancy, queue depth, page
        utilization, free slots, recompile count, and the SLO monitor's
        burn/alert state when one is configured. Safe (and cheap) to
        call from any thread WHILE ``step()`` runs: it returns the
        engine's last published snapshot under a lock rather than
        reading the scheduler's live queue/slot books mid-mutation."""
        with self._health_lock:
            return dict(self._health_snap)

    def start_exposition(self, port: int = 0, host: str = "127.0.0.1"):
        """Opt-in live exposition for THIS engine: starts a background
        :class:`~paddle_tpu.observability.ExpositionServer` over the
        engine's registry + tracer with the engine registered as the
        ``serving`` health provider. Port 0 (default) binds an
        ephemeral port — read ``server.port``. Caller stops it."""
        from paddle_tpu import observability as obs
        srv = obs.ExpositionServer(registry=self._reg,
                                   tracer=self.tracer,
                                   port=port, host=host)
        srv.add_health("serving", self.health)
        srv.add_postmortem("serving", self.flight.bundles)
        return srv.start()

    # -- engine loop ------------------------------------------------------

    def step(self) -> Dict[int, np.ndarray]:
        """One engine iteration: shed expired-deadline queue entries,
        admit into free slots, advance every admitted request's prefill
        under the interleaving budget, **dispatch** the next decode block
        for every slot with budget left, **settle** the block before it
        (the step's one read-back), evict finished sequences. Returns
        ``{rid: generated tokens}`` for requests the host learned to be
        finished now.

        At most one decode block is in flight across calls
        (``self._pending``): block k is dispatched before block k-1 is
        read, so the device goes from k-1 straight into this step's
        prefill calls and block k while the host books, evicts, observes
        and assembles the next step beside it.

        - *Known at dispatch* (:meth:`_dispatch_block`): which slots
          advance and how many of the block's tokens each keeps (its
          budget net of what is in flight), ``cache.lengths`` advanced
          by that, the input tokens as device values (the block in
          flight's last column, a prefill call's first token, or a token
          the host has).
        - *Known at settle* (:meth:`_settle_block`): the tokens, so first
          tokens (TTFT), ``generated``, an ``eos_id`` hit, and who is
          finished. A request is therefore evicted, and its successor
          admitted, one block later than it was computed.
        - *Settled in the step that dispatched it* (the synchronous
          engine is the same two calls back to back): a speculative
          engine, a prefill or decode tier, ``snapshot_every_blocks``
          (``self._settles_at_once``). *Settled on entry*
          (:meth:`_settle_pending`): ``snapshot_slot``, ``release_slot``,
          ``restore_slot``, ``cancel_queued``, ``export_prefix_pages``,
          ``import_prefix_pages``, ``poll_handoffs``,
          ``poll_micro_snapshots`` and a spill's page read.

        A step with nothing to dispatch settles what is pending, so a
        loop to ``scheduler.idle()`` ends with every request returned.

        Every layer boundary inside is one ``tracer.phase`` (names and
        parents: PERF.md section 3): a profiler annotation, a ring span
        when the tracer is on, and the seconds credited to
        ``serving_step_part_seconds_total``. The same seconds, kept for
        this step alone, are its anatomy record's ``parts``; a step the
        slow-step rule flags is counted, and marked in a profiler's
        trace, after its phase has closed (:meth:`_note_slow_step`)."""
        finished: Dict[int, np.ndarray] = {}
        self._anat_steps += 1
        phase = self.tracer.phase
        sched = self._c_part["sched", "book"]
        for _, part in self._step_parts:
            part.in_step = 0.0
        self._step_calls = None
        self._step_width = 0
        self._step_carried.clear()
        n_admitted = 0
        traces0 = _recompile.trace_count()
        gc0 = _recompile.gc_seconds()
        with phase("serving.step", stamp=True,
                   step=self._anat_steps) as step_ph:
            self.anatomy.begin_step(self._anat_steps, t0=step_ph.start)
            away = step_ph.start - self._left_step_at
            if self._pending is not None:
                # what the caller did between two steps is no part of
                # the interval of the block in flight
                self._pending.t0 += away
                self._last_settle_end += away
            step_tokens = 0
            if isinstance(self.scheduler, SLOScheduler):
                with phase("serving.shed", sched):
                    self._shed_expired()
            budget = self.prefill_budget
            prefilled_any = False
            while True:  # admissions cascade as early-EOS slots free up
                # pages are reserved inside the admit callback, so each
                # can_admit check sees the pool net of earlier admissions
                # in the same call (no over-commit on a down-sized pool)
                with phase("serving.admit", sched):
                    admitted = self.scheduler.admit(on_admit=self._on_admit)
                n_admitted += len(admitted)
                done = self._prefill_round(
                    budget, allow_liveness=not prefilled_any)
                prefilled_any = prefilled_any or done > 0
                budget -= done
                finished.update(self._evict())
                if (not admitted and done == 0) or budget <= 0:
                    break

            dslots = self.scheduler.decode_slots()
            if self.tier == "prefill":
                # prefill-done slots PARK for handoff (the replica handle
                # drains them via poll_handoffs); only the handoff-fallback
                # slots explicitly flagged decode-in-place decode here
                dslots = [i for i in dslots if i in self._decode_in_place]
            # a slot whose budget the block in flight exhausts is not
            # dispatched again: its settle finishes it
            dslots = [i for i in dslots if self._budget_left(i) > 0]
            decoded = bool(dslots) or self._pending is not None
            if decoded:
                if dslots:
                    # occupancy/utilization of the batch the decode step
                    # actually runs with (recorded before eviction, which
                    # empties finished slots' lengths)
                    self._g_occupancy.set(
                        len(dslots) / self.scheduler.num_slots)
                    self._g_page_util.set(self.cache.utilization())
                if self.speculative:
                    step_tokens += self._speculative_round(dslots)
                else:
                    step_tokens += self._decode_round(dslots)
                self._c_steps.inc()
                finished.update(self._evict())
                if self.snapshot_every_blocks is not None:
                    self._take_micro_snapshots()
            # a slot that owes its first token decodes in this step, and
            # its block carries the debt to its settle
            assert not self._owed, "a first token outlived its step"

            # what the observability itself costs each step
            with phase("serving.observe", self._c_part["observe", "book"]):
                if decoded:
                    self.recompile_detector.check()
                if self.slo_monitor is not None:
                    self.slo_monitor.check()
                self._refresh_health()
                with self._health_lock:
                    snap = self._health_snap
                self.flight.note(snap)
        if prefilled_any or decoded:
            # the step's seconds and its anatomy record's wall are the
            # step phase's one clock-read pair
            wall = step_ph.end - step_ph.start
            self._c_step_seconds.inc(wall)
            traces = _recompile.trace_count() - traces0
            gc_s = _recompile.gc_seconds() - gc0
            if traces:
                self._c_step_traces.inc(traces)
            if gc_s:
                self._c_step_gc.inc(gc_s)
            calls = self._step_calls
            if calls:
                self._h_step_calls.observe(len(calls))
            # the step's own parts: what its phases credited, what is
            # left of its wall, and the caller's time before it where
            # the engine held work meanwhile (waiting on an empty queue
            # is no part of a step)
            parts = {name: part.in_step for name, part in self._step_parts}
            parts[OTHER_PART] = max(wall - sum(parts.values()), 0.0)
            parts[GAP_PART] = away if self._left_with_work else 0.0
            rec = self.anatomy.end_step(
                tokens=step_tokens, t1=step_ph.end, parts=parts,
                prefill_calls=calls, slow_detail=lambda: dict(
                    slots_live=len(dslots), width=self._step_width,
                    admitted=n_admitted, evicted=len(finished),
                    traces=traces, gc_s=round(gc_s, 9)))
            if rec.get("slow"):
                self._note_slow_step(rec)
        else:
            # an idle tick is not a serving step: recording it would
            # count queue-empty waiting as "host gap"
            self.anatomy.cancel_step()
        self._left_step_at = step_ph.end
        self._left_with_work = (self._pending is not None
                                or not self.scheduler.idle())
        return finished

    def _note_slow_step(self, rec: Dict[str, object]):
        """A step the slow-step rule flagged (its anatomy record says
        which part and by how much): counted under that part, and ONE
        zero-length annotation whose ``step`` is the ``serving.step``
        span's, so that a profiler's trace can be joined to the record.
        Steps that are not slow emit nothing."""
        steps, excess = self._c_slow[rec["slow_part"]]
        steps.inc()
        excess.inc(rec["excess_s"])
        with TraceAnnotation("serving.slow_step", step=rec["step"],
                             part=rec["slow_part"],
                             excess_us=int(rec["excess_s"] * 1e6)):
            pass

    def _budget_left(self, slot: int) -> int:
        """Tokens ``slot``'s request may still be dispatched for: its
        ``max_new_tokens`` net of what the host holds and of what the
        block in flight will hand it."""
        st = self.scheduler.slots[slot]
        return (st.request.max_new_tokens - len(st.generated)
                - self._flying(slot))

    def _flying(self, slot: int) -> int:
        """Tokens the block in flight will hand ``slot``'s request."""
        if self._pending is None:
            return 0
        return self._pending.promised(
            slot, self.scheduler.slots[slot].request.rid)

    def _shed_expired(self):
        """Deadline shedding: queued requests whose TTFT deadline passed
        leave with a structured reject."""
        for req in self.scheduler.shed_expired():
            rej = Reject("deadline_expired", req.lane,
                         self.scheduler.queue_depth(),
                         self.scheduler.est_ttft_s(), 0.001)
            self._rejects[req.rid] = rej
            while len(self._rejects) > self._results_cap:
                self._rejects.popitem(last=False)
            self._reg.counter("serving_rejected_total",
                              "requests load-shed instead of queued"
                              ).inc(reason=rej.reason)
            self._phase_acc.pop(req.rid, None)
            self._ext_trace.pop(req.rid, None)
            root = self._req_spans.pop(req.rid, None)
            if root is not None:
                root.add_event("shed", reason=rej.reason,
                               deadline_s=req.ttft_deadline_s)
                root.finish(status="shed")

    def _decode_width(self, dslots, n: int) -> int:
        """Gather width of a decode round: the pow2 page count that
        covers the longest live slot after ``n`` more tokens."""
        return self._pow2_width(self.cache.config.pages_for(
            int(self.cache.lengths[dslots].max()) + n))

    def _count_attention(self, span, dslots, keeps, n: int, w: int):
        """One decode round of ``n`` token steps at gather width ``w``,
        of which slot ``dslots[k]`` keeps ``keeps[k]`` tokens, from the
        tables and the lengths BEFORE the round: each kind of layer feeds
        its own series (``span``: the round's) and hands back its share
        of ``serving_decode_kv_bytes_total``: live, what token step j of
        a slot holding L tokens attends over (L + j + 1; the formula
        ``benchmark/flops.paged_decode_bytes`` applies from outside), and
        gathered, every slot of the batch, live or not, at the whole
        pages of the table its kernel is handed (a kernel that walks a
        slot's live pages itself lays out no width: for it the ratio says
        how wide the bucket is for what the slots hold)."""
        live = gathered = 0
        for kind in self._kinds:
            live_b, wide_b = kind.count_decode(
                span, self.cache.block_tables, self.cache.lengths, dslots,
                keeps, n, w)
            live += live_b
            gathered += wide_b
        self._c_kv_live.inc(live)
        self._c_kv_gathered.inc(n * self.scheduler.num_slots
                                * self.cache.config.page_size * gathered)

    def _note_step_stats(self, phase, counts):
        """Feed one call's device-side counts (``self._step_stats``
        order) to their counters, and the round's span its
        ``experts_touched`` / ``selected`` attributes."""
        for child, n in zip(self._c_step_stats, counts):
            child.inc(int(n))
        if phase.span is not None:
            got = dict(zip(self._step_stats, counts))
            attrs = [("experts_touched", "moe_experts_touched"),
                     ("selected", "attn_selected_tokens")]
            if "moe_routed_pairs" in got:   # a chip's share of the experts
                attrs.append(("pairs_held", "moe_assignments"))
            phase.span.set_attrs(**{attr: int(got[name])
                                    for attr, name in attrs if name in got})

    def _read_back(self, phase: str, unread, *handles):
        """The host waits for the device: ``handles`` and the program
        counts ``unread`` (``(the call's phase, handle)``, those of the
        calls queued before what is read) come over in one transfer (the
        copies start together), and the counts go to their counters.
        Inside ``step()`` nothing else reads a device value, and a step
        calls this once: to settle the decode block dispatched by the
        step before (by itself where the engine settles at once). More
        only where a finishing prompt's first token is needed at once
        (:meth:`_reads_first_token_at_once`)."""
        got, counts = jax.device_get((handles, [c for _, c in unread]))
        self._c_readbacks[phase].inc()
        for (call, _), c in zip(unread, counts):
            self._note_step_stats(call, c)
        return got

    def _book_first_token(self, st, tok: int, now: float):
        """The first generated token of a slot whose prompt is done,
        stamped when the host learned it: closes the admit -> first
        token half of the TTFT split."""
        req = st.request
        st.generated.append(tok)
        st.first_token_at = now
        acc = self._phase_acc.get(req.rid)
        if acc is not None:
            acc["prefill_done_s"] = now
        ttft = now - req.submitted_at
        self._h_ttft.observe(ttft)
        self._h_admit_to_first.observe(now - st.admitted_at)
        self._c_tokens.inc()
        self.scheduler.note_ttft(ttft)
        root = self._req_spans.get(req.rid)
        if root is not None:
            root.add_event("first_token", ttft_s=round(ttft, 6))

    def _decode_round(self, dslots) -> int:
        """One decode round: dispatch a block of ``decode_block`` tokens
        for ``dslots`` (:meth:`_dispatch_block`) and settle a block
        (:meth:`_settle_block`): the one dispatched by the round before,
        so that the new one stays in flight beside the host's work until
        the next round; or, where the engine settles at once
        (``self._settles_at_once``) and nothing is ever in flight, the
        new one itself. With no ``dslots`` it settles what is pending.
        Returns tokens kept."""
        w = self._decode_width(dslots, self.decode_block) if dslots else 0
        self._step_width = w
        with self.tracer.phase("serving.decode_round", width=w,
                               slots_live=len(dslots)) as rnd:
            if not dslots:
                return self._settle_pending()
            due = self._pending
            new = self._pending = self._dispatch_block(dslots, w, rnd)
            if self._settles_at_once:
                due, self._pending = new, None
            if due is None:         # the first block of a run: no wait
                self.anatomy.add_phase("decode", new.t0, new.t1)
                return 0
            return self._settle_block(due, since=new.t0)

    def _dispatch_block(self, dslots, w: int, rnd) -> _Block:
        """Queue one block of ``decode_block`` tokens for ``dslots`` at
        gather width ``w`` behind whatever the device holds, and return
        its record without waiting. Decided here, from what the host
        knows: how many of the block's tokens each slot keeps (its
        budget net of the block in flight, ``self._pending``), and
        ``cache.lengths`` advanced by that (the pages were reserved at
        admission). A slot's input token is a device value wherever the
        host has not read it: the last column of the block in flight, or
        lane j of a prefill call of this step (``self._owed``); both are
        coded as negative entries of the uploaded token vector and
        merged there by ``first_token_step``."""
        n = self.decode_block
        s_tot = self.scheduler.num_slots
        slots = self.scheduler.slots
        phase, part = self.tracer.phase, self._c_part
        prev = self._pending
        owed, self._owed = self._owed, []
        owing = {i for _, lanes in owed for _, i in lanes}
        with phase("serving.decode.assemble",
                   part["decode", "assemble"]) as asm:
            tokens = np.zeros((s_tot,), np.int32)
            active = np.zeros((s_tot,), np.int32)
            rows: Dict[int, tuple] = {}
            carried = []
            for i in dslots:
                st = slots[i]
                owes = i in owing
                rows[i] = (st.request.rid,
                           min(n, self._budget_left(i) - owes), owes)
                active[i] = 1
                if self._flying(i):
                    carried.append(i)
                elif not owes:
                    tokens[i] = st.generated[-1]
            # an entry says where its token is on the device: lane j of
            # the k-th call to merge, as -(1 + j) - k * S (a token id is
            # never negative), so the codes ride the tokens' upload. The
            # block in flight, where a slot goes on from it, is call 0
            # and a slot's lane there is the slot
            calls = ([(prev.out, [(i, i) for i in carried])]
                     if carried else []) + owed
            for k, (_, lanes) in enumerate(calls):
                for j, i in lanes:
                    tokens[i] = -(1 + j) - k * s_tot
            self._count_attention(
                rnd.span, dslots, np.asarray([rows[i][1] for i in dslots]),
                n, w)
            self._count_state(rnd.span, decoding=len(dslots), token_steps=n)
            groups = self._shared_groups(dslots)
            tok_dev = self._upload(tokens)
            for nxt, _ in calls:
                tok_dev = self.first_token_step(tok_dev, nxt)
            # copies: an upload may read its host array after it returns,
            # and these two change under a block in flight (the lengths
            # just below, a table when its slot is freed)
            args = (jnp.asarray(self.cache.block_tables[:, :w].copy()),
                    jnp.asarray(self.cache.lengths.copy()),
                    tok_dev, jnp.asarray(active)) + groups
            for i, (_, keep, _) in rows.items():
                self.cache.lengths[i] += keep
        with phase("serving.decode.dispatch",
                   part["decode", "dispatch"]) as disp:
            out, self.cache.pages = self.decode_step(
                self._step_params, self.cache.pages, *args)
        # the program's counts, and those of the step's prefill calls
        # queued before it, come over when this block is read
        counts, self._unread_counts = self._unread_counts, []
        if self._step_stats:
            out, own = out
            counts.append((rnd, own))
        self._c_decode_rounds.inc()
        if prev is not None:
            self._c_overlapped.inc()
        return _Block(out=out, started_from=tok_dev if owed else None,
                      counts=counts, rows=rows, width=w, rnd=rnd,
                      t0=asm.start, t1=disp.end)

    def _settle_block(self, blk: _Block, since: Optional[float] = None
                      ) -> int:
        """Read one dispatched block and book it; returns tokens kept
        (counted in ``serving_tokens_total`` here). ``since``: where the
        round's decode interval began, if with a dispatch before this.
        The read-back brings the block's tokens, the first tokens it
        started from and every count pending with it; first tokens are
        booked (TTFT stamped at ``sync.end``, when the host learned
        them), then each row's tokens up to what dispatch promised or an
        ``eos_id``. A row whose request left its slot since (it ended in
        the block before, while this one was in flight) is dropped:
        ``serving_decode_discarded_tokens_total``. The spans carry
        ``block``, the span id of the round that dispatched it."""
        phase, part = self.tracer.phase, self._c_part
        slots = self.scheduler.slots
        rnd = blk.rnd
        with phase("serving.decode.sync", part["decode", "sync"],
                   block=rnd.span_id) as sync:
            # (S, decode_block), and the tokens the block started from
            out, first = self._read_back("decode", blk.counts, blk.out,
                                         blk.started_from)
        # the block's wall time: uploads, dispatch and sync; where it
        # was dispatched before the last read-back, since that one (the
        # cadence at which a client is handed blocks, net of the
        # caller's own time between steps: ``step()`` shifts both)
        t0, t1 = max(blk.t0, self._last_settle_end), sync.end
        self._last_settle_end = t1
        self._h_decode_step.observe(t1 - t0)
        # the step's anatomy holds what the host spent on the round:
        # the new block's uploads and dispatch, then this wait
        self.anatomy.add_phase(
            "decode", sync.start if since is None else since, t1)
        self._note_busy((("decode", blk.width),), t1 - t0)
        with phase("serving.decode.book", part["decode", "book"],
                   block=rnd.span_id):
            tr_on = self.tracer.enabled
            kept = discarded = 0
            for i, (rid, keep, owes) in blk.rows.items():
                st = slots[i]
                if st is None or st.request.rid != rid:
                    discarded += keep
                    continue
                req = st.request
                if owes:
                    self._book_first_token(st, int(first[i]), t1)
                kept_i = 0
                for j in range(keep):
                    tok = int(out[i, j])
                    st.generated.append(tok)
                    kept_i += 1
                    if req.eos_id is not None and tok == req.eos_id:
                        break
                kept += kept_i
                discarded += keep - kept_i
                acc = self._phase_acc.get(rid)
                if acc is not None:
                    acc["decode_s"] += t1 - t0
                    acc["decode_blocks"] += 1
                if tr_on:
                    # lanes run in the same batched call, so the spans
                    # share the interval — a parallel track per
                    # request; ``call`` names the round that caused it
                    self.tracer.record_span(
                        "serving.decode_block", start=t0, end=t1,
                        parent=self._req_spans.get(rid),
                        slot=i, tokens=kept_i, call=rnd.span_id)
            if discarded:
                self._c_discarded.inc(discarded)
        self._c_tokens.inc(kept)
        return kept

    def _settle_pending(self) -> int:
        """Settle the block in flight, if any, with no dispatch before
        it: a step with nothing to dispatch, and the entry of every call
        that reads or moves a slot's tokens or pages between two steps.
        A request this finishes outside ``step()`` stays in its slot for
        the next ``step()`` to evict and return. Returns tokens kept."""
        blk, self._pending = self._pending, None
        return self._settle_block(blk) if blk is not None else 0

    def _speculative_round(self, dslots) -> int:
        """One speculative decode round (ISSUE 13): the draft model
        proposes ``spec_k`` greedy tokens per slot on its own paged
        cache, the target verifies the whole chunk ``[pending, d_1 ..
        d_{k-1}]`` in ONE fixed-shape batched-prefill-shaped step
        (per-position greedy argmax), and each slot accepts the longest
        prefix of draft tokens the target agrees with PLUS the target's
        own next token — so every accepted token is exactly what
        non-speculative greedy decoding would have produced (the
        bit-exactness gate), and each round yields 1..spec_k tokens.

        Rollback is a host-side cursor rewind: both caches advance
        their write cursors by only the accepted inputs; rejected
        tokens' K/V stay behind the slot length (masked as dead by the
        ragged kernels, overwritten by the next round) and their pages
        were part of the slot's up-front all-or-nothing reservation, so
        nothing leaks. Returns tokens kept."""
        n = self.spec_k
        s_tot = self.scheduler.num_slots
        w = self._step_width = self._decode_width(dslots, n)
        phase, part = self.tracer.phase, self._c_part
        with phase("serving.decode_round", width=w,
                   slots_live=len(dslots)) as rnd:
            with phase("serving.decode.assemble",
                       part["decode", "assemble"]) as asm:
                tokens = np.zeros((s_tot,), np.int32)
                active = np.zeros((s_tot,), np.int32)
                nv = np.zeros((s_tot,), np.int32)
                for i in dslots:
                    st = self.scheduler.slots[i]
                    tokens[i] = st.generated[-1]
                    active[i] = 1
                    # never write past the slot's reservation: the chunk
                    # is capped at the remaining generation budget
                    nv[i] = min(n, st.request.max_new_tokens
                                - len(st.generated))
                self._count_attention(rnd.span, dslots, nv[dslots], n, w)
                nv_dev = jnp.asarray(nv)
                tok_dev = jnp.asarray(tokens)
                draft_args = (
                    jnp.asarray(self.draft_cache.block_tables[:, :w]),
                    jnp.asarray(self.draft_cache.lengths), tok_dev,
                    jnp.asarray(active), nv_dev)
                bt_dev = jnp.asarray(self.cache.block_tables[:, :w])
                len_dev = jnp.asarray(self.cache.lengths)
            with phase("serving.decode.dispatch",
                       part["decode", "dispatch"]):
                props_dev, self.draft_cache.pages = self.draft_propose_step(
                    self.draft_params, self.draft_cache.pages, *draft_args)
                # verify dispatches on the UN-materialized proposals (the
                # chunk is assembled inside the jitted step), so the
                # draft->verify chain never blocks on a host round-trip;
                # the props transfer below overlaps the verify compute
                ver, self.cache.pages = self.verify_step(
                    self._step_params, self.cache.pages, bt_dev, len_dev,
                    tok_dev, props_dev, nv_dev)
            with phase("serving.decode.sync", part["decode", "sync"]) as sync:
                props = np.asarray(props_dev)      # (S, spec_k) proposals
                # the props transfer completes when the draft chain has;
                # the clock read between the two materializations splits
                # the round into draft/verify anatomy without changing
                # dispatch overlap
                t_mid = self.tracer.now()
                ver = np.asarray(ver)              # (S, spec_k) target greedy
                self._c_readbacks["decode"].inc(2)
            t0, t1 = asm.start, sync.end
            self._h_decode_step.observe(t1 - t0)
            self.anatomy.add_phase("draft", t0, t_mid)
            self.anatomy.add_phase("verify", t_mid, t1)
            self._note_busy((("draft", w), ("verify", w)), t1 - t0)
            self._c_decode_rounds.inc()
            with phase("serving.decode.book", part["decode", "book"]):
                kept = self._book_speculative(dslots, props, ver, nv,
                                              t0, t1, rnd.span_id)
        self._c_tokens.inc(kept)
        return kept

    def _book_speculative(self, dslots, props, ver, nv, t0, t1,
                          call: int) -> int:
        """The accept loop of one speculative round, per slot."""
        tr_on = self.tracer.enabled
        kept = 0
        for i in dslots:
            st = self.scheduler.slots[i]
            req = st.request
            c = int(nv[i])
            # accept: t_1, plus t_{j+1} for every draft token d_j the
            # target reproduced — the canonical greedy accept-prefix
            a = 1
            while a < c and props[i, a - 1] == ver[i, a - 1]:
                a += 1
            kept_i = 0
            for j in range(a):
                tok = int(ver[i, j])
                st.generated.append(tok)
                kept_i += 1
                if req.eos_id is not None and tok == req.eos_id:
                    break
            kept += kept_i
            if not st.finished():
                # commit exactly the accepted inputs on BOTH caches;
                # the rejected tail is rewound by simply not advancing
                self.cache.lengths[i] += a
                self.draft_cache.lengths[i] += a
            proposed, accepted = max(c - 1, 0), a - 1
            self._reg.counter(
                "serving_spec_proposed_total",
                "draft tokens proposed for verification").inc(proposed)
            self._reg.counter(
                "serving_spec_accepted_total",
                "draft tokens the target verified and kept").inc(accepted)
            if proposed:
                self._reg.histogram(
                    "serving_spec_accept_rate",
                    "accepted/proposed draft tokens per verify round",
                    buckets=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75,
                             0.875, 1.0)).observe(accepted / proposed)
            acc = self._phase_acc.get(req.rid)
            if acc is not None:
                acc["decode_s"] += t1 - t0
                acc["decode_blocks"] += 1
                acc["spec_proposed"] += proposed
                acc["spec_accepted"] += accepted
            if tr_on:
                self.tracer.record_span(
                    "serving.verify_block", start=t0, end=t1,
                    parent=self._req_spans.get(req.rid), slot=i,
                    tokens=kept_i, proposed=proposed, accepted=accepted,
                    call=call)
        return kept

    def generate_many(self, prompts: Sequence, max_new_tokens: int = 32,
                      eos_id: Optional[int] = None,
                      max_steps: Optional[int] = None) -> List[np.ndarray]:
        """Submit ``prompts`` and run the loop until all finish; returns
        each request's generated tokens in submission order."""
        rids = [self.submit(p, max_new_tokens, eos_id) for p in prompts]
        collected: Dict[int, np.ndarray] = {}
        steps = 0
        while not self.scheduler.idle():
            collected.update(self.step())
            steps += 1
            if max_steps is not None and steps > max_steps:
                raise RuntimeError(f"no convergence in {max_steps} steps")
        for r in rids:          # consumed here; drop from the store
            self._results.pop(r, None)
        return [collected[r] for r in rids]

    def _evict(self) -> Dict[int, np.ndarray]:
        with self.tracer.phase("serving.evict", self._c_part["sched", "book"]):
            return self._evict_finished()

    def _evict_finished(self) -> Dict[int, np.ndarray]:
        out = {}
        for slot, st in self.scheduler.evict_finished().items():
            self.cache.free_slot(slot)
            self._decode_in_place.discard(slot)
            if self.speculative:
                self.draft_cache.free_slot(slot)
            toks = np.asarray(st.generated, np.int32)
            req = st.request
            self._results[req.rid] = toks
            acc = self._phase_acc.pop(req.rid, None) or {}
            root = self._req_spans.pop(req.rid, None)
            # per-phase breakdown: the wall split (queue wait, admit →
            # first token, total) from the lifecycle timestamps plus the
            # compute split (prefill/decode seconds + chunk/block/share
            # counts) whose numbers ARE the request's trace spans —
            # identical values to summing its serving.prefill_chunk /
            # serving.decode_block children
            self._stats[req.rid] = {
                "ttft_s": st.first_token_at - req.submitted_at,
                "queue_wait_s": st.admitted_at - req.submitted_at,
                "prefill_s": st.first_token_at - st.admitted_at,
                "prefill_compute_s": acc.get("prefill_s", 0.0),
                "decode_s": acc.get("decode_s", 0.0),
                "prefill_chunks": acc.get("prefill_chunks", 0.0),
                "decode_blocks": acc.get("decode_blocks", 0.0),
                "shared_tokens": acc.get("shared_tokens", 0.0),
                "spec_proposed": acc.get("spec_proposed", 0.0),
                "spec_accepted": acc.get("spec_accepted", 0.0),
                "tokens": float(len(st.generated)),
                # handoff timestamps (ISSUE 19): monotonic stamps that
                # attribute the TTFT split's transfer time honestly —
                # 0.0 on requests that never crossed a tier boundary
                "prefill_done_s": acc.get("prefill_done_s", 0.0),
                "handoff_s": acc.get("handoff_s", 0.0),
                "decode_start_s": acc.get("decode_start_s", 0.0),
                "trace_id": float(root.trace_id) if root is not None
                else float(self._ext_trace.pop(req.rid, 0)),
            }
            self._ext_trace.pop(req.rid, None)
            self._micro_snaps.pop(req.rid, None)
            self._last_snap_blocks.pop(req.rid, None)
            if root is not None:
                root.add_event("finished", tokens=len(st.generated))
                root.set_attrs(
                    tokens=len(st.generated),
                    shared_tokens=int(acc.get("shared_tokens", 0)))
                root.finish()
            out[req.rid] = toks
        while len(self._results) > self._results_cap:
            self._results.popitem(last=False)   # oldest unconsumed
        while len(self._stats) > self._results_cap:
            self._stats.popitem(last=False)
        return out

    # -- prefill ----------------------------------------------------------

    def _spill_read(self, pid: int):
        """Cache spill callback: read one page to host through the
        warmed ``("page_read",)`` signature. Returns the host arrays
        the spill pool stores — ``(kv,)`` or ``(kv, scales)`` when
        quantized, so int8 scale rows always travel with their page.
        The host waits for the page, so the block in flight is settled
        first: the step's wait stays the block's."""
        self._settle_pending()
        self._c_readbacks["page_read"].inc()
        return self._read_page(pid)

    def _spill_rotted(self, ent) -> bool:
        """Whether a host-spilled page no longer matches its sha256: a
        rotted copy must neither be restored nor leave this replica, so it
        is dropped (the advertisement goes stale too) and counted."""
        if payload_digest(ent.payload) == ent.sha256:
            return False
        self.cache.spill_pool.pop(ent.key)
        self._reg.counter("serving_spill_corrupt_total",
                          "host-spilled pages refused on restore "
                          "(sha256 mismatch)").inc()
        return True

    def _restore_spilled(self, prompt, rid: int) -> int:
        """Admission-overlapped restore (the DeviceEmbeddingCache
        ``pull_async`` pattern): before reserving pages for ``prompt``,
        pull any host-spilled pages of its published chain back to the
        device so ``reserve`` maps them as ordinary shared-prefix hits.
        All ``device_put`` transfers start first (async, overlapping
        each other and this thread's bookkeeping), then each page is
        adopted + written through the warmed ``("page_write",)``
        signature — zero compiles, zero new shapes. A payload whose
        sha256 no longer matches is dropped and the chain walk stops
        there: a corrupt page must cause a re-prefill, never a
        corrupt hit."""
        pool = self.cache.spill_pool
        if pool is None:
            return 0
        plan = self.cache.spill_restore_plan(prompt)
        if not plan:
            return 0
        entries, devs = [], []
        for ent in plan:
            if self._spill_rotted(ent):
                break
            entries.append(ent)
            devs.append(tuple(jax.device_put(a) for a in ent.payload))
        nbytes = 0
        for ent, dv in zip(entries, devs):
            pid = self.cache.adopt_published_page(ent.key, ent.tokens)
            self.cache.pages = self.write_page_step(
                self.cache.pages, jnp.asarray(pid, jnp.int32), *dv)
            nbytes += ent.nbytes
        if entries:
            pool.note_restored(len(entries), nbytes)
            self._reg.counter(
                "serving_spill_restored_pages_total",
                "host-spilled pages restored to HBM on a prefix hit"
            ).inc(len(entries))
            self._reg.counter(
                "serving_spill_restored_bytes_total",
                "bytes restored from the host spill pool"
            ).inc(nbytes)
            root = self._req_spans.get(rid)
            if root is not None:
                root.add_event("spill_restored", pages=len(entries),
                               bytes=nbytes)
        return len(entries)

    def _on_admit(self, slot: int, req):
        """Admission callback: reserve pages (mapping any published
        shared prefix), seed the slot's prefill cursor past the shared
        tokens, and record the queue-wait half of the TTFT split."""
        self._restore_spilled(req.prompt, req.rid)
        shared = self.cache.reserve(slot, req.total_tokens,
                                    prompt=req.prompt)
        if self.speculative:
            # lockstep reservation: same geometry + same alloc/free
            # history as the target cache, so this cannot overflow when
            # the target reserve succeeded (sharing is off — the draft
            # prefills the whole prompt, so nothing is skipped)
            self.draft_cache.reserve(slot, req.total_tokens)
        st = self.scheduler.slots[slot]
        st.prefilled = shared
        # (a request new to its slot is one no call of this step carried)
        self._step_carried.discard(slot)
        if shared:
            self._c_prefix_shared.inc(shared)
        self._reg.histogram(
            "serving_queue_wait_seconds",
            "submit -> slot admission wait",
            buckets=_LATENCY_BUCKETS).observe(
                max(st.admitted_at - req.submitted_at, 0.0))
        acc = self._phase_acc.get(req.rid)
        if acc is not None:
            acc["shared_tokens"] = float(shared)
        root = self._req_spans.get(req.rid)
        if root is not None:
            root.add_event("admitted", slot=slot, queue_wait_s=round(
                max(st.admitted_at - req.submitted_at, 0.0), 6))
            if shared:
                root.add_event("prefix_shared", tokens=shared)

    def _prefill_round(self, budget: int,
                       allow_liveness: bool = True) -> int:
        """Advance in-prefill slots' next prompt chunks through the
        batched fixed-shape prefill step, spending at most ``budget``
        prompt tokens. Returns tokens computed. A slot whose prompt
        completes has its first generated token computed by the same
        call; the host learns it there only where the step needs it at
        once, else at the decode round's read-back (:meth:`_prefill_call`).

        A lane of a call is one chunk of one slot, and each batched call
        computes up to ``lanes x prefill_chunk`` tokens, so the lane
        count is capped by the budget left; when less than one chunk
        remains the round stops rather than overshoot — except the
        ``allow_liveness`` single-lane exception (used once per
        ``step()``), which keeps an admitted slot progressing even with
        ``prefill_budget < prefill_chunk``. Net per-step contract: at
        most ``max(prefill_budget, prefill_chunk)`` prompt tokens.

        When lanes must wait the slots nearest their first token go
        first (that closes TTFTs soonest, and each completion shrinks
        the set so no admitted slot waits forever), and each slot gives
        the call a **run** of consecutive chunks, a lane each
        (:meth:`_lanes_of`). Where the engine's run limit is 1 that is
        the call of one chunk a slot, and the round is the loop it
        always was.

        Where calls carry runs the budget is a ceiling, not a quota, and
        a FURTHER call of a step is made by two rules over what the step
        has issued and the call's own live lanes. While a slot decodes,
        a slot gives a step one run: a further call carries only slots
        no call of this step has carried (a burst of more short prompts
        than a call has lanes), and what a carried slot could still give
        leads the next step's call, where it rides with whoever was
        admitted meanwhile; such a call would read the stage's weights
        again for the same few prompts, between the first call and the
        decode block every decoding slot waits for (PERF.md section 6,
        PR 54: 46 ms for 8 lanes behind a call of 24 in the long-prompt
        cell, the gap between tokens 1.1% longer for 0.5% more tokens).
        Where no slot decodes nothing waits behind a call, and the
        budget is spent as it always was: a lone long prompt advances a
        budget a step, in calls of one run. And a further call is made
        only for at least ``_second_call_lanes`` live lanes, the
        break-even under which it is mostly a second read of the
        weights; fewer wait for the next step."""
        consumed = 0
        c = self.prefill_chunk
        runs = self._run_limit > 1
        with self.tracer.phase("serving.prefill_round"):
            while budget - consumed > 0:
                further = runs and bool(self._step_carried)
                pslots = [i for i in self.scheduler.active_slots()
                          if not self.scheduler.slots[i].prefill_done]
                if further and self.scheduler.decode_slots():
                    pslots = [i for i in pslots
                              if i not in self._step_carried]
                if not pslots:
                    break
                lane_cap = min((budget - consumed) // c, self._lane_cap)
                if lane_cap == 0:
                    if consumed > 0 or not allow_liveness:
                        break
                    lane_cap = 1    # the once-per-step liveness lane
                lanes = self._lanes_of(pslots, lane_cap)
                if further and len(lanes) < self._second_call_lanes:
                    break
                consumed += self._prefill_call(lanes)
                if runs:
                    self._step_carried.update(lane[0] for lane in lanes)
        return consumed

    def _lanes_of(self, pslots, lane_cap: int) -> List[tuple]:
        """The lanes of one prefill call over ``pslots``, at most
        ``lane_cap``: ``(slot, start, tokens)`` each, a slot's run of
        consecutive chunks as consecutive lanes. When lanes must wait
        (the slots want more than the call has) the slots nearest their
        first token go first, else the scheduler's order stands; the
        lanes go round the slots, a chunk a slot a round, until the call
        is full or no slot's run can grow: each slot gets of one call
        what the calls of one chunk a slot gave it of a step, so the
        prompts in prefill advance side by side as they did (the order
        decides who decodes when, and with it every request's gap
        between tokens: PERF.md section 6, PR 54). A run is at most the
        engine's run limit, which at 1 makes this the call of one chunk
        a slot; a prompt's last chunk may be partial and a run may end
        in it; a slot that still owes the copy of a borrowed tail page
        gives one chunk (its run would start in the page the call
        copies first)."""
        slots = self.scheduler.slots
        c = self.prefill_chunk

        def left(i):
            return int(slots[i].request.prompt.shape[0]) - slots[i].prefilled
        most = {i: min(-(-left(i) // c),
                       1 if self.cache.pending_copy(i) is not None
                       else self._run_limit) for i in pslots}
        if sum(most.values()) > lane_cap:
            pslots = sorted(pslots, key=left)[:lane_cap]
        runs, free = dict.fromkeys(pslots, 0), lane_cap
        while free and any(runs[i] < most[i] for i in pslots):
            for i in pslots:
                if free and runs[i] < most[i]:
                    runs[i] += 1
                    free -= 1
        return [(i, slots[i].prefilled + k * c, min(c, left(i) - k * c))
                for i in pslots for k in range(runs[i])]

    def _reads_first_token_at_once(self, st) -> bool:
        """Whether the step must know a finishing prompt's first token
        before its decode round: the admission cascade evicts on it (an
        ``eos_id``, a budget of one token), a speculative round reads
        it on the host, and a prefill tier parks the slot for handoff
        instead of decoding it. Facts of the request and of how the
        engine was built; everything else waits for the block."""
        req = st.request
        return (req.eos_id is not None or req.max_new_tokens == 1
                or self.speculative or self.tier != "colocated")

    def _prefill_call(self, lanes) -> int:
        """One batched fixed-shape prefill call over ``lanes``, ``(slot,
        start, tokens)`` each, a slot's consecutive chunks as consecutive
        lanes (:meth:`_lanes_of`); returns the prompt tokens it computed.
        Every lane's rows of a layer are written before the layer
        attends, so a lane finds the rows of its slot's earlier lanes
        like any cached before. The host waits for the call only where a
        prompt ends in it whose first token the step needs at once
        (:meth:`_reads_first_token_at_once`); otherwise the tokens stay
        on the device, a finished prompt's as ``self._owed`` for the
        decode round of this step: the token of the lane whose chunk
        ends the prompt."""
        c = self.prefill_chunk
        cfgc = self.cache.config
        slots = self.scheduler.slots
        # compact batch: bucketed over the lanes actually live (a lone
        # late admission does not pay for num_slots lanes of attention);
        # padding lanes are inert (n_valid 0, null-page block tables)
        sb = self._pow2_count(len(lanes))
        pslots, los, ns = (list(col) for col in zip(*lanes))
        # which lanes open their slot's run, and the runs' lengths
        heads = np.asarray([j == 0 or i != pslots[j - 1]
                            for j, i in enumerate(pslots)])
        runs = np.diff(np.append(np.flatnonzero(heads), len(lanes)))
        call_tokens = sum(ns)
        w = self._pow2_width(max(cfgc.pages_for(lo + n)
                                 for lo, n in zip(los, ns)))
        # lanes whose prompt ends in this chunk: the only ones whose
        # token anybody reads
        ends = [(j, i) for j, (i, lo, n) in enumerate(zip(pslots, los, ns))
                if lo + n >= int(slots[i].request.prompt.shape[0])]
        wait = any(self._reads_first_token_at_once(slots[i])
                   for _, i in ends)
        phase, part = self.tracer.phase, self._c_part
        with phase("serving.prefill_call", lanes=sb, width=w,
                   tokens=call_tokens, lanes_live=len(lanes),
                   slots=len(runs)) as call:
            pend = [(i, pc) for i in dict.fromkeys(pslots)
                    if (pc := self.cache.pending_copy(i)) is not None]
            if pend:
                with phase("serving.prefill.cow_copy",
                           part["prefill", "cow_copy"]):
                    for i, (src, dst) in pend:
                        # copy-on-write of a borrowed tail page, owed
                        # before this slot's first write lands in it
                        self.cache.pages = self.copy_page_step(
                            self.cache.pages, jnp.asarray(src, jnp.int32),
                            jnp.asarray(dst, jnp.int32))
                        self.cache.copy_done(i)
                        self._c_cow.inc()
                        root = self._req_spans.get(slots[i].request.rid)
                        if root is not None:
                            root.add_event("cow_copy", src_page=int(src),
                                           dst_page=int(dst))
            with phase("serving.prefill.assemble",
                       part["prefill", "assemble"]) as asm:
                tokens = np.zeros((sb, c), np.int32)
                starts = np.zeros((sb,), np.int32)
                nv = np.zeros((sb,), np.int32)
                bt_rows = np.zeros((sb, cfgc.max_pages_per_slot), np.int32)
                dbt_rows = np.zeros_like(bt_rows) if self.speculative \
                    else None
                for j, (i, lo, n) in enumerate(zip(pslots, los, ns)):
                    prompt = slots[i].request.prompt
                    # borrower write isolation: the page this chunk starts
                    # writing into must be slot-owned (a shared tail page
                    # must have been CoW-resolved above, never written)
                    assert self.cache.writable(i, lo // cfgc.page_size), \
                        f"slot {i} would write a borrowed page"
                    tokens[j, :n] = prompt[lo:lo + n]
                    starts[j] = lo
                    nv[j] = n
                    bt_rows[j] = self.cache.block_tables[i]
                    if self.speculative:
                        dbt_rows[j] = self.draft_cache.block_tables[i]
                args = (jnp.asarray(starts), jnp.asarray(tokens),
                        jnp.asarray(nv))
                bt_rows = bt_rows[:, :w]
                if self._lane_slot_column:
                    # a lane says whose state or ring it holds, pool row
                    # slot + 1, in one more column of its table (a pad
                    # lane: row 0)
                    state_rows = np.zeros((sb, 1), np.int32)
                    state_rows[:len(pslots), 0] = np.asarray(pslots) + 1
                    bt_rows = np.concatenate([bt_rows, state_rows], axis=1)
                bt_dev = jnp.asarray(bt_rows)
                self._count_state(call.span, lanes=len(lanes),
                                  fresh=sum(lo == 0 for lo in los),
                                  tokens=call_tokens)
                for kind in self._kinds:
                    kind.count_prefill(call.span, starts[:len(lanes)],
                                       nv[:len(lanes)], heads)
                dbt_dev = jnp.asarray(dbt_rows[:, :w]) if self.speculative \
                    else None
            with phase("serving.prefill.dispatch",
                       part["prefill", "dispatch"]) as disp:
                nxt, self.cache.pages = self.prefill_step(
                    self._step_params, self.cache.pages, bt_dev, *args)
                if self.speculative:
                    # the draft cache ingests the SAME chunks so its pages
                    # mirror the target's committed prefix (its next-token
                    # output is discarded — proposals start at decode
                    # time)
                    _, self.draft_cache.pages = self.draft_prefill_step(
                        self.draft_params, self.draft_cache.pages, dbt_dev,
                        *args)
            if self._step_stats:     # counts ride the next read-back
                nxt, counts = nxt
                self._unread_counts.append((call, counts))
            if wait:
                with phase("serving.prefill.sync",
                           part["prefill", "sync"]) as sync:
                    unread, self._unread_counts = self._unread_counts, []
                    nxt, = self._read_back("prefill", unread, nxt)
                now = sync.end
            else:
                now = disp.end
                if ends:
                    self._owed.append((nxt, ends))
            # the call's wall time: uploads, dispatch and what the call
            # waited for, from the phases' own clock reads
            t0 = asm.start
            self._h_prefill_step.observe(now - t0)
            self.anatomy.add_phase("prefill", t0, now)
            self._note_busy((("prefill", w, sb),)
                            + ((("draft_prefill", w, sb),)
                               if self.speculative else ()), now - t0,
                            waited=wait)
            self._c_prefill_calls.inc()
            self._h_call_lanes.observe(len(lanes))
            self._c_lanes_live.inc(len(lanes))
            self._c_lanes_bucket.inc(sb)
            for run in runs:
                self._h_run_chunks.observe(int(run))
            if self._step_calls is None:
                self._step_calls = []
            self._step_calls.append((len(lanes), sb, w, call_tokens,
                                     now - t0, int(runs.max())))
            with phase("serving.prefill.book", part["prefill", "book"]):
                tr_on = self.tracer.enabled
                for j, (i, n) in enumerate(zip(pslots, ns)):
                    st = slots[i]
                    rid = st.request.rid
                    st.prefilled += n
                    self.cache.lengths[i] += n
                    if self.speculative:
                        self.draft_cache.lengths[i] += n
                    self.cache.publish_prefix(i, st.request.prompt,
                                              st.prefilled)
                    acc = self._phase_acc.get(rid)
                    if acc is not None:
                        if heads[j]:    # the call's wall once a slot
                            acc["prefill_s"] += now - t0
                        acc["prefill_chunks"] += 1
                    if tr_on:
                        self.tracer.record_span(
                            "serving.prefill_chunk", start=t0, end=now,
                            parent=self._req_spans.get(rid), slot=i,
                            tokens=n, start_pos=st.prefilled - n,
                            call=call.span_id)
                    if wait and st.prefill_done:
                        self._book_first_token(st, int(nxt[j]), now)
                self._c_prefill_tokens.inc(call_tokens)
        return call_tokens

    def _pow2_width(self, need: int) -> int:
        """Pow2 page count covering ``need`` pages — the gathers (and
        the Pallas grids) then scale with the LIVE high-water mark, not
        full slot capacity, while the set of compiled shapes stays
        log-sized; :meth:`warmup` precompiles them all. ONE width, the
        slot's whole table, where a layer's kind says so
        (``layer_kinds.Kind.whole_table``) or the program has state
        layers."""
        widest = self.cache.config.max_pages_per_slot
        if self._whole_table:
            return widest
        w = 1
        while w < need:
            w *= 2
        return min(w, widest)

    def _pow2_count(self, need: int) -> int:
        """The lane bucket of a prefill call with ``need`` live lanes: a
        power of two up to ``_LANE_STEP``, then the next multiple of it,
        and never more than the slots (the name is older than the
        multiples)."""
        s = 1
        while s < min(need, _LANE_STEP):
            s *= 2
        if need > s:
            s = -(-need // _LANE_STEP) * _LANE_STEP
        return min(s, self.scheduler.num_slots)

    def warmup_plan(self):
        """The signatures ``warmup()`` precompiles, in compile order:
        ``("decode", width)``, ``("prefill", width, lanes)``, and
        ``("copy_page",)`` — a speculative engine swaps the decode
        buckets for ``("draft", width)`` + ``("verify", width)`` and
        adds the draft's ``("draft_prefill", width, lanes)`` twins (the
        verify/draft buckets are part of the coverage proof like any
        other). Derived from the warmup-side doubling loops —
        :func:`~paddle_tpu.analysis.hlo_lint.serving_bucket_coverage`
        proves this plan covers :meth:`reachable_signatures`, turning
        the runtime zero-recompile invariant into an ahead-of-time
        proof."""
        c = self.cache.config

        def covering(cap, limit, step=None):
            # 1, 2, 4, .. (from ``step`` on in steps of it) up to the
            # first that covers ``cap``, none above ``limit``
            out, n = [], 1
            while n < cap:
                out.append(n)
                n = n * 2 if step is None or n < step else n + step
            return out + [min(n, limit)]
        widths = [c.max_pages_per_slot] if self._whole_table \
            else covering(c.max_pages_per_slot, c.max_pages_per_slot)
        # up to the lanes the budget buys a call: more are never asked for
        counts = covering(self._lane_cap, self.scheduler.num_slots,
                          _LANE_STEP)
        plan = []
        for w in widths:
            if self.speculative:
                plan.append(("draft", w))
                plan.append(("verify", w))
            else:
                plan.append(("decode", w))
            for sb in counts:
                plan.append(("prefill", w, sb))
                if self.speculative:
                    plan.append(("draft_prefill", w, sb))
        plan.append(("copy_page",))
        # a finishing call's tokens merged into the decode block's
        # input, and the last tokens of the block in flight
        plan += [("first_token", sb) for sb in counts]
        plan.append(("last_token",))
        # migration page IO: scalar-indexed, so one signature each
        # covers every page a fleet drain ever reads or writes
        plan.append(("page_read",))
        plan.append(("page_write",))
        return [sig for sig in plan if self._tier_sig(sig)]

    def _tier_sig(self, sig) -> bool:
        """Tier filter over bucket signatures (ISSUE 19): a prefill
        replica warms only prefill + page-IO buckets, a decode replica
        only decode + page-IO buckets — the per-tier half of the
        bucket-coverage proof (plan == reachable per tier). Page IO and
        the CoW copy stay on both tiers: handoff reads pages on the
        prefill side and writes them on the decode side."""
        if sig[0] in ("page_read", "page_write") \
                and "migration" not in self.program.spec.supports:
            return False        # pages never leave this engine
        if sig[0] == "first_token":
            # where a finishing call is read at once nothing is merged
            return self.tier == "colocated" and not self.speculative
        if sig[0] == "last_token":
            # where a block is settled at once none is gone on from
            return not self._settles_at_once
        if self.tier == "prefill" and sig[0] == "decode":
            return False
        if self.tier == "decode" and sig[0] == "prefill":
            return False
        return True

    def reachable_signatures(self):
        """Every bucket signature the steady-state ``step()`` loop can
        request, enumerated from the STEP-side bucketing functions
        (``_pow2_width`` over every possible live page count,
        ``_pow2_count`` over every count of live lanes a call can carry)
        — the other half of the bucket-coverage proof. A speculative engine's
        decode phase requests draft + verify buckets instead of decode
        buckets, plus the draft-prefill twins."""
        c = self.cache.config
        widths = {self._pow2_width(n)
                  for n in range(1, c.max_pages_per_slot + 1)}
        counts = {self._pow2_count(n)
                  for n in range(1, self._lane_cap + 1)}
        if self.speculative:
            sigs = {("draft", w) for w in widths}
            sigs |= {("verify", w) for w in widths}
            sigs |= {("draft_prefill", w, sb)
                     for w in widths for sb in counts}
        else:
            sigs = {("decode", w) for w in widths}
        sigs |= {("prefill", w, sb) for w in widths for sb in counts}
        sigs |= {("first_token", sb) for sb in counts}
        sigs.add(("last_token",))
        sigs.add(("copy_page",))
        sigs.add(("page_read",))
        sigs.add(("page_write",))
        return {sig for sig in sigs if self._tier_sig(sig)}

    def warmup(self, cost_gauges: bool = True):
        """Compile every decode AND prefill gather-width bucket plus the
        CoW page copy up front (all against the null page — no live
        state is touched), so a serving process takes its compiles at
        startup and the steady-state loop stays at ZERO recompiles.
        Records the compiled set in :attr:`warmed_signatures`.

        ``cost_gauges`` additionally lowers each bucket through the
        static cost model before its first call. That lowering pays the
        bucket's trace (seconds a signature, most of a warm-cache
        warm-up) and the first call then finds it in jit's cache, so the
        gauges move time from ``part="first_call"`` to
        ``part="cost_gauges"`` of ``serving_warmup_seconds_total`` and add
        little of their own: about 3 s of 67 on the chip's host (PERF.md
        section 6, PR 25). It publishes per-bucket flops / peak-HBM into
        ``serving_bucket_cost_flops`` /
        ``serving_bucket_cost_peak_hbm_bytes`` gauges (labels: phase,
        width, lanes), with the full reports kept in
        :attr:`bucket_costs` for budget audits."""
        s_tot = self.scheduler.num_slots
        zeros = jnp.zeros((s_tot,), jnp.int32)
        tok0 = self._upload(np.zeros((s_tot,), np.int32))
        self.warmed_signatures = set()
        self.bucket_costs = {}
        clock = self.tracer.now

        def ints(*shape):
            return jnp.zeros(shape, jnp.int32)

        def first_call(step, cache, params, *rest):
            args = (params, cache.pages) + rest
            if cost_gauges:
                self._bucket_cost_gauges(sig, step, args)
            _, cache.pages = step(*args)

        for sig in self.warmup_plan():
            t_sig, cost0 = clock(), self._c_warm_cost.value()
            w, sb = (sig + (None, None))[1:3]
            if sig[0] == "decode":
                # (the groups of a block in which no slot shares a page)
                first_call(self.decode_step, self.cache, self._step_params,
                           ints(s_tot, w), zeros, tok0, zeros,
                           *self._shared_groups([]))
            elif sig[0] == "draft":
                first_call(self.draft_propose_step, self.draft_cache,
                           self.draft_params, ints(s_tot, w), zeros, zeros,
                           zeros, zeros)
            elif sig[0] == "verify":
                first_call(self.verify_step, self.cache, self._step_params,
                           ints(s_tot, w), zeros, zeros,
                           ints(s_tot, self.spec_k), zeros)
            elif sig[0] == "prefill":
                first_call(self.prefill_step, self.cache, self._step_params,
                           ints(sb, w + self._lane_slot_column), ints(sb),
                           ints(sb, self.prefill_chunk), ints(sb))
            elif sig[0] == "draft_prefill":
                first_call(self.draft_prefill_step, self.draft_cache,
                           self.draft_params, ints(sb, w), ints(sb),
                           ints(sb, self.prefill_chunk), ints(sb))
            elif sig[0] == "first_token":
                self.first_token_step(
                    tok0, self._upload(np.zeros((sig[1],), np.int32)))
            elif sig[0] == "last_token":
                self.first_token_step(tok0, self._upload(
                    np.zeros((s_tot, self.decode_block), np.int32)))
            elif sig[0] == "page_read":
                jax.block_until_ready(self.read_page_step(
                    self.cache.pages, jnp.asarray(0, jnp.int32)))
            elif sig[0] == "page_write":
                # a blank page: what a page read hands back, in zeros
                null = jnp.asarray(0, jnp.int32)
                blank = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
                    lambda a: jnp.zeros(a.shape, a.dtype), jax.eval_shape(
                        self.read_page_step, self.cache.pages, null)))
                self.cache.pages = self.write_page_step(
                    self.cache.pages, null, *blank)
            else:
                self.cache.pages = self.copy_page_step(
                    self.cache.pages, jnp.asarray(0, jnp.int32),
                    jnp.asarray(0, jnp.int32))
            self.warmed_signatures.add(sig)
            self._c_warm_first.inc(clock() - t_sig - (
                self._c_warm_cost.value() - cost0))

    def _bucket_cost_gauges(self, sig, step_fn, args):
        """Static cost of one warmup bucket -> observability gauges
        (lower-only; donation must not consume the live cache pages, so
        the lowering runs on abstracted args). Its seconds go to
        ``serving_warmup_seconds_total{part="cost_gauges"}``."""
        from paddle_tpu.analysis import cost_model

        t0 = self.tracer.now()
        phase, width = sig[0], sig[1]
        lanes = sig[2] if len(sig) > 2 else self.scheduler.num_slots
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
        cost = cost_model.estimate_cost(
            step_fn, *abstract, name=f"{phase}_w{width}")
        self.bucket_costs[sig] = cost
        labels = dict(phase=phase, width=str(width), lanes=str(lanes))
        self._reg.gauge(
            "serving_bucket_cost_flops",
            "static flops per compiled bucket (cost model)").set(
                cost.total_flops, **labels)
        self._reg.gauge(
            "serving_bucket_cost_peak_hbm_bytes",
            "static peak-HBM estimate per compiled bucket").set(
                cost.peak_hbm_bytes, **labels)
        self._c_warm_cost.inc(self.tracer.now() - t0)

    # -- live migration (fleet drain) -------------------------------------

    def snapshot_slot(self, slot: int) -> Dict[str, object]:
        """Portable snapshot of one in-flight request: its full
        ``Request``/``SlotState`` bookkeeping plus the slot's live KV
        pages, each page carried as one sha256-digested shard (the
        resilience manifest discipline as a live-migration transfer
        format). The slot keeps running — snapshotting mutates nothing;
        pair with :meth:`release_slot` to actually drain it. A pending
        copy-on-write tail reads THROUGH to its source page (the dst
        has not been copied yet), so the snapshot always carries the
        logical KV content. An int8 cache's shards carry the pages'
        scale rows alongside the int8 KV — ONE shard, one hash over
        both, so a transfer can never split a page from its scales."""
        self._require("migration", "snapshot_slot")
        self._settle_pending()
        if self.speculative:
            raise SlotMigrationError(
                "speculative engines do not migrate slots (the draft "
                "cache state is not carried in a snapshot)")
        st = self.scheduler.slots[slot]
        if st is None:
            raise SlotMigrationError(f"slot {slot} is empty")
        req = st.request
        cfgc = self.cache.config
        length = int(self.cache.lengths[slot])
        n_live = cfgc.pages_for(length) if length else 0
        pids = [int(p) for p in self.cache.block_tables[slot, :n_live]]
        pc = self.cache.pending_copy(slot)
        if pc is not None:
            src, dst = pc
            pids = [src if p == dst else p for p in pids]
        shards, manifest = [], []
        for k, pid in enumerate(pids):
            cut_up = self._page_shards(k, self._read_page(pid))
            shards += cut_up[0]
            manifest += cut_up[1]
        root = self._req_spans.get(req.rid)
        trace_id = (root.trace_id if root is not None
                    else self._ext_trace.get(req.rid, 0))
        acc = self._phase_acc.get(req.rid) or {}
        return {
            "format": MIGRATION_FORMAT,
            "geometry": self._geometry(),
            "request": {"prompt": np.asarray(req.prompt, np.int32),
                        "max_new_tokens": req.max_new_tokens,
                        "eos_id": req.eos_id, "lane": req.lane,
                        "ttft_deadline_s": req.ttft_deadline_s,
                        "submitted_at": req.submitted_at},
            "state": {"generated": list(st.generated),
                      "prefilled": int(st.prefilled),
                      "length": length,
                      "admitted_at": st.admitted_at,
                      "first_token_at": st.first_token_at,
                      "phase_acc": dict(acc)},
            "trace_id": int(trace_id),
            "shards": shards,
            "manifest": manifest,
        }

    def _take_micro_snapshots(self):
        """Refresh the micro-checkpoint outbox: any in-flight decode
        slot that crossed another ``snapshot_every_blocks`` decode
        blocks gets a fresh :meth:`snapshot_slot` keyed by rid (newest
        wins — the outbox holds at most one snapshot per request)."""
        k = self.snapshot_every_blocks
        for i in self.scheduler.decode_slots():
            st = self.scheduler.slots[i]
            rid = st.request.rid
            acc = self._phase_acc.get(rid)
            blocks = int(acc["decode_blocks"]) if acc else 0
            if blocks and blocks % k == 0 \
                    and self._last_snap_blocks.get(rid) != blocks:
                snap = self._micro_snaps[rid] = self.snapshot_slot(i)
                self._last_snap_blocks[rid] = blocks
                self._c_readbacks["page_read"].inc(
                    len(snap["shards"]) // self.tp)   # one wait a page

    def poll_micro_snapshots(self) -> Dict[int, Dict]:
        """Drain the micro-checkpoint outbox (``{rid: snapshot}``,
        newest per request). The fleet replica handle forwards these to
        the router, which keeps the latest as the warm-restore seed
        bounding re-decode work after a crash."""
        self._settle_pending()
        out, self._micro_snaps = self._micro_snaps, {}
        return out

    def poll_handoffs(self) -> List:
        """Drain the prefill tier's handoff outbox (ISSUE 19): every
        PARKED prefill-done slot — prompt fully prefilled, first token
        emitted, not finished, not flagged decode-in-place — is
        :meth:`snapshot_slot`-ted (the exact migration transfer format:
        per-(page, tp-shard) sha256 shards) and released, freeing its
        slot for the next prompt immediately. Returns ``[(rid,
        snapshot), ...]``; the router streams each snapshot to a
        decode-tier replica's :meth:`restore_slot`. Empty on
        non-prefill tiers (and on an idle prefill tier)."""
        self._settle_pending()
        if self.tier != "prefill":
            return []
        out = []
        now = time.monotonic()
        for slot in list(self.scheduler.active_slots()):
            st = self.scheduler.slots[slot]
            if not st.prefill_done or st.finished() \
                    or slot in self._decode_in_place:
                continue
            rid = st.request.rid
            snap = self.snapshot_slot(slot)
            # the transfer-time half of the handoff timestamp split;
            # restore_slot stamps decode_start_s on the receiving tier
            snap["state"]["phase_acc"]["handoff_s"] = now
            self.release_slot(slot)
            out.append((rid, snap))
        self._refresh_health()
        return out

    def _geometry(self) -> Dict[str, object]:
        """What a snapshot or a prefix bundle must agree with this engine
        on: the shard layout IS part of the transfer format."""
        c = self.cache.config
        return {"num_layers": c.num_layers, "num_heads": c.num_heads,
                "head_dim": c.head_dim, "page_size": c.page_size,
                "dtype": str(jnp.dtype(c.dtype)), "tp": self.tp}

    def _read_page(self, pid: int) -> tuple:
        """Page ``pid`` on the host as the page read hands it over:
        ``(kv,)``, or ``(kv, scales)`` where the pages carry scale rows."""
        page = self.read_page_step(self.cache.pages,
                                   jnp.asarray(pid, jnp.int32))
        return tuple(np.asarray(a)
                     for a in (page if self.quantized else (page,)))

    def _page_shards(self, index: int, payload):
        """One page's host arrays as its transfer shards: one
        sha256-digested shard per (page, tp shard), the head axis of (2,
        L, ps, H, Dh) cut at mesh-shard boundaries, so each shard's KV
        travels and verifies independently (an int8 shard carries the
        replicated scale rows alongside: one hash over both). Returns
        (shards, their manifest entries)."""
        hl = self._tp_heads
        shards, manifest = [], []
        for t in range(self.tp):
            kv_t = payload[0][..., t * hl:(t + 1) * hl, :]
            shard = (kv_t, payload[1]) if self.quantized else kv_t
            shards.append(shard)
            manifest.append({"index": index, "tp_shard": t,
                             "sha256": self._shard_digest(shard),
                             "bytes": sum(int(a.nbytes) for a in (
                                 shard if self.quantized else (shard,)))})
        return shards, manifest

    def _install_page(self, dst: int, chunks):
        """Write page ``dst`` from its tp shards: head-axis chunks back in
        mesh-shard order (scale rows, in every shard, from the first)."""
        kv = np.concatenate([np.asarray(c[0] if self.quantized else c)
                             for c in chunks], axis=3)
        rest = (jnp.asarray(chunks[0][1]),) if self.quantized else ()
        self.cache.pages = self.write_page_step(
            self.cache.pages, jnp.asarray(dst, jnp.int32), jnp.asarray(kv),
            *rest)

    def _shard_digest(self, shard) -> str:
        """sha256 of one migration shard — a quantized shard hashes the
        int8 KV AND its scale rows as one digest (a scale-only
        corruption is as fatal as a KV corruption and must be refused
        the same way)."""
        return payload_digest(shard if self.quantized else (shard,))

    def cancel_queued(self) -> List[Request]:
        """Pop every queued (not yet admitted) request and close its
        engine-side bookkeeping — the open root span finishes with
        status ``requeued`` (the fleet drain path re-submits the
        request on a peer, which starts a fresh span on the same
        trace), and the phase/trace maps are cleaned so nothing leaks.
        Returns the popped :class:`~paddle_tpu.serving.Request`s in
        queue order."""
        self._settle_pending()
        out: List[Request] = []
        sched = self.scheduler
        while sched.queue:
            r = sched.queue.popleft()
            self._phase_acc.pop(r.rid, None)
            self._ext_trace.pop(r.rid, None)
            root = self._req_spans.pop(r.rid, None)
            if root is not None:
                root.add_event("requeued")
                root.finish(status="requeued")
            out.append(r)
        self._refresh_health()
        return out

    def release_slot(self, slot: int):
        """Drop a migrated-out slot WITHOUT recording a result: free
        its pages, close its trace span as ``migrated``, and return the
        popped :class:`~paddle_tpu.serving.SlotState` (the drain path's
        receipt). The request lives on wherever its snapshot was
        restored."""
        self._settle_pending()
        st = self.scheduler.slots[slot]
        if st is None:
            raise SlotMigrationError(f"slot {slot} is empty")
        self.scheduler.slots[slot] = None
        self.cache.free_slot(slot)
        self._decode_in_place.discard(slot)
        if self.speculative:
            self.draft_cache.free_slot(slot)
        rid = st.request.rid
        self._phase_acc.pop(rid, None)
        self._ext_trace.pop(rid, None)
        self._micro_snaps.pop(rid, None)
        self._last_snap_blocks.pop(rid, None)
        root = self._req_spans.pop(rid, None)
        if root is not None:
            root.add_event("migrated_out", slot=slot,
                           tokens=len(st.generated))
            root.finish(status="migrated")
        self.migrated_out_total += 1
        self._reg.counter("serving_migrated_out_total",
                          "in-flight requests migrated away").inc()
        self._refresh_health()
        return st

    def restore_slot(self, snap: Dict[str, object], *,
                     parent_span=None) -> int:
        """Restore a :meth:`snapshot_slot` snapshot into a free slot of
        THIS engine and resume it exactly where it left off: every
        shard is sha256-verified before any page lands (corrupt
        transfers are refused, never decoded), pages are reserved
        all-or-nothing (unshared — the restored slot owns and may write
        every page), and decode continues from the carried token
        stream, so greedy outputs are byte-identical to an unmigrated
        run. Returns the request's NEW rid on this engine. The restored
        root span adopts the snapshot's ``trace_id`` (under
        ``parent_span`` when given), keeping one timeline across the
        migration."""
        self._require("migration", "restore_slot")
        self._settle_pending()
        if self.speculative:
            raise SlotMigrationError(
                "speculative engines do not migrate slots (the draft "
                "cache state is not carried in a snapshot)")
        if snap.get("format") != MIGRATION_FORMAT:
            raise SlotMigrationError(
                f"unknown snapshot format {snap.get('format')!r}")
        cfgc = self.cache.config
        geo, mine = snap["geometry"], self._geometry()
        if geo != mine:
            # cross-tp restore is refused like any other geometry
            # mismatch: the shard layout IS part of the transfer format
            raise SlotMigrationError(
                f"cache geometry mismatch: snapshot {geo} != engine {mine}")
        shards, manifest = snap["shards"], snap["manifest"]
        if len(shards) != len(manifest):
            raise SlotMigrationError(
                f"{len(shards)} shards != {len(manifest)} manifest entries")
        for shard, rec in zip(shards, manifest):
            digest = self._shard_digest(shard)
            if digest != rec["sha256"]:
                raise SlotMigrationError(
                    f"shard {rec['index']} sha256 mismatch "
                    f"({digest[:12]}… != {rec['sha256'][:12]}…) — "
                    "refusing to restore a corrupt page")
        free = self.scheduler.free_slots()
        if not free:
            raise SlotMigrationError("no free slot to restore into")
        rq = snap["request"]
        prompt = np.asarray(rq["prompt"], np.int32).reshape(-1)
        total = int(prompt.shape[0]) + int(rq["max_new_tokens"])
        # shard count must agree with the carried live length AND fit
        # the reservation: an excess shard would index past the block
        # table's reserved entries (fill value 0) and overwrite the
        # null page other live requests gather from
        length = int(snap["state"]["length"])
        n_live = cfgc.pages_for(length) if length > 0 else 0
        tp_shards = self.tp
        if length < 0 or length > total or \
                len(shards) != n_live * tp_shards:
            raise SlotMigrationError(
                f"{len(shards)} shards for {length} live tokens "
                f"({tp_shards} per page) of a {total}-token "
                "reservation — snapshot state inconsistent, refusing "
                "to restore")
        if self.tier == "decode" and \
                int(snap["state"]["prefilled"]) < int(prompt.shape[0]):
            # a mid-prefill slot would run prefill buckets this tier
            # never warms; such snapshots restore on prefill/colocated
            # peers (which finish the prefill and hand off again)
            raise SlotMigrationError(
                "decode-tier engines restore only prefill-complete "
                f"slots ({int(snap['state']['prefilled'])} of "
                f"{int(prompt.shape[0])} prompt tokens prefilled)")
        if not self.cache.can_reserve(total):
            raise SlotMigrationError(
                f"no page capacity for {total} tokens")
        slot = free[0]
        # prompt=None: never map shared pages — the restore WRITES the
        # carried KV into every live page, so the slot must own them all
        self.cache.reserve(slot, total)
        stt = snap["state"]
        for k in range(n_live):
            # reassemble each page from its tp shards: hash-verified
            # head-axis chunks concatenated back in mesh-shard order
            self._install_page(int(self.cache.block_tables[slot, k]),
                               shards[k * tp_shards:(k + 1) * tp_shards])
        self.cache.lengths[slot] = int(stt["length"])
        rid = next(self.scheduler._ids)     # fresh local rid, no collision
        req = Request(rid, prompt, int(rq["max_new_tokens"]),
                      rq["eos_id"], submitted_at=rq["submitted_at"],
                      lane=rq["lane"],
                      ttft_deadline_s=rq["ttft_deadline_s"])
        st = SlotState(req, generated=list(stt["generated"]),
                       prefilled=int(stt["prefilled"]),
                       admitted_at=stt["admitted_at"],
                       first_token_at=stt["first_token_at"])
        self.scheduler.slots[slot] = st
        if snap.get("decode_in_place") and self.tier == "prefill":
            # handoff fallback (ISSUE 19): no decode-tier capacity, so
            # this prefill engine decodes the slot itself — the one
            # documented exception to the prefill tier's decode gate
            # (and to its zero-recompile steady state)
            self._decode_in_place.add(slot)
        acc = {"prefill_s": 0.0, "decode_s": 0.0, "prefill_chunks": 0.0,
               "decode_blocks": 0.0, "shared_tokens": 0.0}
        acc.update(stt.get("phase_acc") or {})
        if acc.get("handoff_s") and not acc.get("decode_start_s"):
            # the decode-side half of the handoff timestamp split
            acc["decode_start_s"] = time.monotonic()
        self._phase_acc[rid] = acc
        trace_id = int(snap.get("trace_id") or 0)
        if trace_id:
            self._ext_trace[rid] = trace_id
        if self.tracer.enabled:
            root = self.tracer.start_span(
                "serving.request", parent=parent_span,
                trace_id=trace_id or None, rid=rid, lane=req.lane,
                migrated=True, prompt_tokens=int(prompt.shape[0]),
                max_new_tokens=req.max_new_tokens)
            root.add_event("migrated_in", slot=slot,
                           tokens=len(st.generated),
                           kv_tokens=int(stt["length"]))
            self._req_spans[rid] = root
        self.migrated_in_total += 1
        self._reg.counter("serving_migrated_in_total",
                          "in-flight requests migrated in").inc()
        self._refresh_health()
        return rid

    # -- fleet-global prefix reuse (ISSUE 20) ------------------------------

    def export_prefix_pages(self, digests) -> Optional[Dict[str, object]]:
        """Package the leading run of ``digests`` this engine still
        holds — device-published OR host-spilled — as a prefix-page
        bundle a peer can :meth:`import_prefix_pages`. Each page ships
        its chain key, its token content, and per-(page, tp-shard)
        sha256 shards (the slot-migration layout, so int8 scale rows
        travel inside the shard hash). Stops at the first digest this
        cache no longer holds: later pages could not chain onto a
        missing parent on the importer anyway. Returns None when
        nothing is exportable — the router degrades to re-prefill."""
        self._require("prefix_export", "export_prefix_pages")
        self._settle_pending()
        if not self.cache.config.share_prefix:
            return None
        pages, total_bytes = [], 0
        for key in digests:
            key = int(key)
            hit = self.cache.lookup_prefix_page(key)
            if hit is None:
                break
            if hit[0] == "device":
                _, pid, tokens = hit
                payload = self._read_page(pid)
            else:
                ent = hit[1]
                if self._spill_rotted(ent):
                    break
                tokens, payload = ent.tokens, ent.payload
            shards, manifest = self._page_shards(len(pages), payload)
            total_bytes += sum(rec["bytes"] for rec in manifest)
            pages.append({"key": key,
                          "tokens": np.asarray(tokens, np.int32),
                          "shards": shards, "manifest": manifest})
        if not pages:
            return None
        self._reg.counter(
            "serving_prefix_exported_pages_total",
            "published prefix pages exported to fleet peers"
        ).inc(len(pages))
        return {
            "format": PREFIX_BUNDLE_FORMAT,
            "geometry": self._geometry(),
            "pages": pages,
            "bytes": int(total_bytes),
        }

    def import_prefix_pages(self, bundle) -> int:
        """Install a peer's :meth:`export_prefix_pages` bundle into the
        published-prefix index so the NEXT admission maps the pages as
        ordinary shared-prefix hits instead of re-prefilling. The whole
        bundle is verified before any page lands: format, cache
        geometry, the full publication hash chain from the root (each
        page's key must equal ``chain(parent, tokens)`` — a bundle
        claiming pages it cannot prove is refused), and every shard's
        sha256. Pages land all-or-nothing into idle free pages only
        (never evicting), through the warmed ``("page_write",)``
        signature. Returns pages installed (0 when everything was
        already held — not an error)."""
        self._require("prefix_export", "import_prefix_pages")
        self._settle_pending()
        if bundle is None or not self.cache.config.share_prefix:
            return 0
        if bundle.get("format") != PREFIX_BUNDLE_FORMAT:
            raise SlotMigrationError(
                f"unknown prefix bundle format {bundle.get('format')!r}")
        cfgc = self.cache.config
        tp_shards = self.tp
        mine = self._geometry()
        if bundle.get("geometry") != mine:
            raise SlotMigrationError(
                f"cache geometry mismatch: bundle "
                f"{bundle.get('geometry')} != engine {mine}")
        pages = bundle.get("pages") or []
        prev = _ROOT_KEY
        for page in pages:
            tokens = np.asarray(page["tokens"], np.int32).reshape(-1)
            if tokens.shape[0] != cfgc.page_size:
                raise SlotMigrationError(
                    f"prefix page carries {tokens.shape[0]} tokens "
                    f"(page_size {cfgc.page_size}) — refusing")
            key = int(page["key"])
            if _chain(prev, tokens) != key:
                raise SlotMigrationError(
                    "prefix bundle breaks the publication hash chain "
                    "— refusing to install unprovable pages")
            prev = key
            shards, manifest = page["shards"], page["manifest"]
            if len(shards) != tp_shards or len(manifest) != tp_shards:
                raise SlotMigrationError(
                    f"{len(shards)} shards for a {tp_shards}-shard "
                    "page — refusing")
            for shard, rec in zip(shards, manifest):
                digest = self._shard_digest(shard)
                if digest != rec["sha256"]:
                    raise SlotMigrationError(
                        f"prefix shard sha256 mismatch ({digest[:12]}… "
                        f"!= {rec['sha256'][:12]}…) — refusing to "
                        "install a corrupt page")
        held = self.cache.advertised_digests()
        install = [p for p in pages if int(p["key"]) not in held]
        if not install:
            return 0
        if len(install) > self.cache.idle_free_pages:
            # all-or-nothing, and never by eviction: installing a
            # remote prefix must not destroy local published pages
            raise SlotMigrationError(
                f"no idle page capacity for {len(install)} fetched "
                "prefix pages")
        nbytes = 0
        for page in install:
            pid = self.cache.adopt_published_page(
                int(page["key"]), page["tokens"])
            self._install_page(pid, page["shards"])
            nbytes += sum(int(r["bytes"]) for r in page["manifest"])
        self._reg.counter(
            "serving_prefix_fetched_pages_total",
            "prefix pages installed from fleet peers").inc(len(install))
        self._reg.counter(
            "serving_prefix_fetched_bytes_total",
            "bytes of prefix pages installed from fleet peers"
        ).inc(nbytes)
        self._refresh_health()
        return len(install)

    # -- jitted step bodies ----------------------------------------------

    @staticmethod
    def _stat_names(spec, kinds):
        """The counts a step of this program hands back beside the
        tokens: the program's own, then those of its layers' kinds."""
        return tuple(spec.stats) + tuple(dict.fromkeys(
            name for kind in kinds for name in kind.stat_names))

    @staticmethod
    def _step_stat_vector(spec, kind, ffn_stats, context, selected):
        """One layer-call's counts in :meth:`_stat_names` order."""
        vals = [ffn_stats[name] for name in spec.stats]
        vals += kind.step_counts(context, selected)
        return jnp.stack([jnp.asarray(v).astype(jnp.int32) for v in vals])

    @staticmethod
    def _attn_in(program, params, i, x, positions, state, rows, fresh,
                 valid):
        """Layer ``i``'s ``attn_in``: -> (q, rows to cache, index, the
        slot-state pools it advanced or None). A program whose attention
        projections own the slot state (``spec.slot_state_reader``)
        takes what ``mixer`` would and hands the pools back. A decode
        step gives ``fresh`` None (no lane's prompt starts there) and
        ``valid`` (S,), expanded here so that a program without such
        state traces to the step it had."""
        if program.spec.slot_state_reader == "attn_in":
            if fresh is None:
                fresh, valid = jnp.zeros_like(rows), valid[:, None]
            return program.attn_in(params, i, x, positions, state, rows,
                                   fresh, valid)
        return program.attn_in(params, i, x, positions) + (None,)

    @staticmethod
    def _ffn(program, params, i, x, valid, carry):
        """Layer ``i``'s ``ffn``: -> (x, stats, carry), the carry passed
        through the layer where the program declares one
        (``spec.layer_carry``)."""
        if program.spec.layer_carry:
            return program.ffn(params, i, x, valid, carry)
        return program.ffn(params, i, x, valid) + (carry,)

    @staticmethod
    def _carry_start(spec, lanes, tokens):
        """What a token carries into the first layer beside the residual
        stream: zeros, one array an entry of ``spec.layer_carry``."""
        return tuple(jnp.zeros((lanes, tokens, width), jnp.float32)
                     for _name, width in spec.layer_carry)

    def _decode_loop(self, params, pages, block_tables, lengths, tokens,
                     active, n_valid=None, groups=None, *, program=None,
                     kinds=(), n_steps=1):
        """The shared greedy token loop behind the decode step AND the
        draft-proposal step, written against what a model supplies
        (``program``, :mod:`paddle_tpu.serving.program`) and what its
        cache's layers are (``kinds``, one a layer,
        :mod:`paddle_tpu.serving.layer_kinds`): ``n_steps`` inner
        iterations, each entering every slot's current token at position
        ``lengths[s]``, landing the rows the program wants cached where
        the layer's kind places them, and attending as the kind does.
        ``n_valid`` (draft proposing) additionally masks writes of
        iterations ``j >= n_valid[s]`` to the null page — a chunk capped
        below ``n_steps`` must not write past the slot's reservation.
        ``groups`` (a kind whose decode folds shared pages): which
        decoding slots' tables open with the same pages, fixed for the
        block. The keyword-only args are static config (default-marked so
        the AST host-sync lint, which runs on THIS body via the graph_lint
        preset, seeds only the array args as tracers). Returns (tokens
        (S, n_steps), pages), or ((tokens, counts), pages) where the
        program counts (``self._step_stats`` order)."""
        spec = program.spec
        ps = self.cache.config.page_size
        s_tot = tokens.shape[0]
        slot_ids = jnp.arange(s_tot)
        n_stats = len(self._stat_names(spec, kinds))
        mixes = bool(spec.slot_state) and spec.slot_state_reader == "mixer"
        distinct = tuple(kind for kind in dict.fromkeys(kinds) if kind.paged)

        def one_token(j, pages, lengths, tokens):
            pos = jnp.minimum(lengths, spec.max_position - 1)
            with jax.named_scope("embed"):
                x = program.embed(params, tokens[:, None], pos[:, None])
            writable = active > 0
            if n_valid is not None:
                writable = writable & (j < n_valid)
            under = layer_kinds.under_table(
                block_tables, lengths, writable, slot_ids, ps, lengths)
            with jax.named_scope("stats"):
                seen = jnp.where(writable, lengths + 1, 0).sum()
            # a decoding slot's state is pool row slot + 1; any other
            # slot's (free, or mid-prefill and owning live state) is
            # not this block's to touch: the null row
            state_rows = jnp.where(writable, slot_ids + 1, 0) \
                if spec.slot_state else None
            places = {kind: kind.place_decode(under, writable, slot_ids)
                      for kind in distinct}
            new_pages, counts = [], 0
            carry = self._carry_start(spec, s_tot, 1)
            for i, kind in enumerate(kinds):
                n_paged = len(kind.pools)
                if kind.paged:
                    with jax.named_scope("attn_in"):
                        q, rows, index, state = self._attn_in(
                            program, params, i, x, pos[:, None],
                            pages[i][n_paged:], state_rows, None, writable)
                    with jax.named_scope("write_rows"):
                        ent = kind.write(pages[i][:n_paged],
                                         tuple(r[:, 0] for r in rows),
                                         places[kind])
                    with jax.named_scope("attend"):
                        att, attended = kind.attend_decode(
                            q[:, :, 0, :], ent, places[kind], index,
                            groups)                             # (S,H,Dh)
                    with jax.named_scope("attn_out"):
                        x_in, x = x, program.attn_out(params, i, x,
                                                      att[:, None])
                else:
                    # a state layer: no rows, no queries, no page; its
                    # mixer below is the block's token mixer
                    ent, x_in, attended = (), x, 0
                if mixes and kind.state:
                    with jax.named_scope("mixer"):
                        mixed, state = program.mixer(
                            params, i, x_in, pages[i][n_paged:], state_rows,
                            jnp.zeros_like(state_rows), writable[:, None])
                        x = x + mixed
                if spec.slot_state and kind.state:
                    ent = ent + tuple(state)
                new_pages.append(ent)
                with jax.named_scope("ffn"):
                    x, ffn_stats, carry = self._ffn(
                        program, params, i, x, writable[:, None], carry)
                if n_stats:
                    with jax.named_scope("stats"):
                        counts = counts + self._step_stat_vector(
                            spec, kind, ffn_stats, seen,
                            jnp.where(writable, attended, 0).sum())
            with jax.named_scope("head"):
                logits = program.head(params, x[:, 0])
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            return new_pages, nxt, counts

        out = jnp.zeros((s_tot, n_steps), jnp.int32)
        totals = jnp.zeros((n_stats,), jnp.int32) if n_stats else ()

        def body(j, carry):
            pages, lengths, tokens, out, totals = carry
            pages, nxt, counts = one_token(j, pages, lengths, tokens)
            if n_stats:
                totals = totals + counts
            return pages, lengths + 1, nxt, out.at[:, j].set(nxt), totals

        pages, _, _, out, totals = jax.lax.fori_loop(
            0, n_steps, body, (pages, lengths, tokens, out, totals))
        return ((out, totals) if n_stats else out), pages

    def _decode_step_impl(self, params, pages, block_tables, lengths,
                          tokens, active, groups=None):
        """Fixed-shape batched decode of ONE BLOCK of ``decode_block``
        tokens per slot — one host round-trip per block instead of per
        token. Non-decoding lanes (``active == 0``: free slots AND
        slots still mid-prefill, which own live pages the block must
        not corrupt) write to the null page; post-EOS/post-cap lanes
        write past their reservation into the null page and produce
        discarded garbage (the host keeps only in-budget, pre-EOS
        tokens). ``groups``: only a program whose decode folds shared
        pages is handed them (None there: every slot walked alone).
        Returns (tokens (S, decode_block), pages)."""
        return self._decode_loop(params, pages, block_tables, lengths,
                                 tokens, active, groups=groups,
                                 program=self.program,
                                 kinds=self.cache.config.kinds,
                                 n_steps=self.decode_block)

    def _draft_propose_step_impl(self, params, pages, block_tables,
                                 lengths, tokens, active, n_valid):
        """Fixed-shape draft proposal: ``spec_k`` greedy draft tokens
        per slot on the DRAFT cache (the first ``spec_k - 1`` become
        the verify chunk's candidates). Iterations at/after
        ``n_valid[s]`` write to the null page — their outputs are
        discarded lanes. Returns (proposals (S, spec_k), pages)."""
        return self._decode_loop(params, pages, block_tables, lengths,
                                 tokens, active, n_valid,
                                 program=self.draft_program,
                                 kinds=self.draft_cache.config.kinds,
                                 n_steps=self.spec_k)

    def _prefill_loop(self, params, pages, block_tables, starts, tokens,
                      n_valid, *, program=None, kinds=(),
                      all_positions=False):
        """The shared chunk-forward behind the batched prefill step, the
        draft prefill step, and the speculative VERIFY step, written
        against what a model supplies (``program``) and what its cache's
        layers are (``kinds``): ``tokens`` (S, C), a chunk a LANE, enter
        at absolute positions ``starts[s]..starts[s]+C-1`` (first
        ``n_valid[s]`` real, rest pad to the null page), the rows the
        program wants cached land where each layer's kind places them, and
        every live lane attends causally over everything cached, as the
        kind does. Consecutive lanes may be consecutive chunks of one slot
        (same table, the next start): a layer's rows of EVERY lane are
        written before the layer attends, so a lane finds its slot's
        earlier lanes' rows among what is cached.
        ``all_positions=False`` returns the greedy next token after each
        slot's LAST valid position (prefill's first generated token);
        ``all_positions=True`` returns the greedy argmax after EVERY
        chunk position (S, C) — the speculative verifier's per-candidate
        target tokens. Keyword-only args are static config (the AST
        host-sync lint runs on this body — see :meth:`_decode_loop`).
        Returns (tokens, pages), or ((tokens, counts), pages) where the
        program counts."""
        spec = program.spec
        ps = self.cache.config.page_size
        s_tot, c = tokens.shape
        mixes = bool(spec.slot_state) and spec.slot_state_reader == "mixer"
        distinct = tuple(kind for kind in dict.fromkeys(kinds) if kind.paged)
        state_rows = fresh = None
        if spec.slot_state or any(kind.by_slot for kind in distinct):
            # the lanes' slots (pool row slot + 1) ride the tables' last
            # column; a lane whose prompt starts here starts from zeros,
            # its slot's reset at admission
            state_rows, block_tables = block_tables[:, -1], \
                block_tables[:, :-1]
            fresh = (starts == 0).astype(jnp.int32)
        positions = starts[:, None] + jnp.arange(c, dtype=jnp.int32)
        pos_e = jnp.minimum(positions, spec.max_position - 1)
        with jax.named_scope("embed"):
            x = program.embed(params, tokens, pos_e)            # (S,C,D)
        valid = jnp.arange(c)[None, :] < n_valid[:, None]
        slot_ids = jnp.arange(s_tot)[:, None]
        under = layer_kinds.under_table(
            block_tables, positions, valid, slot_ids, ps, starts)
        counting = bool(self._stat_names(spec, kinds))
        with jax.named_scope("stats"):
            seen = positions + 1             # tokens a query can attend to
            attended = {kind: kind.attends_prefill(seen, block_tables)
                        for kind in distinct}
            seen = jnp.where(valid, seen, 0).sum()
            attended = {kind: jnp.where(valid, a, 0).sum()
                        for kind, a in attended.items()}
        places = {kind: kind.place_prefill(under, positions, valid,
                                           state_rows)
                  for kind in distinct}
        new_pages, counts = [], 0
        carry = self._carry_start(spec, s_tot, c)
        for i, kind in enumerate(kinds):
            n_paged = len(kind.pools)
            if kind.paged:
                with jax.named_scope("attn_in"):
                    q, rows, index, state = self._attn_in(
                        program, params, i, x, pos_e, pages[i][n_paged:],
                        state_rows, fresh, valid)
                with jax.named_scope("write_rows"):
                    ent = kind.write(pages[i][:n_paged], rows, places[kind])
                with jax.named_scope("attend"):
                    att = kind.attend_prefill(
                        q.transpose(0, 2, 1, 3), ent, places[kind], n_valid,
                        index)                                  # (S,C,H,Dh)
                with jax.named_scope("attn_out"):
                    x_in, x = x, program.attn_out(params, i, x, att)
            else:
                ent, x_in = (), x           # a state layer: its mixer alone
            if mixes and kind.state:
                with jax.named_scope("mixer"):
                    mixed, state = program.mixer(
                        params, i, x_in, pages[i][n_paged:], state_rows,
                        fresh, valid)
                    x = x + mixed
            if spec.slot_state and kind.state:
                ent = ent + tuple(state)
            new_pages.append(ent)
            with jax.named_scope("ffn"):
                x, ffn_stats, carry = self._ffn(program, params, i, x, valid,
                                                carry)
            if counting:
                with jax.named_scope("stats"):
                    counts = counts + self._step_stat_vector(
                        spec, kind, ffn_stats, seen, attended.get(kind, 0))
        with jax.named_scope("head"):
            if all_positions:
                logits = program.head(params, x)                # (S,C,V)
            else:
                last = jnp.take_along_axis(
                    x, jnp.maximum(n_valid - 1, 0)[:, None, None],
                    axis=1)[:, 0]
                logits = program.head(params, last)             # (S, V)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return ((nxt, counts) if counting else nxt), new_pages

    def _prefill_step_impl(self, params, pages, block_tables, starts,
                           tokens, n_valid):
        """Fixed-shape BATCHED chunked prefill: one call advances the
        admitted requests' next prompt chunks, a lane each (see
        :meth:`_prefill_loop`). Returns (greedy next token after each
        lane's last valid position (S,), pages)."""
        return self._prefill_loop(params, pages, block_tables, starts,
                                  tokens, n_valid, program=self.program,
                                  kinds=self.cache.config.kinds)

    def _draft_prefill_step_impl(self, params, pages, block_tables,
                                 starts, tokens, n_valid):
        """The draft model's prefill twin: same chunks, its own cache —
        keeps the draft's committed prefix in lockstep with the
        target's so proposals condition on identical context."""
        return self._prefill_loop(params, pages, block_tables, starts,
                                  tokens, n_valid,
                                  program=self.draft_program,
                                  kinds=self.draft_cache.config.kinds)

    def _verify_step_impl(self, params, pages, block_tables, starts,
                          tokens, props, n_valid):
        """The speculative VERIFY step: the batched-prefill shape is
        exactly right for k-token verification — assemble the chunk
        ``[pending, d_1 .. d_{k-1}]`` from each slot's pending token
        (S,) and the draft's proposals (S, spec_k) IN-GRAPH (so the
        step dispatches on the un-materialized draft output, no host
        round-trip between draft and verify), enter it at the slot's
        live positions, commit its K/V, and return the target's greedy
        argmax after EVERY position (S, spec_k) so the host can accept
        the longest agreeing prefix. ONE fixed-shape call verifies all
        k candidates of every slot."""
        chunk = jnp.concatenate(
            [tokens[:, None], props[:, :self.spec_k - 1]], axis=1)
        return self._prefill_loop(params, pages, block_tables, starts,
                                  chunk, n_valid, program=self.program,
                                  kinds=self.cache.config.kinds,
                                  all_positions=True)

    def _first_token_impl(self, tokens, nxt):
        """The decode block's input tokens ``(S,)`` with the tokens one
        earlier call left on the device: the first tokens of the prompts
        a prefill call finished (``nxt`` (lanes,)), or the last column of
        the decode block in flight (``nxt`` (S, decode_block)), where a
        slot goes on from the token it ended on. An entry ``-(1 + j)``
        takes lane ``j`` of that call's ``nxt``; one that codes a later
        call (``- k * S`` more) moves up a call; a token stays. Fixed
        shape for a lane count, whatever number of lanes is taken."""
        if nxt.ndim == 2:
            nxt = nxt[:, -1]
        s_tot = tokens.shape[0]
        lane = -1 - tokens
        return jnp.where(
            (lane >= 0) & (lane < s_tot),
            nxt[jnp.clip(lane, 0, nxt.shape[0] - 1)],
            jnp.where(lane >= s_tot, tokens + s_tot, tokens))

    def _copy_page_impl(self, pages, src, dst):
        """Device-side page copy (CoW of a borrowed shared tail page):
        every layer's page ``src`` duplicated into ``dst`` as the layer's
        kind copies one (whatever arrays its entry holds travel with
        their page). Fixed shape — src/dst are traced scalars, so one
        compile covers every copy."""
        return [kind.copy_page(ent[:len(kind.pools)], src, dst)
                + tuple(ent[len(kind.pools):])
                for kind, ent in zip(self.cache.config.kinds, pages)]

    def _read_page_impl(self, pages, src):
        """One page's K/V across every layer, stacked (2, L, page_size,
        H, Dh) — the migration shard unit and its WIRE format, which
        names the head axis whatever the pool's stored shape: the
        pool's folded ``(page_size, H*Dh)`` rows are the same row-major
        bytes, so the unfold moves nothing and a payload's sha256 does
        not depend on how the pool is stored. A quantized pool also
        returns the page's scale rows (2, L, page_size), carried in the
        same shard.
        ``src`` is a traced scalar: one compile covers every page ever
        snapshotted."""
        c = self.cache.config
        ks, vs, *scales = (jnp.stack([ent[j][src] for ent in pages])
                           for j in range(4 if self.quantized else 2))
        kv = jnp.stack([ks, vs]).reshape(
            2, len(pages), c.page_size, c.num_heads, c.head_dim)
        return (kv, jnp.stack(scales)) if scales else kv

    def _write_page_impl(self, pages, dst, kv, sc=None):
        """Install one migration shard (the :meth:`_read_page_impl`
        layout) into page ``dst`` of every layer, folded back to the
        pool's ``(page_size, H*Dh)`` rows — quantized shards
        carry ``sc`` and restore the scale rows alongside the int8
        page; pages donated, dst a traced scalar — one compile covers
        every restore."""
        kv = kv.reshape(kv.shape[:3] + (-1,))
        parts = (kv[0], kv[1]) + (() if sc is None else (sc[0], sc[1]))
        return [tuple(pool.at[dst].set(part[i].astype(pool.dtype))
                      for pool, part in zip(ent, parts))
                for i, ent in enumerate(pages)]
