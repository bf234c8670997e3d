"""Tier-aware continuous-batching serving subsystem.

This package is the serving-side realization of the paper's quantitative
workflow: decode is the catalog's link-saturating, latency-sensitive cell,
so it is where disaggregated-memory placement and admission decisions
matter most (cf. the CXL-pooling studies arXiv:2211.02682, 2303.06420).

The KV cache is a PHYSICAL page pool end-to-end (the default,
`EngineConfig.paged`): self-attention K/V lives as (stack, n_slots *
n_pages, page_tokens, heads, head_dim) arrays, and every jitted cell —
decode, prefill-insert, chunked prefill — reads and writes it through the
live (n_slots, n_pages) block table the pager emits. Page placement is
therefore real at the data-layout level, not an accounting overlay: the
paper's three-level local/pool byte split prices exactly the pages the
kernels gather.

POOL DTYPE (`EngineConfig.pool_dtype`): the pool payload is polymorphic.

* "fp" stores cfg.dtype bit-identically — the exact safety net; the
  engine is token-for-token equal to the contiguous layout. Parity
  tests and lanes that assert exactness pin this mode.
* "bf16" stores a 2-byte cast of the payload (fp16-class pooling).
* "int8" (the DEFAULT since the physical substrate made pool bytes
  real) BLOCK-QUANTIZES every page: the payload pool is int8 and each
  attention cache dict grows per-page float32 (scale, zero) leaves
  "k_sz"/"v_sz" of shape (stack, n_slots * n_pages, kv_heads, 2)
  (`repro.kernels.quant` layout, affine mid-range: q = round((x -
  zero)/scale), |dequant(q) - x| <= scale/2 per element). Inserts
  quantize (bucket-insert and chunk cells quantize whole pages; the
  decode cell requantizes the slot's tail page around the new token) and
  the paged kernels dequantize each gathered page in their epilogue, so
  only int8 payload plus the per-page scalars ever cross the pool link.

  Bytes per cached token (the pager's dtype-aware accounting, also in
  closed form as `core.access.kv_pool_token_bytes`):

      2 (K and V) * kv_heads * head_dim * payload_bytes * n_attn_layers
      + 2 * kv_heads * 8 / page_tokens * n_attn_layers     [int8 only]

  i.e. ~4x fewer pool bytes than an fp32 pool (~2x vs bf16) at a
  bounded logit drift — and under a FIXED local-tier byte budget the
  remote share drops further because the same HBM holds ~4x more pages
  (the serve_int8 bench lane asserts <= 0.30x of the fp16 lane's pool
  bytes at >= 0.95x tokens/s).

SPECULATIVE DECODING (`EngineConfig.speculative`, `serving/
speculative.py`): greedy decode is the serving stack's lowest-
arithmetic-intensity loop — every emitted token costs one full sweep of
the slot batch's pool-resident KV pages, which is exactly the traffic
the paper's corridor prices. Speculation raises that intensity without
changing the tokens: a PROPOSER guesses `speculative_k - 1` draft
tokens per slot ("ngram" — self-speculative suffix matching over the
slot's own history, zero parameters, zero device work; or "draft" — a
small draft model decoding ahead against its own contiguous scratch
caches, weights deterministic and shared engine-wide through
`EngineCells`), the VERIFY cell (`runtime.serve.
build_decode_verify_paged`) flattens the (slots, k) candidates to
slots*k decode rows with vector positions and k-repeated block-table
rows and scores all of them in ONE paged-decode call, and greedy
acceptance commits the longest candidate prefix matching the verify
argmaxes — `1 + accepted` tokens per slot per sweep, BIT-IDENTICAL to
plain greedy decode on fp pools by construction (int8 pools keep the
same bounded drift either way). The pager's multi-token accounting
(`KVPager.step(tokens=...)`) charges the sweep once while lengths
advance by the acceptance length; `ensure_tail_pages(lookahead=k)`
makes all k candidate write positions live+private up front, and
`KVPager.truncate` rolls the page accounting back over the rejected
tail (whose dead KV every kernel already masks and the next verify
overwrites). int8 pools switch to the PER-TOKEN sub-scale layout
(`sz_granularity="token"`, k_sz/v_sz at (stack, P, page_tokens,
kv_heads, 2)): each candidate row quantizes its own K/V rows
independently, a pure disjoint scatter — the per-page requantize
round trip would have k rows of one slot read-modify-writing the same
tail page concurrently. `ServeStats.spec` reports verify steps and the
mean acceptance length; the serve_speculative bench lane gates the
tokens/s win (>= 1.5x the greedy chat lane at equal output tokens).

SHARED-PREFIX RADIX CACHE (`EngineConfig.prefix_cache`): requests behind
the same system prompt share bit-identical prefix KV (K/V at position i
depends on token i, the weights and the rotary phase — not the suffix),
so the pager refcounts physical pages and `prefix_cache.py` keys a radix
trie on page-granular token blocks (one full `page_tokens`-token tuple
per edge; terminal partial-block nodes cover a prompt's trailing partial
page). Lifecycle: on admission the prompt is matched against the trie
and the hit's pages are guard-pinned, mapped into the slot's block table
(bucketed prefill inserts into private pages then `remap_shared`
deduplicates — the fused-scatter contract below demands uniquely owned
write targets — while chunked prefill `map_shared`s up front and starts
at the first divergent chunk, genuinely skipping the shared chunks'
compute); the trie pins its pages (`KVPager.pin`) so they outlive the
donor slot, `release` decrefs and frees only at refcount zero, and LRU
leaves are reclaimed under free-list pressure. A shared page is NEVER
written: the moment a slot's write cursor lands inside one (a shared
partial tail), `KVPager.cow_split` repoints the writer at a fresh page
and the engine's `page_copy` cell (`runtime.serve.build_page_copy`)
materializes the private copy first. int8 pools share their per-page
(scale, zero) leaves alongside the payload by construction (same
physical page ids). Capacity accounting is deduplicated — a prefix
shared by n slots occupies ONE page of budget (`phys_tiers()`,
`local/pool_bytes_used`); per-token footprint in closed form is
`core.access.kv_dedup_token_bytes`:

    (n_sharers * (n_tokens - shared) + shared) * token_bytes
        / (n_sharers * n_tokens)

PHYSICAL SUBSTRATE (`serving/substrate/`, `EngineConfig.substrate`):
the pager's local/pool tier map stops being bookkeeping and becomes
physical placement. `TierSubstrate` owns a host-resident TWIN of the
paged pool leaves (`models.blocks.paged_pool_leaves`), laid out one
tile-aligned row block per page and placed through
`runtime.sharding.named(..., memory_kind=...)` — pinned_host where the
backend supports it ("physical" mode), default memory with identical
program shapes where it doesn't ("emulated", the XLA:CPU CI fallback;
`runtime.capability.substrate_mode` resolves "auto" per backend probe).
Each decode step the engine drains the substrate: the pager's pool page
set is diffed against the twin's residency and reconciled with jitted
async transfer STREAMS — page_out (device pool -> twin, one DMA per
page into the donated twin), page_in (twin -> device, promotion), drop
(freed, no bytes move) — every stream recorded in a completion-tracked
`SubstrateLedger` whose `page_bytes` are MEASURED from the pool arrays'
nbytes. Contract (bench-gated): after every drain,
`KVPager.pool_bytes_used() == ledger.placement_bytes()` — the virtual
clock prices exactly the bytes that physically moved. Fleet handoffs
(`fleet/roles.py`) price their page copies off the same measured
number. Prefix-cache interplay: trie-pinned pages keep ref > 0, so a
shared cold prefix stays POOL-placed across donor-slot release (one
twin page however many slots map it); reclaim drops the pin and the
next drain turns the freed pages into a drop stream.

MESH-SHARDED SERVING (`runtime.serve.make_engine_cells(mesh=...)`): all
cells jit with NamedSharding in/out shardings — KV heads over the tp
axis, slots over dp for contiguous leaves, the PAGE AXIS always
unsharded (pages are gathered through the block table, which stays
replicated as do the tokens/positions the host mutates) — see
`runtime.sharding.paged_cache_pspec`. The substrate twin carries the
same partitioning (pool_pspec), so tier transfers move per-shard
without resharding. The sharded-parity CI lane forces 8 host devices
(`--xla_force_host_platform_device_count=8`) and asserts token parity
vs the single-device engine: bit-exact for fp pools, drift-bounded for
int8.

FUSED-SCATTER CONTRACT: on the kernel backends (pallas / interpret) no
serving cell issues a standalone jnp page-scatter over the pool. The
chunked-prefill cell's chunk K/V write is fused into the paged-prefill
kernel itself — the chunk tiles (int8: pre-quantized payload +
(scale, zero) rows) are kernel operands and the pool arrays are aliased
input->output (`input_output_aliases`), killing the one-full-extra
read+write of the chunk's K/V the separate scatter cost — and the
bucket prefill-insert cell lands whole pages through the same aliased
page-writer kernel (`kernels.page_io`). The reference backend keeps the
unfused scatter-then-attend oracle, and fp-mode fused-vs-unfused cache
parity is bit-for-bit (`tests/test_kernels.py` checks both, plus a
jaxpr scan asserting the fused cells contain zero scatter ops).

FLEET LAYER (`serving/fleet/`): N engines behind a `FleetRouter` — the
paper's rack-scale thesis (placement/interference policy over a SHARED
pool decides performance, sec 6-7) applied one level up, across
engines instead of across pages. The router is pure-Python
orchestration over the engines' re-entrant tick primitives (`pump` /
`advance_to` / `begin_capture` / `capture_stats`); all engines share
ONE compiled cell set and one param tree (`FleetRouter.build`), each
with its own page pool, pager and virtual clock.

* PLACEMENT PROTOCOL (`fleet/placement.py`): a policy maps (eligible
  `EngineView` snapshots, prompt tokens) -> engine_id and is notified
  via `record` once per placed request — a pure function of the views,
  so decisions are deterministic and unit-testable without engines.
  Three policies: `round_robin` (baseline; with greedy decoding the
  token streams are placement-invariant, the CI fleet-parity lane's
  gate), `kv_aware` (queue depth / slot capacity + half-weighted pool
  pressure from free physical pages, lowest-id tie-break), and
  `prefix_aware` (a router-side radix index over page-granular token
  blocks steering shared-prefix traffic to the engine whose radix trie
  already holds those pages; kv-aware fallback on cold misses).
* ROLES + PAGE-HANDOFF LEDGER (`fleet/roles.py`): disaggregated
  prefill/decode. A prefill-role engine completes chunked prefill,
  emits the first token, guard-PINS the prompt pages and parks the
  slot in the `handoff` phase; `execute_handoff` admits the request
  into a decode-role engine, allocates destination pages
  (`KVPager.admit`), copies every paged leaf (k/v + int8 scale planes)
  along the physical-page axis, prices the transfer at pool bandwidth
  on the decode engine's clock, then the source UNPINS and releases
  (`complete_handoff`). The `TransferLedger` logs pages/bytes/latency
  per transfer. Contract: pinned pages are immutable until the copy
  lands; the destination slot starts at `start_pos = prompt_len` with
  the prefill-emitted first token.
* PRIORITIES + CANCELLATION (`queue.py`): `RequestQueue` orders by
  (priority class, arrival) — single-class traces stay bit-identical
  FIFO; requests cancel eagerly or at a virtual-time deadline, are
  dropped at the queue or swept out of slots
  (`ServingEngine.sweep_cancelled` -> `KVPager.release`).
* AUTOSCALING (`fleet/autoscale.py`): queue-depth hysteresis
  (watermarks + patience + cooldown) activates/drains engines between
  min/max; the decision loop is side-effect-free and unit-tested. A
  scale-down drains the victim engine IMMEDIATELY through the fault
  layer's migration path below — pools verified fully free, nothing
  lingers.

FAULT TOLERANCE (`serving/faults.py`, `FleetConfig.faults`): the
paper's pooled tier is a shared, link-attached resource — transfers
flake, engines die, budgets shrink — so the serving stack treats
failure as a first-class, DETERMINISTIC input rather than an
environmental accident.

* FAULT PLANS: a `FaultPlan` (seedable, frozen) names every injection
  site up front — substrate page_in/page_out transfer failure and
  fleet handoff flaking (per-site Philox streams keyed on
  crc32(site), so one site's draw sequence never depends on another's
  interleaving), engine kill/stall at decode step t, pool-page-budget
  shrink, whole-pool loss. `FaultInjector` wraps a plan with consumed
  one-shot triggers and counters; `make_plan("chaos_smoke")` et al.
  name the canonical scenarios. Every chaos run is exactly replayable.
* PREEMPTION / MIGRATION: `ServingEngine.freeze_slot` evicts a live
  slot wholesale — pages pinned and force-placed POOL (or spilled:
  released outright), a `FrozenSlot` snapshot keeps the request,
  emitted history and last token; `thaw_slot` remaps the pages and
  resumes bit-exactly. `adopt` migrates a frozen/displaced request to
  ANOTHER engine by teacher-forced refill: bucketed re-prefill of the
  prompt, then the emitted history is force-fed one token per decode
  step (other slots' clocks parked, writes masked) — greedy decode is
  deterministic per request, so the rebuilt KV is the KV, and on fp
  pools the resumed stream is bit-identical to the never-failed one.
  Admission uses the same lever: when a prompt cannot get pages, the
  lowest-priority active slot is frozen-with-spill instead of
  deadlocking the queue (`_ensure_pages_for`), and `_thaw_tick`
  restores frozen work FIFO ahead of lower-priority arrivals.
* RECOVERY POLICY: substrate transfers and handoffs retry with
  exponential backoff (`_attempt_transfer`), every failed attempt
  logged in the ledgers as a "retry" stream — wasted link bytes move,
  placement unchanged — and fatal past `max_retries`. The router's
  watchdog marks an engine dead when `pump` reports it (or a stall
  outlives `watchdog_s`), then `_recover_engine` evacuates it: queued
  work re-routes with ORIGINAL arrivals, in-flight slots re-adopt on
  survivors, and the dead engine's pool is asserted fully free (zero
  refcounts, empty placement). Pool-loss degrades the engine to
  local-only paging with tightened admission (`degrade_pool`).
  `ServeStats.faults` / `FleetStats.faults` carry the whole bill —
  retries, retry_bytes, re-prefilled tokens, preempt/restore counts,
  backoff seconds — and stay EMPTY ({}) on fault-free runs; the
  chaos-parity CI lane and the bench_fleet fault lane gate the
  headline contract: a fleet with one engine killed mid-decode and
  10% transfer flaking emits bit-identical tokens to the fault-free
  run on fp pools.

Architecture (one module per concern):

  queue.py    — `Request` / `RequestQueue` and deterministic arrival
                scenarios (chat / long-context / bursty /
                shared-prefix).
  telemetry.py — host spans (`span` = `jax.profiler.TraceAnnotation`,
                recorded only under a profiler trace; `SPANS` lists
                every name the engine, pager and substrate write) and
                the program clock `now_ns()` on which each request's
                `Request.wall` stamps (slot taken, first chunk
                dispatched) are taken; `engine.pump`'s `t_ns` stat
                ties that clock to the trace's.
  faults.py   — deterministic fault injection: `FaultPlan` (seedable
                scenario description), `FaultInjector` (per-site
                Philox streams + one-shot triggers + counters), and
                the named `PLANS` registry — see the FAULT TOLERANCE
                section above.
  prefix_cache.py — the shared-prefix radix trie over the pager's
                physical pages: page-block keying, LRU leaf eviction,
                free-list-pressure reclaim (see the section above).
  substrate/  — the physical memory substrate: `TierSubstrate` (host
                pool twin + jitted transfer streams, drained per decode
                step) and `SubstrateLedger` (completion-tracked events,
                measured bytes, placement accounting) — see the
                PHYSICAL SUBSTRATE section above.
  speculative.py — speculative-decoding proposers and the greedy
                acceptance ladder: `ngram_propose` (self-speculative
                suffix matching, stateless) and `accept_greedy` (longest
                candidate prefix matching the verify argmaxes). The
                engine drives them per verify step; see the SPECULATIVE
                DECODING section above.
  batcher.py  — fixed-slot continuous batching: requests flow through
                `n_slots` decode lanes; admission on free slot, release on
                completion; inactive slots mask their cache writes by
                parking the write cursor out of range. With chunked
                prefill, a slot also has a `prefill` phase: occupied but
                outside the decode batch while its prompt advances one
                chunk at a time.
  kv_pager.py — the single page ALLOCATOR plus tier-aware placement: a
                shared free list hands each valid (slot, page) a physical
                page id; `block_table()` is the logical->physical map the
                engine's paged cells and the paged pallas kernels
                (`kernels/decode_attention/paged.py`,
                `kernels/flash_attention/paged_prefill.py`) chase;
                `phys_tiers()` tags every physical page local or pool.
                Hot tail pages stay local, the cold prefix is evicted to
                the pool tier by the paper's placement engine
                (`core.placement`) under the hot/cold decode traffic
                model shared with the workload catalog (`core.access`).
                `static` is the first-touch no-paging baseline; `none`
                the all-local control. With `PagerConfig.prefetch` set,
                cold-prefix page-in is prediction-driven (`repro.
                prefetch` predictor zoo): staged pool transfers overlap
                compute, demand page-ins serialize.
  engine.py   — the event loop over fixed-shape jitted cells built by
                `runtime.serve.make_engine_cells` (prefill per prompt
                bucket, one slot-batched greedy decode cell with per-slot
                positions over the page pool, page-scatter insert cells,
                and — on attention-only archs — a chunked-prefill cell
                that interleaves page-aligned prompt chunks with decode
                steps so prefill never serializes a long prompt against
                the in-flight batch; `ServeStats.decode_stall` measures
                exactly that gap). The admission controller throttles
                batch growth at the M/D/1-knee corridor budget
                (`core.interference.corridor_budget`) using cached
                `core.quantify.profile_for` submission-time metrics,
                tightened online by the pager's measured prefetch-excess
                pool traffic.

No recompilation occurs at steady state: every cell's shapes are fixed at
build time, and admissions/completions/page churn/chunk progress only flip
mask/position/block-table ARRAYS — `tests/test_serving.py` asserts the
executable-cache sizes stay constant. CI gates this subsystem three ways:
the tier-1 fast lane runs the serving tests on every push; the
paged-engine-parity lane replays `scripts/dev_serve.py --paged` with
interpret-mode pallas kernels, asserting token-for-token equality between
the paged engine and the contiguous naive loop; and the benchmark smoke
job runs `benchmarks/bench_serving` (chat / long-context / bursty /
chunked-prefill) and uploads the BENCH artifacts, including the
long-context pager-vs-static comparison that must show the tier-aware
pager cutting the remote (pool-tier) access share at equal tokens/s and
the chunked-prefill lane that must show a lower p95 decode-step stall
than serialized prefill.
"""

from repro.serving.batcher import ContinuousBatcher, Slot
from repro.serving.engine import (
    AdmissionController,
    EngineConfig,
    INT8_TOKEN_AGREEMENT,
    ServeStats,
    ServingEngine,
)
from repro.serving.faults import FaultInjector, FaultPlan, PLANS, make_plan
from repro.serving.kv_pager import KVPager, PagerConfig, StepTraffic
from repro.serving.prefix_cache import PrefixCache, PrefixHit
from repro.serving.speculative import accept_greedy, ngram_propose
from repro.serving.substrate import SubstrateLedger, TierSubstrate
from repro.serving.queue import (
    Request,
    RequestQueue,
    SCENARIOS,
    bursty_stream,
    chat_stream,
    long_context_stream,
    make_scenario,
    multi_tenant_stream,
    shared_prefix_stream,
)
from repro.serving import fleet

__all__ = [
    "AdmissionController",
    "ContinuousBatcher",
    "EngineConfig",
    "FaultInjector",
    "FaultPlan",
    "INT8_TOKEN_AGREEMENT",
    "KVPager",
    "PLANS",
    "PagerConfig",
    "PrefixCache",
    "PrefixHit",
    "Request",
    "RequestQueue",
    "SCENARIOS",
    "ServeStats",
    "ServingEngine",
    "Slot",
    "StepTraffic",
    "SubstrateLedger",
    "TierSubstrate",
    "accept_greedy",
    "bursty_stream",
    "chat_stream",
    "fleet",
    "long_context_stream",
    "make_plan",
    "make_scenario",
    "multi_tenant_stream",
    "ngram_propose",
    "shared_prefix_stream",
]
